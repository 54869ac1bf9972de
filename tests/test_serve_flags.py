"""launch/serve.py flag-combination validation (no devices, no model)."""

import os
import sys
from argparse import Namespace

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.serve import validate_serve_args  # noqa: E402


def _args(**kw):
    base = dict(paged=False, fused=None, impl="exaq", kv_dtype="bf16", dp=1, tp=1,
                online=False, priority_classes=1, deadline_ms=0, max_inflight=0,
                spec_k=0, temperature=0.0)
    base.update(kw)
    return Namespace(**base)


def test_defaults_pass():
    validate_serve_args(_args())
    validate_serve_args(_args(paged=True, fused=True, kv_dtype="int8", dp=2, tp=2),
                        device_count=4)
    validate_serve_args(_args(paged=True, online=True, priority_classes=3,
                              deadline_ms=250, max_inflight=8))
    validate_serve_args(_args(paged=True, spec_k=4))


@pytest.mark.parametrize("kw,msg", [
    (dict(fused=True), "--paged"),
    (dict(fused=False), "--paged"),
    (dict(paged=True, fused=True, impl="exact"), "--impl exaq"),
    (dict(kv_dtype="int8"), "--paged"),
    (dict(dp=2), "--paged"),
    (dict(tp=2), "--paged"),
    (dict(dp=0), ">= 1"),
    (dict(tp=-1), ">= 1"),
    (dict(online=True), "--paged"),
    (dict(paged=True, online=True, dp=2), "--dp"),
    (dict(paged=True, online=True, priority_classes=0), ">= 1"),
    (dict(paged=True, online=True, deadline_ms=-1), ">= 0"),
    (dict(paged=True, online=True, max_inflight=-4), ">= 0"),
    (dict(paged=True, priority_classes=2), "--online"),
    (dict(paged=True, deadline_ms=100), "--online"),
    (dict(paged=True, max_inflight=4), "--online"),
    (dict(spec_k=4), "--paged"),
    (dict(paged=True, spec_k=-1), ">= 0"),
    (dict(paged=True, spec_k=4, temperature=0.8), "greedy-only"),
])
def test_rejections_name_the_fix(kw, msg):
    with pytest.raises(SystemExit, match=msg):
        validate_serve_args(_args(**kw))


def test_device_count_check():
    with pytest.raises(SystemExit, match="needs 8 devices"):
        validate_serve_args(_args(paged=True, dp=4, tp=2), device_count=4)
    validate_serve_args(_args(paged=True, dp=4, tp=2), device_count=8)
    # no device_count given -> the mesh builder checks at construction instead
    validate_serve_args(_args(paged=True, dp=64, tp=64))


def test_no_fused_flag_is_paged_only_but_impl_agnostic():
    validate_serve_args(_args(paged=True, fused=False, impl="exact"))


def test_compile_cache_follows_env_else_checkout(monkeypatch):
    """Importing the library sets no compile cache; the entry points' helper
    defers to JAX_COMPILATION_CACHE_DIR when set and otherwise points JAX at
    the fixed ``.jax_cache/`` of the checkout."""
    import jax

    import repro.launch.serve  # noqa: F401
    from repro.launch.compile_cache import CHECKOUT, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    assert before == os.environ.get("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax-cache")
    assert enable_compile_cache() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert enable_compile_cache() == str(CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT / ".jax_cache")
        assert (CHECKOUT / "chip_smoke.py").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
