"""Cache/pool PartitionSpec policy: divisibility fallbacks and rank locks.

Two failure classes this file pins down (DESIGN.md §9):

  * divisibility edge cases — a mesh axis that does not divide the
    corresponding tensor dim must degrade to a *replicated* (or
    sequence-parallel) spec, never crash and never emit an invalid spec;
  * spec-rank drift — every spec function's rank must keep matching the
    cache tensors it describes (``init_cache`` / ``init_block_pool`` /
    ``_ssm_cache``), or ``device_put`` fails at runtime on the first
    sharded engine.

The spec functions only consult ``mesh.shape`` / ``mesh.axis_names``, so a
stub mesh exercises every tp/dp combination without multi-device jax.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import get_config  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.runtime import sharding as shd  # noqa: E402


class StubMesh:
    """Duck-typed mesh: shape dict + axis_names, no devices."""

    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


def _dense_cfg(kv_heads=2):
    return get_config("yi-6b").reduced(
        num_kv_heads=kv_heads, num_heads=2 * kv_heads)


# ------------------------------------------------------------ validate_spec


def test_validate_spec_keeps_divisible_drops_indivisible():
    mesh = StubMesh(data=2, model=4)
    assert shd.validate_spec(P(None, "model"), (3, 8), mesh) == P(None, "model")
    assert shd.validate_spec(P(None, "model"), (3, 6), mesh) == P(None, None)
    assert shd.validate_spec(P("data", "model"), (6, 6), mesh) == P("data", None)


def test_validate_spec_tuple_axes_use_product():
    mesh = StubMesh(pod=2, data=3, model=2)
    spec = P(("pod", "data"), None)
    assert shd.validate_spec(spec, (12, 5), mesh) == spec  # 12 % 6 == 0
    assert shd.validate_spec(spec, (8, 5), mesh) == P(None, None)  # 8 % 6 != 0


def test_validate_spec_pads_short_specs():
    mesh = StubMesh(model=2)
    out = shd.validate_spec(P("model"), (4, 3, 5), mesh)
    assert out == P("model", None, None)
    assert len(out) == 3


# ------------------------------------------------- pool/cache spec policy


def test_block_pool_spec_shards_kv_heads_when_divisible():
    cfg = _dense_cfg(kv_heads=4)
    assert shd.block_pool_spec(cfg, StubMesh(data=1, model=2)) == \
        P(None, None, "model", None, None)
    assert shd.block_scale_spec(cfg, StubMesh(data=1, model=2)) == \
        P(None, None, "model")


def test_block_pool_spec_falls_back_to_replicated():
    """tp=4 over 2 kv heads: the pool must replicate, not crash — the engine
    then runs the single-shard kernel path (ops._tp_mesh returns None)."""
    cfg = _dense_cfg(kv_heads=2)
    mesh = StubMesh(data=2, model=4)
    assert shd.block_pool_spec(cfg, mesh) == P(None, None, None, None, None)
    assert shd.block_scale_spec(cfg, mesh) == P(None, None, None)


def test_cache_specs_fall_back_to_sequence_parallel():
    """The rectangular/slot caches have a sequence axis to fall back on:
    kv-heads indivisible -> shard sequence over 'model' instead."""
    cfg = _dense_cfg(kv_heads=2)
    div, indiv = StubMesh(data=2, model=2), StubMesh(data=2, model=4)
    assert shd.cache_spec(cfg, div) == P(None, ("data",), "model", None, None)
    assert shd.cache_spec(cfg, indiv) == P(None, ("data",), None, "model", None)
    assert shd.slot_cache_spec(cfg, div) == P(None, ("data",), "model", None, None)
    assert shd.slot_cache_spec(cfg, indiv) == P(None, ("data",), None, "model", None)


def test_ssm_cache_specs_divisibility():
    cfg = get_config("mamba2-1.3b").reduced()
    tp_ok = StubMesh(data=1, model=2)
    specs = shd.ssm_cache_specs(cfg, tp_ok)
    if cfg.ssm_heads % 2 == 0:
        assert specs["ssm"][2] == "model"
    huge = StubMesh(data=1, model=10**9)  # divides nothing
    specs = shd.ssm_cache_specs(cfg, huge)
    assert specs["ssm"][2] is None
    assert specs["conv"][3] is None


# ------------------------------------------------------------- rank locks


def test_kv_cache_spec_rank_matches_init_cache():
    cfg = _dense_cfg()
    mesh = StubMesh(data=1, model=1)
    cache = jax.eval_shape(
        lambda: build_model(cfg).init_cache(2, 32, jnp.bfloat16))
    assert len(shd.cache_spec(cfg, mesh)) == len(cache["k"].shape) == 5
    assert len(shd.slot_cache_spec(cfg, mesh)) == len(cache["v"].shape) == 5


def test_block_pool_spec_rank_matches_init_block_pool():
    cfg = _dense_cfg()
    m = build_model(cfg)
    mesh = StubMesh(data=1, model=1)
    pool = jax.eval_shape(lambda: m.init_block_pool(8, 16, jnp.int8))
    assert len(shd.block_pool_spec(cfg, mesh)) == len(pool["k"].shape) == 5
    assert len(shd.block_scale_spec(cfg, mesh)) == len(pool["k_scale"].shape) == 3
    assert pool["k_scale"].shape == pool["k"].shape[:3]  # (L, N, KV) planes


def test_ssm_cache_spec_ranks_match_ssm_cache():
    cfg = get_config("mamba2-1.3b").reduced()
    mesh = StubMesh(data=1, model=1)
    cache = jax.eval_shape(
        lambda: build_model(cfg).init_cache(2, 32, jnp.bfloat16))
    specs = shd.ssm_cache_specs(cfg, mesh)
    assert len(specs["conv"]) == len(cache["conv"].shape) == 4
    assert len(specs["ssm"]) == len(cache["ssm"].shape) == 5


def test_hybrid_cache_shardings_pad_stacked_ranks():
    """Hybrid caches stack (n_groups, period, ...) on top of the flat specs;
    cache_shardings must tail-align (prefix-pad) every spec, so the batch
    axis keeps its 'data' sharding one position deeper."""
    from repro.runtime import serve as serve_rt

    cfg = get_config("zamba2-2.7b").reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    cache = jax.eval_shape(lambda: build_model(cfg).init_cache(2, 32, jnp.bfloat16))
    shardings = serve_rt.cache_shardings(cfg, mesh, cache)
    for name in ("conv", "ssm", "k", "v"):
        assert len(shardings[name].spec) == len(cache[name].shape), name


# ------------------------------------------------- trace-time TP dispatch


def test_tp_mesh_discovery_follows_pool_spec_policy():
    """ops._tp_mesh and block_pool_spec must agree: the kernel dispatch goes
    tensor-parallel exactly when the pool spec shards the kv-head axis."""
    from repro.kernels import ops

    assert ops._tp_mesh(4) is None  # no ambient mesh

    div = StubMesh(data=1, model=2)
    with shd.activation_rules(div, {}):
        assert ops._tp_mesh(4) is div
        assert ops._tp_mesh(2) is div
        assert ops._tp_mesh(3) is None  # indivisible -> single-shard path

    no_model = StubMesh(data=4)
    with shd.activation_rules(no_model, {}):
        assert ops._tp_mesh(4) is None

    tp1 = StubMesh(data=1, model=1)
    with shd.activation_rules(tp1, {}):
        assert ops._tp_mesh(4) is None  # tp=1: shard_map would be pure overhead

    assert shd.current_mesh() is None  # context restored


def test_use_mesh_roundtrip():
    mesh = StubMesh(data=1, model=2)
    assert shd.current_mesh() is None
    with shd.use_mesh(mesh):
        assert shd.current_mesh() is mesh
    assert shd.current_mesh() is None
    with shd.use_mesh(None):
        assert shd.current_mesh() is None
