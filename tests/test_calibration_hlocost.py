"""Calibration collector (paper §5.1.1) and the trip-counted HLO cost model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.calibration import Calibrator, SiteStats
from repro.utils import hlo_cost


# ----------------------------------------------------------- calibration

def test_welford_matches_numpy():
    rng = np.random.default_rng(0)
    st = SiteStats()
    chunks = [rng.normal(2.0, 1.5, 997) for _ in range(5)]
    for c in chunks:
        st.update(c)
    allv = np.concatenate(chunks)
    assert st.std == pytest.approx(allv.std(), rel=1e-6)
    assert st.min == pytest.approx(allv.min())
    assert st.count == allv.size


def test_calibrator_observe_and_params():
    rng = np.random.default_rng(1)
    cal = Calibrator()
    for _ in range(4):
        x = jnp.asarray(rng.normal(0, 1.8, (4, 128)), jnp.float32)
        cal.observe("attn/0", x)
    sigma = cal.sigma("attn/0")
    assert 1.0 < sigma < 2.5
    p = cal.exaq_params("attn/0", 2, rule="paper")
    assert p.clip == pytest.approx(-1.66 * sigma - 1.85, rel=1e-6)
    pn = cal.naive_params("attn/0", 2)
    assert pn.clip < 0


def test_calibrator_mask_excludes_invalid():
    cal = Calibrator()
    x = jnp.zeros((2, 8), jnp.float32)
    x = x.at[:, 4:].set(-1e9)  # junk that a mask must exclude
    mask = jnp.arange(8)[None, :] < 4
    cal.observe("s", x, where=jnp.broadcast_to(mask, x.shape))
    assert cal.sigma("s") == pytest.approx(0.0, abs=1e-6)


def test_calibrator_json_roundtrip():
    cal = Calibrator()
    cal.observe("a", jnp.asarray(np.random.default_rng(0).normal(0, 1, (2, 64)), jnp.float32))
    cal2 = Calibrator.from_json(cal.to_json())
    assert cal2.sigma("a") == pytest.approx(cal.sigma("a"))


# ------------------------------------------------------------- hlo cost

def _flops_of(f, *args):
    c = jax.jit(f).lower(*args).compile()
    return hlo_cost.analyze(c.as_text(), 1)


def test_trip_counted_scan_flops():
    x = jnp.zeros((128, 128), jnp.float32)
    w = jnp.zeros((6, 128, 128), jnp.float32)

    def scanned(x, w):
        return jax.lax.scan(lambda c, wi: (c @ wi, None), x, w)[0]

    cs = _flops_of(scanned, x, w)
    assert cs.flops == pytest.approx(6 * 2 * 128**3)
    # XLA's own analysis counts the body once — the bug this module fixes
    xla = jax.jit(scanned).lower(x, w).compile().cost_analysis()["flops"]
    # rel=1e-4: XLA adds a handful of scalar flops for the loop counter
    assert xla == pytest.approx(2 * 128**3, rel=1e-4)


def test_nested_scan_flops():
    x = jnp.zeros((64, 64), jnp.float32)
    w = jnp.zeros((4, 64, 64), jnp.float32)

    def nested(x, w):
        def outer(c, _):
            return jax.lax.scan(lambda cc, wi: (cc @ wi, None), c, w)[0], None
        return jax.lax.scan(outer, x, None, length=3)[0]

    assert _flops_of(nested, x, w).flops == pytest.approx(3 * 4 * 2 * 64**3)


def test_bytes_model_add():
    x = jnp.zeros((256, 256), jnp.float32)
    cs = _flops_of(lambda a, b: a + b, x, x)
    assert cs.bytes == pytest.approx(3 * 256 * 256 * 4)  # 2 reads + 1 write


def test_dynamic_slice_charged_at_slice_granularity():
    big = jnp.zeros((64, 1024), jnp.float32)

    def f(big):
        def body(c, i):
            return c + jax.lax.dynamic_slice_in_dim(big, i, 1, 0)[0], None
        return jax.lax.scan(body, jnp.zeros(1024), jnp.arange(64))[0]

    cs = _flops_of(f, big)
    # 64 iterations x ~3 row-sized touches (slice r/w + add) << full-array x 64
    assert cs.bytes < 64 * 1024 * 4 * 8
    assert cs.bytes > 64 * 1024 * 4  # but not free either


def test_collective_parse_on_sharded_module():
    # single-device module has no collectives
    x = jnp.zeros((128, 128), jnp.float32)
    cs = _flops_of(lambda a: a @ a, x)
    assert cs.collective_total == 0.0
