import os
import sys
import zlib

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import pytest  # noqa: E402

# ----------------------------------------------------------- seed discipline
#
# Every randomized test derives its seed from the PYTEST_SEED env var (default
# 0) XOR a stable hash of the test's nodeid, so (a) the whole suite is
# reproducible run-to-run, (b) each test draws an independent stream, and
# (c) CI can diversify coverage by exporting a different PYTEST_SEED per
# scheduled run. The fixture prints the derivation; pytest shows captured
# output only for failing tests, so the repro line surfaces exactly when
# it is needed.

PYTEST_SEED = int(os.environ.get("PYTEST_SEED", "0"))

try:  # optional dep: the property suite degrades to a seeded fallback driver
    from hypothesis import settings as _hyp_settings

    _hyp_settings.register_profile("default", max_examples=50, deadline=None)
    _hyp_settings.register_profile(
        "long", max_examples=int(os.environ.get("HYPOTHESIS_MAX_EXAMPLES", "500")),
        deadline=None,
    )
    _hyp_settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "default"))
except ImportError:
    pass


def derive_seed(nodeid: str, base: int = PYTEST_SEED) -> int:
    """Per-test seed: crc32 of the nodeid XOR the suite-wide PYTEST_SEED."""
    return zlib.crc32(nodeid.encode()) ^ (base & 0xFFFFFFFF)


@pytest.fixture
def test_seed(request):
    """Reproducible per-test seed (int). Prints the repro recipe so a failing
    test's report carries everything needed to replay it."""
    seed = derive_seed(request.node.nodeid)
    print(f"[seed] PYTEST_SEED={PYTEST_SEED} nodeid={request.node.nodeid!r} "
          f"-> derived seed {seed} (replay: PYTEST_SEED={PYTEST_SEED} pytest "
          f"'{request.node.nodeid}')")
    return seed


@pytest.fixture
def rng(test_seed):
    """numpy Generator seeded per-test from PYTEST_SEED (see ``test_seed``)."""
    import numpy as np

    return np.random.default_rng(test_seed)


@pytest.fixture(scope="session")
def smoke_model():
    """Trained 2-layer smoke model (the bench's recipe, briefly overfit on a
    periodic stream so greedy margins are confident, not argmax noise).
    Session-scoped and shared by the differential-fuzz, SLA-scheduler, and
    chaos suites — training dominates their cost."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))
    from bench_serving import make_smoke_model

    cfg, params, loss = make_smoke_model("yi-6b", train_steps=60)
    assert loss < 0.2, f"smoke model failed to overfit (loss {loss})"
    return cfg, params


@pytest.fixture
def quantize_pool():
    """fp pool -> (int8 codes, per-(block, kv-head) scales) the way the write
    path would store it (DESIGN.md §6): scale = margin * amax / 127. The ONE
    test-side encoding of the write-path contract, shared by the paged-decode
    and paged-prefill kernel suites."""
    import jax.numpy as jnp

    from repro.kernels.ops import KV_QMAX, KV_SCALE_MARGIN, kv_quantize

    def _quantize(pk, pv):
        def q(pool):  # (..., N, KV, bs, D) fp -> codes + (..., N, KV) scales
            amax = jnp.max(jnp.abs(pool), axis=(-2, -1))
            scale = KV_SCALE_MARGIN * amax / KV_QMAX
            return kv_quantize(pool, scale[..., None, None]), scale

        qk, ks = q(pk.astype(jnp.float32))
        qv, vs = q(pv.astype(jnp.float32))
        return qk, qv, ks, vs

    return _quantize


@pytest.fixture
def quantize_pool_int4():
    """fp pool -> (packed uint8 nibbles, fp32 block scales, uint8 sub codes)
    the way the int4 write path would store it (DESIGN.md §10). The test-side
    twin of the scatter's first-write seeding: block scale = margin * amax /
    7, sub code = ceil(15 * margin * amax_sub / (7 * block_scale)) in [1, 15]
    (0 where the sub-block is all-zero). Null block 0 is zeroed everywhere —
    payload, scale, sub codes — matching a fresh pool's reserved sink."""
    import jax.numpy as jnp

    from repro.kernels.ops import (
        kv4_effective_scale,
        kv4_num_sub,
        kv4_quantize,
        kv4_sub_block,
        kv4_write_block_scales,
        kv4_write_sub_scales,
    )

    def _quantize(pk, pv):
        def q(pool):  # (..., N, KV, bs, D) fp -> packed + scales
            *lead, N, KV, bs, D = pool.shape
            sub_bs = kv4_sub_block(bs)
            n_sub = kv4_num_sub(bs)
            pool = pool.astype(jnp.float32)
            amax = jnp.max(jnp.abs(pool), axis=(-2, -1))  # (..., N, KV)
            scale = kv4_write_block_scales(amax, jnp.zeros_like(amax))
            amax_sub = jnp.max(
                jnp.abs(pool.reshape(*lead, N, KV, n_sub, sub_bs, D)), axis=(-2, -1)
            )  # (..., N, KV, n_sub)
            codes = kv4_write_sub_scales(amax_sub, scale, jnp.zeros(amax_sub.shape, jnp.uint8))
            per_tok = jnp.repeat(kv4_effective_scale(scale, codes), sub_bs, axis=-1)
            packed = kv4_quantize(pool, per_tok)
            # block 0 is the reserved null sink: unset grid, zero payload
            return (packed.at[..., 0, :, :, :].set(0), scale.at[..., 0, :].set(0.0),
                    codes.at[..., 0, :, :].set(0))

        qk, ks, ksub = q(pk)
        qv, vs, vsub = q(pv)
        return qk, qv, ks, vs, ksub, vsub

    return _quantize
