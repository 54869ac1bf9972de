"""Fused Pallas paged-decode EXAQ attention vs the gather reference
(DESIGN.md §3, fused paged decode): ragged/GQA parity matrix, dead-tail
clamping in ``gather_block_kv``, the bytes-moved model, and bit-exact greedy
parity through ``PagedEngine`` — at fp32/bf16, on the int8 per-block-scaled
pool (DESIGN.md §6), and on the packed-int4 sub-block-scaled pool
(DESIGN.md §10), whose fused paths must match the *dequantizing* gather
oracle and whose engine-level greedy tokens must track the fp32 pool's.
All kernels run in interpret mode on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import exaq_params
from repro.kernels import ops
from repro.kernels.exaq_paged_attention import decode_tile_tokens, paged_decode_bytes_model

RNG = np.random.default_rng(42)


def _pool_setup(S, KV, bs, MB, D, *, dtype=jnp.float32, seed=0, layers=1):
    """Random pool stacked over ``layers`` (L, N, KV, bs, D) + disjoint
    per-slot tables (ids permuted so table order differs from pool order —
    a bug that ignores the table shows up)."""
    rng = np.random.default_rng(seed)
    N = 1 + S * MB
    pk = jnp.asarray(rng.normal(0, 1, (layers, N, KV, bs, D)), dtype)
    pv = jnp.asarray(rng.normal(0, 1, (layers, N, KV, bs, D)), dtype)
    ids = rng.permutation(np.arange(1, N))[: S * MB].reshape(S, MB)
    return pk, pv, jnp.asarray(ids, jnp.int32)


# int8 pools quantize via the shared `quantize_pool` fixture (conftest.py).


@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("bits", [2, 3])
def test_fused_matches_gather_reference_gqa(group, bits):
    """GQA group sizes 1/4/8: fused kernel == global-grid gather reference."""
    KV, bs, MB, D = 2, 8, 4, 64
    H, S = KV * group, 3
    p = exaq_params(1.5, bits)
    q = jnp.asarray(RNG.normal(0, 1, (S, H, 1, D)), jnp.float32)
    pk, pv, tbl = _pool_setup(S, KV, bs, MB, D, seed=group)
    lens = jnp.asarray([5, 17, MB * bs], jnp.int32)
    got = ops.paged_decode_attention(q, pk, pv, tbl, lens, p, D**-0.5, use_kernel=True)
    want = ops.paged_decode_attention(q, pk, pv, tbl, lens, p, D**-0.5, use_kernel=False)
    assert got.shape == (S, H, 1, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_fused_ragged_lens_edges():
    """Ragged kv_lens: empty slot (len 0), exactly one block, exactly on a
    block boundary, one past a boundary, full table."""
    S, H, KV, bs, MB, D = 5, 4, 2, 8, 3, 32
    p = exaq_params(1.0, 2)
    q = jnp.asarray(RNG.normal(0, 1, (S, H, 1, D)), jnp.float32)
    pk, pv, tbl = _pool_setup(S, KV, bs, MB, D, seed=1)
    lens = jnp.asarray([0, bs, 2 * bs, 2 * bs + 1, MB * bs], jnp.int32)
    got = ops.paged_decode_attention(q, pk, pv, tbl, lens, p, D**-0.5, use_kernel=True)
    want = ops.paged_decode_attention(q, pk, pv, tbl, lens, p, D**-0.5, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # a slot with no live KV attends to nothing and outputs exactly zero
    assert float(jnp.abs(got[0]).max()) == 0.0


def test_fused_single_block_sequences():
    """MB == 1: the whole cache is one block per slot (init decode state)."""
    S, H, KV, bs, D = 2, 8, 8, 16, 128
    p = exaq_params(2.0, 2)
    q = jnp.asarray(RNG.normal(0, 1, (S, H, 1, D)), jnp.float32)
    pk, pv, tbl = _pool_setup(S, KV, bs, 1, D, seed=2)
    lens = jnp.asarray([1, bs], jnp.int32)
    got = ops.paged_decode_attention(q, pk, pv, tbl, lens, p, D**-0.5, use_kernel=True)
    want = ops.paged_decode_attention(q, pk, pv, tbl, lens, p, D**-0.5, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_fused_shared_prefix_blocks():
    """Two slots whose tables name the SAME prefix blocks (the prefix-cache
    layout): per-slot results match gathering each window independently."""
    S, H, KV, bs, MB, D = 2, 4, 2, 8, 4, 64
    p = exaq_params(1.0, 2)
    q = jnp.asarray(RNG.normal(0, 1, (S, H, 1, D)), jnp.float32)
    pk, pv, _ = _pool_setup(S, KV, bs, MB, D, seed=3)
    tbl = jnp.asarray([[1, 2, 3, 4], [1, 2, 5, 6]], jnp.int32)  # shared 2-block prefix
    lens = jnp.asarray([3 * bs + 2, 4 * bs], jnp.int32)
    got = ops.paged_decode_attention(q, pk, pv, tbl, lens, p, D**-0.5, use_kernel=True)
    want = ops.paged_decode_attention(q, pk, pv, tbl, lens, p, D**-0.5, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_fused_bf16_pool():
    """Serving dtype: bf16 pool, fp32 q — fused and reference agree (both
    promote K/V to fp32 before the dots)."""
    S, H, KV, bs, MB, D = 3, 4, 4, 8, 3, 64
    p = exaq_params(1.5, 2)
    q = jnp.asarray(RNG.normal(0, 1, (S, H, 1, D)), jnp.float32)
    pk, pv, tbl = _pool_setup(S, KV, bs, MB, D, dtype=jnp.bfloat16, seed=4)
    lens = jnp.asarray([7, 20, 24], jnp.int32)
    got = ops.paged_decode_attention(q, pk, pv, tbl, lens, p, D**-0.5, use_kernel=True)
    want = ops.paged_decode_attention(q, pk, pv, tbl, lens, p, D**-0.5, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ------------------------------------------------------------- int8 KV pool

@pytest.mark.parametrize("group", [1, 4, 8])
def test_fused_int8_matches_dequantizing_gather_gqa(group, quantize_pool):
    """GQA 1/4/8 at int8: the fused kernel (scalar-prefetched scales, dequant
    in VMEM) matches the dequantizing gather oracle to <= 1e-5 — both read
    the same codes and the same per-(block, kv-head) scales (DESIGN.md §6)."""
    KV, bs, MB, D = 2, 8, 4, 64
    H, S = KV * group, 3
    p = exaq_params(1.5, 2)
    q = jnp.asarray(RNG.normal(0, 1, (S, H, 1, D)), jnp.float32)
    pk, pv, tbl = _pool_setup(S, KV, bs, MB, D, seed=10 + group)
    qk, qv, ks, vs = quantize_pool(pk, pv)
    lens = jnp.asarray([5, 17, MB * bs], jnp.int32)
    got = ops.paged_decode_attention(q, qk, qv, tbl, lens, p, D**-0.5,
                                     k_scale=ks, v_scale=vs, use_kernel=True)
    want = ops.paged_decode_attention(q, qk, qv, tbl, lens, p, D**-0.5,
                                      k_scale=ks, v_scale=vs, use_kernel=False)
    assert got.shape == (S, H, 1, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_fused_int8_close_to_fp_oracle(quantize_pool):
    """Quantization error is bounded by the scale grid: int8 outputs stay
    within a few dequant ulps of the fp32-pool result on the same values."""
    S, H, KV, bs, MB, D = 2, 4, 2, 8, 3, 32
    p = exaq_params(1.0, 2)
    q = jnp.asarray(RNG.normal(0, 1, (S, H, 1, D)), jnp.float32)
    pk, pv, tbl = _pool_setup(S, KV, bs, MB, D, seed=11)
    qk, qv, ks, vs = quantize_pool(pk, pv)
    lens = jnp.asarray([7, 2 * bs], jnp.int32)
    got = ops.paged_decode_attention(q, qk, qv, tbl, lens, p, D**-0.5,
                                     k_scale=ks, v_scale=vs, use_kernel=True)
    want = ops.paged_decode_attention(q, pk, pv, tbl, lens, p, D**-0.5, use_kernel=False)
    # attention output is a convex combination of dequantized V rows, so the
    # error is bounded by V's dequant step (scale/2) plus the K-side weight
    # perturbation — small multiples of the grid, not tight equality
    tol = 10 * float(jnp.max(vs)) / 2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)


def test_fused_int8_dead_tail_and_null_block_zero(quantize_pool):
    """Ragged lens at int8: empty slot reads only the null block (scale 0,
    payload 0) and outputs exactly zero; boundary lens match the oracle."""
    S, H, KV, bs, MB, D = 5, 4, 2, 8, 3, 32
    p = exaq_params(1.0, 2)
    q = jnp.asarray(RNG.normal(0, 1, (S, H, 1, D)), jnp.float32)
    pk, pv, tbl = _pool_setup(S, KV, bs, MB, D, seed=12)
    qk, qv, ks, vs = quantize_pool(pk, pv)
    lens = jnp.asarray([0, bs, 2 * bs, 2 * bs + 1, MB * bs], jnp.int32)
    got = ops.paged_decode_attention(q, qk, qv, tbl, lens, p, D**-0.5,
                                     k_scale=ks, v_scale=vs, use_kernel=True)
    want = ops.paged_decode_attention(q, qk, qv, tbl, lens, p, D**-0.5,
                                      k_scale=ks, v_scale=vs, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert float(jnp.abs(got[0]).max()) == 0.0


def test_gather_requires_scales_iff_int8(quantize_pool):
    pk, pv, tbl = _pool_setup(1, 2, 8, 2, 16, seed=13)
    qk, qv, ks, vs = quantize_pool(pk, pv)
    with pytest.raises(ValueError):
        ops.gather_block_kv(qk, qv, tbl)  # int8 without scales
    with pytest.raises(ValueError):
        ops.gather_block_kv(qk, qv, tbl, None, ks, None)  # int8 missing v_scale
    with pytest.raises(ValueError):
        ops.gather_block_kv(pk, pv, tbl, None, ks, vs)  # fp with scales
    p = exaq_params(1.0, 2)
    lens = jnp.asarray([8], jnp.int32)
    with pytest.raises(ValueError):
        ops.paged_decode_attention(jnp.zeros((1, 2, 1, 16)), qk, qv, tbl, lens, p, 0.25,
                                   k_scale=ks, use_kernel=True)  # fused missing v_scale


# ------------------------------------------------------- packed int4 KV pool

@pytest.mark.parametrize("group", [1, 4, 8])
def test_fused_int4_matches_dequantizing_gather_gqa(group, quantize_pool_int4):
    """GQA 1/4/8 at packed int4: the fused kernel (in-VMEM nibble unpack,
    scalar-prefetched block scales + sub codes) matches the dequantizing
    gather oracle to <= 1e-5 — both decode the same bytes through
    ``kv4_effective_scale``'s exact multiply order (DESIGN.md §10)."""
    KV, bs, MB, D = 2, 8, 4, 64
    H, S = KV * group, 3
    p = exaq_params(1.5, 2)
    q = jnp.asarray(RNG.normal(0, 1, (S, H, 1, D)), jnp.float32)
    pk, pv, tbl = _pool_setup(S, KV, bs, MB, D, seed=20 + group)
    qk, qv, ks, vs, ksub, vsub = quantize_pool_int4(pk, pv)
    assert qk.dtype == jnp.uint8 and qk.shape[-1] == D // 2
    lens = jnp.asarray([5, 17, MB * bs], jnp.int32)
    got = ops.paged_decode_attention(q, qk, qv, tbl, lens, p, D**-0.5,
                                     k_scale=ks, v_scale=vs, k_sub=ksub, v_sub=vsub,
                                     use_kernel=True)
    want = ops.paged_decode_attention(q, qk, qv, tbl, lens, p, D**-0.5,
                                      k_scale=ks, v_scale=vs, k_sub=ksub, v_sub=vsub,
                                      use_kernel=False)
    assert got.shape == (S, H, 1, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_fused_int4_narrow_head_dim_padding(quantize_pool_int4):
    """D // 2 below the 128-lane tile: the packed pool pads to a full lane
    tile and the q/out planes pad to twice that — garbage K padding lanes
    must be zero-killed (a bug here poisons every score), V garbage must be
    sliced away. D=6 makes the padding dominate the payload."""
    S, H, KV, bs, MB, D = 2, 2, 2, 4, 2, 6
    p = exaq_params(1.0, 2)
    q = jnp.asarray(RNG.normal(0, 1, (S, H, 1, D)), jnp.float32)
    pk, pv, tbl = _pool_setup(S, KV, bs, MB, D, seed=24)
    qk, qv, ks, vs, ksub, vsub = quantize_pool_int4(pk, pv)
    lens = jnp.asarray([3, MB * bs], jnp.int32)
    got = ops.paged_decode_attention(q, qk, qv, tbl, lens, p, D**-0.5,
                                     k_scale=ks, v_scale=vs, k_sub=ksub, v_sub=vsub,
                                     use_kernel=True)
    want = ops.paged_decode_attention(q, qk, qv, tbl, lens, p, D**-0.5,
                                      k_scale=ks, v_scale=vs, k_sub=ksub, v_sub=vsub,
                                      use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_fused_int4_dead_tail_and_null_block_zero(quantize_pool_int4):
    """Ragged lens at int4: the empty slot reads only the null block (scale
    0, sub codes 0, payload 0) and outputs exactly zero; block-boundary lens
    match the oracle. Sub-code-0 tails decoding to exact zero is the codec
    property test_kv_packing pins; this asserts the kernel honors it."""
    S, H, KV, bs, MB, D = 5, 4, 2, 8, 3, 32
    p = exaq_params(1.0, 2)
    q = jnp.asarray(RNG.normal(0, 1, (S, H, 1, D)), jnp.float32)
    pk, pv, tbl = _pool_setup(S, KV, bs, MB, D, seed=25)
    qk, qv, ks, vs, ksub, vsub = quantize_pool_int4(pk, pv)
    lens = jnp.asarray([0, bs, 2 * bs, 2 * bs + 1, MB * bs], jnp.int32)
    got = ops.paged_decode_attention(q, qk, qv, tbl, lens, p, D**-0.5,
                                     k_scale=ks, v_scale=vs, k_sub=ksub, v_sub=vsub,
                                     use_kernel=True)
    want = ops.paged_decode_attention(q, qk, qv, tbl, lens, p, D**-0.5,
                                      k_scale=ks, v_scale=vs, k_sub=ksub, v_sub=vsub,
                                      use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert float(jnp.abs(got[0]).max()) == 0.0


def test_fused_int4_close_to_fp_oracle(quantize_pool_int4):
    """int4 error tracks the sub-block grid: outputs stay within small
    multiples of the effective scale step of the fp32-pool result."""
    S, H, KV, bs, MB, D = 2, 4, 2, 8, 3, 32
    p = exaq_params(1.0, 2)
    q = jnp.asarray(RNG.normal(0, 1, (S, H, 1, D)), jnp.float32)
    pk, pv, tbl = _pool_setup(S, KV, bs, MB, D, seed=26)
    qk, qv, ks, vs, ksub, vsub = quantize_pool_int4(pk, pv)
    lens = jnp.asarray([7, 2 * bs], jnp.int32)
    got = ops.paged_decode_attention(q, qk, qv, tbl, lens, p, D**-0.5,
                                     k_scale=ks, v_scale=vs, k_sub=ksub, v_sub=vsub,
                                     use_kernel=True)
    want = ops.paged_decode_attention(q, pk, pv, tbl, lens, p, D**-0.5, use_kernel=False)
    # V's dequant step is at most the block scale (code 15/15); K noise
    # perturbs convex weights — small multiples of the grid bound it
    tol = 10 * float(jnp.max(vs)) / 2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)


def test_int4_requires_sub_planes_and_fp_forbids_them(quantize_pool_int4):
    pk, pv, tbl = _pool_setup(1, 2, 8, 2, 16, seed=27)
    qk, qv, ks, vs, ksub, vsub = quantize_pool_int4(pk, pv)
    with pytest.raises(ValueError):
        ops.gather_block_kv(qk, qv, tbl, None, ks, vs)  # packed without subs
    with pytest.raises(ValueError):
        ops.gather_block_kv(qk, qv, tbl, None, ks, vs, ksub, None)  # missing v_sub
    with pytest.raises(ValueError):
        ops.gather_block_kv(pk, pv, tbl, None, None, None, ksub, vsub)  # fp with subs
    p = exaq_params(1.0, 2)
    lens = jnp.asarray([8], jnp.int32)
    with pytest.raises(ValueError):
        ops.paged_decode_attention(jnp.zeros((1, 2, 1, 16)), qk, qv, tbl, lens, p, 0.25,
                                   k_scale=ks, v_scale=vs, k_sub=ksub,
                                   use_kernel=True)  # fused missing v_sub


# ------------------------------------------------- live-bounded tile loop

# The kernel walks each slot's live tokens in tiles of decode_tile_tokens():
# 512 tokens (8 blocks) at bs 64. A 10-entry table is 1.25 tiles, so the
# last tile runs past the table's end and the clamp/mask paths both fire.
TILE_BS, TILE_MB = 64, 10


@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "int4"])
def test_fused_tile_edges_match_gather(kv_dtype, group, quantize_pool, quantize_pool_int4):
    """kv_lens at T-1, T, T+1, 1 and the full table, over a table width T/bs
    does not divide, with every slot sharing slot 0's first 5 blocks (the
    prefix-cache layout): the fused kernel matches the (dequantizing) gather
    oracle to <= 1e-5 for every pool dtype and GQA group."""
    KV, D = 2, 64
    H = KV * group
    dtype = {"bf16": jnp.bfloat16, "int8": jnp.int8, "int4": "int4"}[kv_dtype]
    T = decode_tile_tokens(block_size=TILE_BS, max_blocks=TILE_MB, kv_heads=KV, head_dim=D,
                           kv_dtype=dtype)
    assert T == 512 and TILE_MB % (T // TILE_BS)
    lens = jnp.asarray([T - 1, T, T + 1, 1, TILE_MB * TILE_BS], jnp.int32)
    S = lens.shape[0]
    p = exaq_params(1.5, 2)
    q = jnp.asarray(RNG.normal(0, 1, (S, H, 1, D)), jnp.float32)
    pk, pv, tbl = _pool_setup(S, KV, TILE_BS, TILE_MB, D, seed=30 + group)
    tbl = tbl.at[1:, :5].set(tbl[0, :5])
    planes = {}
    if kv_dtype == "bf16":
        pk, pv = pk.astype(jnp.bfloat16), pv.astype(jnp.bfloat16)
    elif kv_dtype == "int8":
        pk, pv, planes["k_scale"], planes["v_scale"] = quantize_pool(pk, pv)
    else:
        pk, pv, *scales = quantize_pool_int4(pk, pv)
        planes = dict(zip(("k_scale", "v_scale", "k_sub", "v_sub"), scales))
    got = ops.paged_decode_attention(q, pk, pv, tbl, lens, p, D**-0.5, use_kernel=True,
                                     **planes)
    want = ops.paged_decode_attention(q, pk, pv, tbl, lens, p, D**-0.5, use_kernel=False,
                                      **planes)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "int4"])
def test_decode_tile_rule(kv_dtype):
    """The tile follows the shapes: 512 tokens where the double buffers fit
    4 MiB, fewer kv heads' worth of bytes for wide pools, never past the
    table, at least one block."""
    dtype = {"bf16": jnp.bfloat16, "int8": jnp.int8, "int4": "int4"}[kv_dtype]

    def tile(**kw):
        args = dict(block_size=16, max_blocks=256, kv_heads=4, head_dim=128, kv_dtype=dtype)
        return decode_tile_tokens(**{**args, **kw})

    assert tile() == 512  # the yi-6b.chat shape
    assert tile(max_blocks=8) == 128  # capped at the table
    assert tile(block_size=1024, max_blocks=4) == 1024  # one block at least
    # 32 kv heads at bf16 need 512 KiB of double buffers a block: 8 fit
    assert tile(kv_heads=32) == {"bf16": 128, "int8": 256, "int4": 256}[kv_dtype]


# --------------------------------------------------------- gather dead tails

def test_gather_block_kv_clamps_dead_tail_to_null_block():
    """With kv_lens, table entries past ceil(len/bs) gather the null block
    instead of whatever stale block ids pad the table — and the live prefix
    is untouched."""
    S, KV, bs, MB, D = 2, 2, 8, 4, 16
    pk, pv, tbl = _pool_setup(S, KV, bs, MB, D, seed=5)
    lens = jnp.asarray([bs + 3, 0], jnp.int32)  # slot0: 2 live blocks; slot1: none
    kg, vg = ops.gather_block_kv(pk, pv, tbl, lens)
    kg_all, _ = ops.gather_block_kv(pk, pv, tbl)
    # live blocks identical to the unclamped gather
    np.testing.assert_array_equal(np.asarray(kg[0, :, : 2 * bs]), np.asarray(kg_all[0, :, : 2 * bs]))
    # dead tail reads block 0 (the null block), not the table's padding ids
    tail = np.asarray(kg[0, :, 2 * bs :]).reshape(KV, MB - 2, bs, D)
    for b in range(MB - 2):
        np.testing.assert_array_equal(tail[:, b], np.asarray(pk[0, 0]))
    tail1 = np.asarray(vg[1]).reshape(KV, MB, bs, D)
    for b in range(MB):
        np.testing.assert_array_equal(tail1[:, b], np.asarray(pv[0, 0]))


def test_repeat_kv_shared_implementation():
    """The single shared GQA repeat: identity at group 1, interleaved copy
    otherwise, and both historical call signatures route through it."""
    from repro.models.attention import _repeat_kv as model_repeat

    x = jnp.asarray(RNG.normal(0, 1, (2, 3, 5, 4)), jnp.float32)
    assert ops.repeat_kv(x, 1) is x
    r = ops.repeat_kv(x, 2)
    assert r.shape == (2, 6, 5, 4)
    np.testing.assert_array_equal(np.asarray(r[:, 0]), np.asarray(r[:, 1]))
    np.testing.assert_array_equal(np.asarray(r[:, 2]), np.asarray(x[:, 1]))
    assert model_repeat is ops.repeat_kv  # one implementation, two call sites
    q = jnp.zeros((2, 6, 5, 4))
    kr, vr = ops._repeat_kv(q, x, x)
    np.testing.assert_array_equal(np.asarray(kr), np.asarray(r))
    np.testing.assert_array_equal(np.asarray(vr), np.asarray(r))


# ------------------------------------------------------------- bytes model

def test_bytes_model_2x_at_half_occupancy():
    """Acceptance: modeled decode-step KV bytes-read drop >= 2x vs the
    gather path at 50% average occupancy."""
    S, MB, bs = 8, 32, 16
    lens = np.full((S,), MB * bs // 2, np.int64)  # 50% occupancy
    m = paged_decode_bytes_model(slots=S, kv_heads=8, max_blocks=MB, block_size=bs,
                                 head_dim=128, kv_lens=lens)
    assert m["bytes_reduction_x"] >= 2.0
    # sanity: gather reads live blocks + writes/reads the dense rectangle
    # (x K+V), fused is (2K + 1V) over live blocks only
    assert m["gather_then_read_bytes"] == (m["live_blocks"] + 2 * S * MB) * 2 * m["block_bytes"]
    assert m["fused_pool_read_bytes"] == 3 * m["live_blocks"] * m["block_bytes"]


def test_bytes_model_kv_dtype_element_sizes():
    """kv_dtype parameterization: fused bytes scale with the element size
    (int8 pays the per-block scale reads; its gather path prices the dense
    dequantized copy at fp32), and int8 cuts >= 1.8x vs bf16."""
    S, MB, bs, KVH, D = 8, 32, 16, 8, 128
    lens = np.full((S,), MB * bs // 2, np.int64)
    kw = dict(slots=S, kv_heads=KVH, max_blocks=MB, block_size=bs, head_dim=D, kv_lens=lens)
    m32 = paged_decode_bytes_model(kv_dtype="fp32", **kw)
    m16 = paged_decode_bytes_model(kv_dtype="bf16", **kw)
    m8 = paged_decode_bytes_model(kv_dtype="int8", **kw)
    assert m32["fused_pool_read_bytes"] == 2 * m16["fused_pool_read_bytes"]
    assert m8["block_bytes"] == KVH * (bs * D + 4)  # payload + 4B scale per head
    assert m16["fused_pool_read_bytes"] / m8["fused_pool_read_bytes"] >= 1.8
    # the gather path's dense intermediate is dequantized fp32 for int8 pools
    assert m8["gather_then_read_bytes"] == (
        m8["live_blocks"] * m8["block_bytes"] + 2 * S * MB * KVH * bs * D * 4) * 2


def test_bytes_model_int4_block_bytes_and_reductions():
    """Packed int4 block pricing (DESIGN.md §10): half-byte payload plus the
    fp32 block scale and one sub code per sub-block, per kv head. Acceptance
    floors: >= 1.8x fewer fused pool bytes than int8, >= 3.5x than bf16."""
    from repro.kernels.ops import kv4_num_sub

    S, MB, bs, KVH, D = 8, 32, 16, 8, 128
    lens = np.full((S,), MB * bs // 2, np.int64)
    kw = dict(slots=S, kv_heads=KVH, max_blocks=MB, block_size=bs, head_dim=D, kv_lens=lens)
    m16 = paged_decode_bytes_model(kv_dtype="bf16", **kw)
    m8 = paged_decode_bytes_model(kv_dtype="int8", **kw)
    m4 = paged_decode_bytes_model(kv_dtype="int4", **kw)
    n_sub = kv4_num_sub(bs)
    assert m4["block_bytes"] == KVH * (bs * D // 2 + 4 + n_sub)
    assert m8["fused_pool_read_bytes"] / m4["fused_pool_read_bytes"] >= 1.8
    assert m16["fused_pool_read_bytes"] / m4["fused_pool_read_bytes"] >= 3.5
    # the gather path's dense intermediate is dequantized fp32 for int4 too
    assert m4["gather_then_read_bytes"] == (
        m4["live_blocks"] * m4["block_bytes"] + 2 * S * MB * KVH * bs * D * 4) * 2


# ------------------------------------------------- the pool the layer loop carries

LAYER_HEAD_DIMS = {"bf16": 128, "int8": 128, "int4": 256}  # lane-aligned pool widths


def _quantized(kv_dtype, pk, pv, quantize_pool, quantize_pool_int4):
    """(pool_k, pool_v, scale planes by name) of an fp pool at ``kv_dtype``."""
    if kv_dtype == "bf16":
        return pk.astype(jnp.bfloat16), pv.astype(jnp.bfloat16), {}
    if kv_dtype == "int8":
        qk, qv, ks, vs = quantize_pool(pk, pv)
        return qk, qv, {"k_scale": ks, "v_scale": vs}
    qk, qv, *scales = quantize_pool_int4(pk, pv)
    return qk, qv, dict(zip(("k_scale", "v_scale", "k_sub", "v_sub"), scales))


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "int4"])
def test_layer_indexed_kernels_match_sliced_layer(kernel, kv_dtype, quantize_pool,
                                                  quantize_pool_int4):
    """Both fused kernels and their gather references read layer ``layer``
    of the whole stacked pool exactly as they read that layer sliced out
    alone: bit-identical outputs, at lane-aligned widths, where the kernels
    index the stacked pool in place instead of slicing it."""
    S, KV, bs, MB, L, layer = 3, 2, 8, 4, 3, 1
    D = LAYER_HEAD_DIMS[kv_dtype]
    p = exaq_params(1.5, 2)
    pk, pv, tbl = _pool_setup(S, KV, bs, MB, D, seed=40, layers=L)
    pk, pv, planes = _quantized(kv_dtype, pk, pv, quantize_pool, quantize_pool_int4)
    sliced = {k: a[layer][None] for k, a in planes.items()}
    if kernel == "decode":
        q = jnp.asarray(RNG.normal(0, 1, (S, 2 * KV, 1, D)), jnp.float32)
        lens = jnp.asarray([5, 17, MB * bs], jnp.int32)

        def run(k, v, lyr, use_kernel, scales):
            return ops.paged_decode_attention(q, k, v, tbl, lens, p, D**-0.5, layer=lyr,
                                              use_kernel=use_kernel, **scales)
    else:
        q = jnp.asarray(RNG.normal(0, 1, (1, 2 * KV, 6, D)), jnp.float32)

        def run(k, v, lyr, use_kernel, scales):
            return ops.paged_prefill_attention(q, k, v, tbl[0], jnp.int32(11), p, D**-0.5,
                                               layer=lyr, use_kernel=use_kernel, **scales)
    for use_kernel in (True, False):
        got = run(pk, pv, jnp.int32(layer), use_kernel, planes)
        want = run(pk[layer][None], pv[layer][None], jnp.int32(0), use_kernel, sliced)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # another layer's bytes are another answer: the index is honoured
        other = run(pk, pv, jnp.int32(0), use_kernel, planes)
        assert not np.array_equal(np.asarray(other), np.asarray(got))


def _sliced_layer_loop(self, params, qstate, h, pool, run_layer):
    """The per-layer reference of ``Model._pool_layer_loop``: every layer's
    pool planes cut out as scan inputs, handed to the layer as a one-layer
    pool, and stacked back as scan outputs."""
    keys = tuple(k for k in ("k", "v", "k_scale", "v_scale", "k_sub", "v_sub") if k in pool)

    def body(h, xs):
        lp, clip, *planes = xs
        h, planes, y = run_layer(lp, clip, jnp.int32(0), h, *(a[None] for a in planes))
        return h, (tuple(a[0] for a in planes), y)

    xs = (self._stack(params), qstate["attn_clip"], *(pool[k] for k in keys))
    h, (planes, ys) = jax.lax.scan(body, h, xs)
    return h, dict(zip(keys, planes)), ys


def _random_pool(model, kv_dtype, n_blocks, bs, seed):
    """A pool whose every byte is drawn, so that a write lands visibly and
    an untouched byte can be told from a rewritten one."""
    rng = np.random.default_rng(seed)
    pool = model.init_block_pool(n_blocks, bs, {"bf16": jnp.bfloat16, "int8": jnp.int8,
                                                 "int4": "int4"}[kv_dtype])
    out = {}
    for k, a in pool.items():
        if k.endswith("_scale"):
            out[k] = jnp.asarray(rng.uniform(0.01, 0.1, a.shape), a.dtype)
        elif k.endswith("_sub") or a.dtype in (jnp.uint8, jnp.int8):
            lo, hi = (1, 16) if k.endswith("_sub") else (-100, 100) if a.dtype == jnp.int8 else (0, 256)
            out[k] = jnp.asarray(rng.integers(lo, hi, a.shape), a.dtype)
        else:
            out[k] = jnp.asarray(rng.normal(0, 1, a.shape), a.dtype)
    return out


@pytest.mark.parametrize("program", ["decode", "prefill", "verify"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "int4"])
@pytest.mark.parametrize("arch", ["yi-6b", "deepseek-moe-16b"])
def test_carried_pool_matches_per_layer_reference(arch, kv_dtype, program, monkeypatch):
    """A decode step, a prefill chunk and a verify chunk over the pool their
    layer loop carries leave every layer's blocks, and return logits,
    bit-identical to the per-layer reference that slices each layer out and
    stacks it back — on the fused kernels and on the gather path — and
    leave every row they do not write untouched, in every layer."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.models.model import Model

    bs, MB, S = 8, 4, 3
    cfg = get_config(arch).reduced(num_layers=3, head_dim=LAYER_HEAD_DIMS[kv_dtype])
    N = 1 + S * MB
    rng = np.random.default_rng(50)
    tables = jnp.asarray(rng.permutation(np.arange(1, N)).reshape(S, MB), jnp.int32)
    if program == "decode":
        lens = jnp.asarray([5, 17, 9], jnp.int32)
        active = jnp.asarray([True, True, False])
        rows = [(int(tables[s, lens[s] // bs]) if active[s] else 0, int(lens[s] % bs))
                for s in range(S)]
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (S, 1)), jnp.int32)

        def call(m, params, pool):
            out = m.decode_step_paged(params, tokens, pool, tables, lens, active,
                                      block_size=bs)
            return out[:2]
    else:
        start, C = 11, 8
        live = C if program == "verify" else 6  # prefill pads its last two rows
        rows = [(int(tables[0, (start + i) // bs]) if i < live else 0, (start + i) % bs)
                for i in range(C)]
        blk_t = jnp.asarray([b for b, _ in rows], jnp.int32)
        off_t = jnp.asarray([o for _, o in rows], jnp.int32)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, C)), jnp.int32)

        def call(m, params, pool):
            if program == "verify":
                return m.verify_paged_chunk(params, tokens, pool, tables[0], jnp.int32(start),
                                            blk_t, off_t)
            return m.prefill_paged_chunk(params, tokens, pool, tables[0], jnp.int32(start),
                                         jnp.int32(live), blk_t, off_t, block_size=bs)

    written = np.zeros((N, bs), bool)
    for b, o in rows:
        written[b, o] = True
    for fused in (True, False):
        m = build_model(cfg.with_quant(softmax_impl="exaq", bits=2, use_fused_kernel=fused))
        params = m.init(jax.random.PRNGKey(1), jnp.float32)
        pool = _random_pool(m, kv_dtype, N, bs, seed=51)
        got_logits, got = jax.jit(lambda p, q: call(m, p, q))(params, pool)
        with monkeypatch.context() as mp:
            mp.setattr(Model, "_pool_layer_loop", _sliced_layer_loop)
            want_logits, want = jax.jit(lambda p, q: call(m, p, q))(params, pool)
        np.testing.assert_array_equal(np.asarray(got_logits), np.asarray(want_logits))
        assert got.keys() == want.keys() == pool.keys()
        for k in pool:
            new, old = np.asarray(got[k]), np.asarray(pool[k])
            np.testing.assert_array_equal(new, np.asarray(want[k]))
            # outside the rows written (and the null block, the sink of
            # padded and inactive rows) every layer's bytes are as they were
            if k in ("k", "v"):
                keep = ~written[:, None, :]  # (N, 1, bs) over (N, KV, bs, W)
                keep = np.broadcast_to(keep[None, ..., None], new.shape).copy()
            else:
                hit = np.zeros((N,), bool)
                hit[[b for b, _ in rows]] = True
                keep = np.broadcast_to((~hit).reshape((1, N) + (1,) * (new.ndim - 2)),
                                       new.shape).copy()
            keep[:, 0] = False
            np.testing.assert_array_equal(new[keep], old[keep])
            if k in ("k", "v"):  # and every live row written is new
                changed = (new != old).any(axis=(0, 2, 4))  # (N, bs)
                assert all(changed[b, o] for b, o in rows if b)


# ------------------------------------------------------- engine greedy parity

def test_paged_engine_fused_matches_gather_greedy():
    """Bit-exact greedy parity through PagedEngine: the fused kernel and the
    gather reference decode the same trace to the same tokens (both are
    global-grid EXAQ; DESIGN.md §3)."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.runtime.engine import PagedEngine

    cfg = get_config("yi-6b").reduced(num_layers=2).with_quant(softmax_impl="exaq", bits=2)
    params = build_model(cfg).init(jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(21)
    spec = [(7, 6), (19, 4), (5, 8)]
    prompts = [rng.integers(0, cfg.vocab_size, n) for n, _ in spec]

    outs = {}
    for fused in (False, True):
        eng = PagedEngine(cfg, params, max_slots=2, max_seq=48, steps_per_sync=4,
                          block_size=8, prefill_chunk=8, seed=0, fused=fused)
        uids = [eng.submit(p, g) for p, (_, g) in zip(prompts, spec)]
        res = eng.run()
        outs[fused] = [res[u].tokens for u in uids]
        # the tile counters count the fused kernel's walk only; a 48-token
        # table is one tile, so every slot of every step walks exactly one
        st = eng.stats
        steps = st["decode_steps"] if fused else 0
        assert st["decode_kv_tiles"] == st["decode_kv_tiles_full"] == 2 * steps
    assert outs[True] == outs[False]


@pytest.mark.parametrize("cache_dtype", [jnp.int8, "int4"], ids=["int8", "int4"])
def test_paged_engine_quantized_fused_matches_gather_greedy(cache_dtype):
    """Engine-level greedy parity on quantized pools: the fused kernel and
    the gather reference dequantize the same codes with the same scales
    (int8: per-block, DESIGN.md §6; int4: block x sub-block grid, §10), so
    paged decode emits identical tokens either way."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.runtime.engine import PagedEngine

    cfg = get_config("yi-6b").reduced(num_layers=2).with_quant(softmax_impl="exaq", bits=2)
    params = build_model(cfg).init(jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(21)
    spec = [(7, 6), (19, 4), (5, 8)]
    prompts = [rng.integers(0, cfg.vocab_size, n) for n, _ in spec]

    outs = {}
    for fused in (False, True):
        eng = PagedEngine(cfg, params, max_slots=2, max_seq=48, steps_per_sync=4,
                          block_size=8, prefill_chunk=8, seed=0, fused=fused,
                          cache_dtype=cache_dtype)
        uids = [eng.submit(p, g) for p, (_, g) in zip(prompts, spec)]
        res = eng.run()
        outs[fused] = [res[u].tokens for u in uids]
        # the tile counters count the fused kernel's walk only; a 48-token
        # table is one tile, so every slot of every step walks exactly one
        st = eng.stats
        steps = st["decode_steps"] if fused else 0
        assert st["decode_kv_tiles"] == st["decode_kv_tiles_full"] == 2 * steps
    assert outs[True] == outs[False]


@pytest.fixture(scope="module")
def trained_periodic_model():
    """2-layer model briefly overfit on a periodic token stream (~10 s). A
    *trained* head is required for quantization-agreement claims — random-init
    argmax margins sit below any quantizer's noise floor (same reason
    bench_serving overfits its smoke model). Returns (cfg, params, prompts):
    EXAQ-configured inference cfg and in-distribution prompt prefixes."""
    from repro.configs import get_config
    from repro.optim.adamw import AdamW
    from repro.runtime.train import init_train_state, make_train_step

    base = get_config("yi-6b").reduced(num_layers=2)
    opt = AdamW(lr=3e-3)
    state = init_train_state(base.with_quant(softmax_impl="exact"), opt, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(base.with_quant(softmax_impl="exact"), opt))
    T, period, tok0 = 32, 7, 5
    seq = np.arange(T + 1) % period + tok0
    batch = {
        "tokens": jnp.asarray(np.stack([np.roll(seq, -s)[:T] for s in range(8)]), jnp.int32),
        "labels": jnp.asarray(np.stack([np.roll(seq, -s)[1 : T + 1] for s in range(8)]), jnp.int32),
    }
    for _ in range(40):
        state, _ = step(state, batch)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), state["params"])
    cfg = base.with_quant(softmax_impl="exaq", bits=2)
    pattern = np.arange(40) % period + tok0
    prompts = [pattern[:n] for n in (9, 14, 6)]
    return cfg, params, prompts


def _greedy_pool_run(cfg, params, prompts, cache_dtype):
    from repro.runtime.engine import PagedEngine

    eng = PagedEngine(cfg, params, max_slots=2, max_seq=48, steps_per_sync=4,
                      block_size=8, prefill_chunk=8, seed=0, cache_dtype=cache_dtype)
    uids = [eng.submit(p, 8) for p in prompts]
    res = eng.run()
    return [res[u].tokens for u in uids], eng.kv_pool_bytes


def test_paged_engine_int8_matches_fp32_pool_greedy(trained_periodic_model):
    """fp32 pool vs int8 pool through the same PagedEngine trace: the
    per-block-scaled quantization error sits far below greedy argmax margins,
    so the token-match rate is asserted >= 99% (DESIGN.md §6)."""
    cfg, params, prompts = trained_periodic_model
    ref, fp32_bytes = _greedy_pool_run(cfg, params, prompts, jnp.float32)
    got, int8_bytes = _greedy_pool_run(cfg, params, prompts, jnp.int8)
    agree = np.concatenate([np.asarray(a) == np.asarray(b) for a, b in zip(ref, got)])
    assert agree.mean() >= 0.99
    # int8 payload + fp32 scales: ~4x smaller than the fp32 pool
    assert fp32_bytes > 3.5 * int8_bytes


def test_paged_engine_int4_matches_fp32_pool_greedy(trained_periodic_model):
    """fp32 pool vs packed-int4 pool on the same trace: the block x sub-block
    scale grid (DESIGN.md §10) keeps 4-bit noise below trained greedy margins
    (acceptance: >= 99% token agreement), at a pool footprint >= 1.8x smaller
    than int8 and >= 7x smaller than fp32."""
    cfg, params, prompts = trained_periodic_model
    ref, fp32_bytes = _greedy_pool_run(cfg, params, prompts, jnp.float32)
    got, int4_bytes = _greedy_pool_run(cfg, params, prompts, "int4")
    agree = np.concatenate([np.asarray(a) == np.asarray(b) for a, b in zip(ref, got)])
    assert agree.mean() >= 0.99
    _, int8_bytes = _greedy_pool_run(cfg, params, prompts, jnp.int8)
    assert int8_bytes > 1.8 * int4_bytes
    assert fp32_bytes > 7.0 * int4_bytes


@pytest.mark.parametrize("cache_dtype", [jnp.int8, "int4"], ids=["int8", "int4"])
def test_slot_engine_rejects_quantized(cache_dtype):
    from repro.configs import get_config
    from repro.runtime.engine import Engine

    cfg = get_config("yi-6b").reduced(num_layers=2)
    with pytest.raises(ValueError):
        Engine(cfg, params=None, max_slots=1, max_seq=16, cache_dtype=cache_dtype)


def test_paged_engine_fused_requires_exaq():
    from repro.configs import get_config
    from repro.runtime.engine import PagedEngine

    cfg = get_config("yi-6b").reduced(num_layers=2).with_quant(softmax_impl="exact")
    with pytest.raises(ValueError):
        PagedEngine(cfg, params=None, max_slots=1, max_seq=16, fused=True)
