"""Fused Pallas paged-prefill EXAQ attention vs the gather-then-attend oracle
(DESIGN.md §7): chunk-boundary/GQA parity matrix, chunk-splitting
bit-exactness vs a one-shot window, shared-prefix (CoW) tables, the int8
per-block-scaled pool with fresh-block scale seeding (DESIGN.md §6), the
prefill bytes model, and bit-exact greedy parity through a full
``PagedEngine`` prefill+decode trace. All kernels run in interpret mode on
CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import exaq_params
from repro.kernels import ops
from repro.kernels.exaq_paged_prefill import paged_prefill_bytes_model

RNG = np.random.default_rng(7)


def _window_setup(KV, bs, MB, D, *, dtype=jnp.float32, seed=0, layers=1):
    """Random pool stacked over ``layers`` (L, N, KV, bs, D) + one request's
    table (ids permuted so table order differs from pool order — a bug that
    ignores the table shows up)."""
    rng = np.random.default_rng(seed)
    N = 1 + 2 * MB
    pk = jnp.asarray(rng.normal(0, 1, (layers, N, KV, bs, D)), dtype)
    pv = jnp.asarray(rng.normal(0, 1, (layers, N, KV, bs, D)), dtype)
    tbl = jnp.asarray(rng.permutation(np.arange(1, N))[:MB], jnp.int32)
    return pk, pv, tbl


# int8 pools quantize via the shared `quantize_pool` fixture (conftest.py).

# chunk geometries straddling block boundaries (bs = 8, MB = 4):
#   chunk 1 at the very start; chunk 1 mid-block; chunk crossing one
#   boundary; chunk landing exactly on a boundary; chunk spanning several
#   blocks; chunk == whole prompt (one-shot)
BOUNDARY_CASES = [(0, 1), (5, 1), (6, 5), (8, 8), (3, 18), (0, 29)]


@pytest.mark.parametrize("start,C", BOUNDARY_CASES)
def test_fused_matches_gather_oracle_chunk_boundaries(start, C):
    KV, bs, MB, D = 2, 8, 4, 32
    H = 2 * KV
    p = exaq_params(1.5, 2)
    pk, pv, tbl = _window_setup(KV, bs, MB, D, seed=start * 37 + C)
    q = jnp.asarray(RNG.normal(0, 1, (1, H, C, D)), jnp.float32)
    got = ops.paged_prefill_attention(q, pk, pv, tbl, jnp.int32(start), p, D**-0.5,
                                      use_kernel=True)
    want = ops.paged_prefill_attention(q, pk, pv, tbl, jnp.int32(start), p, D**-0.5,
                                       use_kernel=False)
    assert got.shape == (1, H, C, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("bits", [2, 3])
def test_fused_matches_gather_oracle_gqa(group, bits):
    """GQA group sizes 1/4/8: one kv head's query group forms the q rows."""
    KV, bs, MB, D = 2, 8, 3, 64
    H, C, start = KV * group, 6, 9
    p = exaq_params(1.5, bits)
    pk, pv, tbl = _window_setup(KV, bs, MB, D, seed=group)
    q = jnp.asarray(RNG.normal(0, 1, (1, H, C, D)), jnp.float32)
    got = ops.paged_prefill_attention(q, pk, pv, tbl, jnp.int32(start), p, D**-0.5,
                                      use_kernel=True)
    want = ops.paged_prefill_attention(q, pk, pv, tbl, jnp.int32(start), p, D**-0.5,
                                       use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_chunked_equals_one_shot_window():
    """Splitting a prefill into chunks is bit-identical to one shot: the
    two-pass combine anchors every row at its true global max, so the rows
    of a later chunk match the same rows of a single whole-window call
    (DESIGN.md §2/§7)."""
    KV, bs, MB, D = 2, 8, 4, 32
    H, P, split = 4, 27, 11
    p = exaq_params(1.0, 2)
    pk, pv, tbl = _window_setup(KV, bs, MB, D, seed=6)
    q = jnp.asarray(RNG.normal(0, 1, (1, H, P, D)), jnp.float32)
    one_shot = ops.paged_prefill_attention(q, pk, pv, tbl, jnp.int32(0), p, D**-0.5,
                                           use_kernel=True)
    first = ops.paged_prefill_attention(q[:, :, :split], pk, pv, tbl, jnp.int32(0),
                                        p, D**-0.5, use_kernel=True)
    second = ops.paged_prefill_attention(q[:, :, split:], pk, pv, tbl, jnp.int32(split),
                                         p, D**-0.5, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(one_shot[:, :, :split]), np.asarray(first))
    np.testing.assert_array_equal(np.asarray(one_shot[:, :, split:]), np.asarray(second))


def test_fused_shared_prefix_cow_table():
    """Two requests whose tables share earlier chunks' prefix blocks (the
    CoW/prefix-cache layout): each request's fused chunk matches gathering
    its own window independently."""
    KV, bs, MB, D = 2, 8, 4, 32
    H, C = 4, 7
    p = exaq_params(1.0, 2)
    pk, pv, _ = _window_setup(KV, bs, MB, D, seed=8)
    tables = [jnp.asarray([1, 2, 3, 4], jnp.int32),   # owner of the prefix
              jnp.asarray([1, 2, 5, 6], jnp.int32)]   # shares blocks 1-2, forked tail
    for start, tbl in zip((2 * bs + 3, 2 * bs + 1), tables):
        q = jnp.asarray(RNG.normal(0, 1, (1, H, C, D)), jnp.float32)
        got = ops.paged_prefill_attention(q, pk, pv, tbl, jnp.int32(start), p, D**-0.5,
                                          use_kernel=True)
        want = ops.paged_prefill_attention(q, pk, pv, tbl, jnp.int32(start), p, D**-0.5,
                                           use_kernel=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_fused_bf16_pool():
    KV, bs, MB, D = 2, 8, 3, 64
    H, C, start = 4, 5, 10
    p = exaq_params(1.5, 2)
    pk, pv, tbl = _window_setup(KV, bs, MB, D, dtype=jnp.bfloat16, seed=9)
    q = jnp.asarray(RNG.normal(0, 1, (1, H, C, D)), jnp.float32)
    got = ops.paged_prefill_attention(q, pk, pv, tbl, jnp.int32(start), p, D**-0.5,
                                      use_kernel=True)
    want = ops.paged_prefill_attention(q, pk, pv, tbl, jnp.int32(start), p, D**-0.5,
                                       use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ------------------------------------------------------------- int8 KV pool

@pytest.mark.parametrize("group", [1, 4])
def test_fused_int8_matches_dequantizing_oracle(group, quantize_pool):
    """int8 pool: the fused kernel (scalar-prefetched scales, dequant in
    VMEM) matches the dequantizing gather oracle — same codes, same
    per-(block, kv-head) scales (DESIGN.md §6)."""
    KV, bs, MB, D = 2, 8, 3, 32
    H, C, start = KV * group, 6, 7
    p = exaq_params(1.5, 2)
    pk, pv, tbl = _window_setup(KV, bs, MB, D, seed=20 + group)
    qk, qv, ks, vs = quantize_pool(pk, pv)
    q = jnp.asarray(RNG.normal(0, 1, (1, H, C, D)), jnp.float32)
    got = ops.paged_prefill_attention(q, qk, qv, tbl, jnp.int32(start), p, D**-0.5,
                                      k_scale=ks, v_scale=vs, use_kernel=True)
    want = ops.paged_prefill_attention(q, qk, qv, tbl, jnp.int32(start), p, D**-0.5,
                                       k_scale=ks, v_scale=vs, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_int8_fresh_block_scale_seeding_through_chunk_scatter():
    """attention_prefill_chunk on an int8 pool seeds still-unset block scales
    from the chunk's per-target-block amax and both read paths (fused kernel
    / gather oracle) then dequantize against the SAME seeded planes — the
    scattered codes, seeded scales, and attention outputs agree."""
    from repro.configs import get_config
    from repro.models import attention as attn
    from repro.models.attention import AttnStatics
    from repro.models.model import default_qstate

    cfg = get_config("yi-6b").reduced(num_layers=2).with_quant(softmax_impl="exaq", bits=2)
    key = jax.random.PRNGKey(3)
    params = attn.init_attention(key, cfg, dtype=jnp.float32)
    bs, MB, C, start = 8, 4, 8, 4
    N = 1 + MB
    KV, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    x = jnp.asarray(RNG.normal(0, 0.1, (1, C, cfg.d_model)), jnp.float32)
    # a two-layer pool, written at layer 1
    L, layer = 2, 1
    pool_k = jnp.zeros((L, N, KV, bs, dh), jnp.int8)
    pool_v = jnp.zeros_like(pool_k)
    # block 1 was written by an earlier chunk (its scale is set and immutable);
    # blocks 2-3 are fresh allocations whose scales must seed from this chunk
    k_scale = jnp.zeros((L, N, KV), jnp.float32).at[:, 1].set(0.05)
    v_scale = jnp.zeros((L, N, KV), jnp.float32).at[:, 1].set(0.07)
    tbl = jnp.asarray([1, 2, 3, 0], jnp.int32)
    blk_t = jnp.asarray([tbl[(start + i) // bs] for i in range(C)], jnp.int32)
    off_t = jnp.asarray([(start + i) % bs for i in range(C)], jnp.int32)
    clip = default_qstate(cfg)["attn_clip"][0]

    outs, pools = {}, {}
    for fused in (False, True):
        statics = AttnStatics("exaq", 2, fused)
        o, new_kv = attn.attention_prefill_chunk(
            params, x, cfg, statics, clip, pool_k, pool_v, jnp.int32(layer), tbl,
            jnp.int32(start), blk_t, off_t, k_scale, v_scale)
        outs[fused], pools[fused] = o, new_kv
    # scatter is shared: codes and seeded scale planes are identical
    for a, b in zip(pools[False], pools[True]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the other layer's planes are untouched
    for a, b in zip(pools[True], (pool_k, pool_v, k_scale, v_scale)):
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    ks_new = pools[True][2][layer]
    assert float(ks_new[1, 0]) == pytest.approx(0.05)  # set scale immutable
    assert float(jnp.min(ks_new[2])) > 0.0             # fresh block seeded
    np.testing.assert_allclose(np.asarray(outs[True]), np.asarray(outs[False]), atol=1e-5)


def test_prefill_requires_scales_iff_int8(quantize_pool):
    KV, bs, MB, D = 2, 8, 2, 16
    pk, pv, tbl = _window_setup(KV, bs, MB, D, seed=30)
    qk, qv, ks, vs = quantize_pool(pk, pv)
    p = exaq_params(1.0, 2)
    q = jnp.zeros((1, 2, 4, D))
    with pytest.raises(ValueError):
        ops.paged_prefill_attention(q, qk, qv, tbl, jnp.int32(0), p, 0.25,
                                    k_scale=ks, use_kernel=True)  # missing v_scale
    with pytest.raises(ValueError):
        ops.paged_prefill_attention(q, pk, pv, tbl, jnp.int32(0), p, 0.25,
                                    k_scale=ks, v_scale=vs, use_kernel=True)  # fp + scales


# ------------------------------------------------------- packed int4 KV pool

@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("start,C", BOUNDARY_CASES)
def test_fused_int4_matches_dequantizing_oracle_matrix(group, start, C, quantize_pool_int4):
    """Acceptance matrix — GQA 1/4/8 x chunk-boundary cases at packed int4:
    the fused kernel (in-VMEM nibble unpack, scalar-prefetched block scales +
    sub codes) matches the dequantizing gather oracle to <= 1e-5
    (DESIGN.md §10)."""
    KV, bs, MB, D = 2, 8, 4, 32
    H = KV * group
    p = exaq_params(1.5, 2)
    pk, pv, tbl = _window_setup(KV, bs, MB, D, seed=start * 37 + C + group)
    qk, qv, ks, vs, ksub, vsub = quantize_pool_int4(pk, pv)
    q = jnp.asarray(RNG.normal(0, 1, (1, H, C, D)), jnp.float32)
    got = ops.paged_prefill_attention(q, qk, qv, tbl, jnp.int32(start), p, D**-0.5,
                                      k_scale=ks, v_scale=vs, k_sub=ksub, v_sub=vsub,
                                      use_kernel=True)
    want = ops.paged_prefill_attention(q, qk, qv, tbl, jnp.int32(start), p, D**-0.5,
                                       k_scale=ks, v_scale=vs, k_sub=ksub, v_sub=vsub,
                                       use_kernel=False)
    assert got.shape == (1, H, C, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_int4_fresh_block_seeding_through_chunk_scatter():
    """attention_prefill_chunk on a packed-int4 pool seeds still-unset block
    scales AND sub codes from the chunk's amax grid; set planes are immutable
    (first-write rule, DESIGN.md §10). Both read paths dequantize against the
    same seeded grid, so scattered nibbles, scale planes, sub codes and
    attention outputs agree."""
    from repro.configs import get_config
    from repro.kernels.ops import kv4_num_sub
    from repro.models import attention as attn
    from repro.models.attention import AttnStatics
    from repro.models.model import default_qstate

    cfg = get_config("yi-6b").reduced(num_layers=2).with_quant(softmax_impl="exaq", bits=2)
    key = jax.random.PRNGKey(3)
    params = attn.init_attention(key, cfg, dtype=jnp.float32)
    bs, MB, C, start = 8, 4, 8, 4
    N = 1 + MB
    KV, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    n_sub = kv4_num_sub(bs)
    x = jnp.asarray(RNG.normal(0, 0.1, (1, C, cfg.d_model)), jnp.float32)
    # a two-layer pool, written at layer 1
    L, layer = 2, 1
    pool_k = jnp.zeros((L, N, KV, bs, dh // 2), jnp.uint8)
    pool_v = jnp.zeros_like(pool_k)
    # block 1 was written by an earlier chunk (scale + sub codes set and
    # immutable); blocks 2-3 are fresh — their whole grid seeds from this chunk
    k_scale = jnp.zeros((L, N, KV), jnp.float32).at[:, 1].set(0.05)
    v_scale = jnp.zeros((L, N, KV), jnp.float32).at[:, 1].set(0.07)
    k_sub = jnp.zeros((L, N, KV, n_sub), jnp.uint8).at[:, 1].set(9)
    v_sub = jnp.zeros((L, N, KV, n_sub), jnp.uint8).at[:, 1].set(11)
    tbl = jnp.asarray([1, 2, 3, 0], jnp.int32)
    blk_t = jnp.asarray([tbl[(start + i) // bs] for i in range(C)], jnp.int32)
    off_t = jnp.asarray([(start + i) % bs for i in range(C)], jnp.int32)
    clip = default_qstate(cfg)["attn_clip"][0]

    outs, pools = {}, {}
    for fused in (False, True):
        statics = AttnStatics("exaq", 2, fused)
        o, new_kv = attn.attention_prefill_chunk(
            params, x, cfg, statics, clip, pool_k, pool_v, jnp.int32(layer), tbl,
            jnp.int32(start), blk_t, off_t, k_scale, v_scale, k_sub, v_sub)
        outs[fused], pools[fused] = o, new_kv
    # scatter is shared: nibbles, scale planes and sub codes are identical
    for a, b in zip(pools[False], pools[True]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the other layer's planes are untouched
    for a, b in zip(pools[True], (pool_k, pool_v, k_scale, v_scale, k_sub, v_sub)):
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    _, _, ks_new, _, ksub_new, vsub_new = (a[layer] for a in pools[True])
    assert float(ks_new[1, 0]) == pytest.approx(0.05)        # scale immutable
    assert int(jnp.min(ksub_new[1])) == 9                    # sub codes immutable
    assert int(jnp.min(vsub_new[1])) == 11
    assert float(jnp.min(ks_new[2])) > 0.0                   # fresh block seeded
    # this chunk wrote rows 4..7 of block 2 -> its written sub-blocks carry
    # live codes in [1, 15]; block 3 stays fully unset (never targeted)
    assert int(jnp.max(ksub_new[2])) >= 1
    assert int(jnp.max(ksub_new[3])) == 0 and float(ks_new[3, 0]) == 0.0
    np.testing.assert_allclose(np.asarray(outs[True]), np.asarray(outs[False]), atol=1e-5)


def test_prefill_requires_sub_planes_iff_int4(quantize_pool_int4):
    KV, bs, MB, D = 2, 8, 2, 16
    pk, pv, tbl = _window_setup(KV, bs, MB, D, seed=31)
    qk, qv, ks, vs, ksub, vsub = quantize_pool_int4(pk, pv)
    p = exaq_params(1.0, 2)
    q = jnp.zeros((1, 2, 4, D))
    with pytest.raises(ValueError):
        ops.paged_prefill_attention(q, qk, qv, tbl, jnp.int32(0), p, 0.25,
                                    k_scale=ks, v_scale=vs, k_sub=ksub,
                                    use_kernel=True)  # packed missing v_sub
    with pytest.raises(ValueError):
        ops.paged_prefill_attention(q, pk, pv, tbl, jnp.int32(0), p, 0.25,
                                    k_sub=ksub, v_sub=vsub,
                                    use_kernel=True)  # fp pool with sub codes


# ------------------------------------------------------------- bytes model

def test_prefill_bytes_model_2x_at_half_occupancy():
    """Acceptance: modeled prefill KV bytes drop >= 2x vs gather-then-attend
    when the prompt fills 50% of the padded window."""
    MB, bs, C = 32, 16, 32
    P = MB * bs // 2  # 50% pool/window occupancy at the end of prefill
    m = paged_prefill_bytes_model(prompt_len=P, chunk=C, kv_heads=8, max_blocks=MB,
                                  block_size=bs, head_dim=128)
    assert m["bytes_reduction_x"] >= 2.0
    # sanity: gather reads live blocks + writes/reads the dense rectangle
    # every chunk (x K+V); fused is (2K + 1V) over live blocks only
    assert m["gather_then_attend_bytes"] == (
        m["live_block_reads"] + 2 * m["chunks"] * MB) * 2 * m["block_bytes"]
    assert m["fused_pool_read_bytes"] == 3 * m["live_block_reads"] * m["block_bytes"]
    assert m["chunks"] == -(-P // C)


def test_prefill_bytes_model_prefix_hits_and_dtype():
    """start_cached (prefix-cache hits) removes whole chunks; int8 pays the
    per-block scale reads and prices the gather's dense copy at fp32."""
    kw = dict(prompt_len=128, chunk=16, kv_heads=4, max_blocks=16, block_size=16,
              head_dim=64)
    cold = paged_prefill_bytes_model(**kw)
    warm = paged_prefill_bytes_model(start_cached=96, **kw)
    assert warm["chunks"] == 2 and cold["chunks"] == 8
    assert warm["fused_pool_read_bytes"] < cold["fused_pool_read_bytes"]
    m8 = paged_prefill_bytes_model(kv_dtype="int8", **kw)
    assert m8["block_bytes"] == 4 * (16 * 64 + 4)
    assert m8["gather_then_attend_bytes"] == (
        m8["live_block_reads"] * m8["block_bytes"]
        + 2 * m8["chunks"] * 16 * 4 * 16 * 64 * 4) * 2
    # packed int4: half-byte payload + fp32 scale + per-sub-block code per head
    from repro.kernels.ops import kv4_num_sub

    m4 = paged_prefill_bytes_model(kv_dtype="int4", **kw)
    assert m4["block_bytes"] == 4 * (16 * 64 // 2 + 4 + kv4_num_sub(16))
    assert m8["fused_pool_read_bytes"] / m4["fused_pool_read_bytes"] >= 1.8
    m16 = paged_prefill_bytes_model(kv_dtype="bf16", **kw)
    assert m16["fused_pool_read_bytes"] / m4["fused_pool_read_bytes"] >= 3.5


# ------------------------------------------------------- engine greedy parity

def _engine_trace(cfg, params, *, fused, cache_dtype=jnp.float32):
    from repro.runtime.engine import PagedEngine

    rng = np.random.default_rng(17)
    shared = rng.integers(0, cfg.vocab_size, 12)
    spec = [(14, 6), (21, 4), (9, 8)]  # prompts span several prefill chunks
    prompts = [np.concatenate([shared, rng.integers(0, cfg.vocab_size, n)])
               for n, _ in spec]
    eng = PagedEngine(cfg, params, max_slots=2, max_seq=64, steps_per_sync=4,
                      block_size=8, prefill_chunk=8, seed=0, fused=fused,
                      cache_dtype=cache_dtype)
    uids = [eng.submit(p, g) for p, (_, g) in zip(prompts, spec)]
    res = eng.run()
    assert eng.stats["prefill_chunks"] > len(prompts)  # chunked, not one-shot
    assert eng.stats["prefix_hit_tokens"] > 0          # CoW/prefix paths engaged
    return [res[u].tokens for u in uids]


def test_paged_engine_fused_prefill_matches_gather_greedy():
    """Bit-exact greedy parity through a full prefill+decode PagedEngine
    trace: with ``fused`` toggled, BOTH the paged-prefill and paged-decode
    kernels swap in, and the emitted tokens must match the gather references
    exactly (shared-prefix prompts, multi-chunk prefills — DESIGN.md §7)."""
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config("yi-6b").reduced(num_layers=2).with_quant(softmax_impl="exaq", bits=2)
    params = build_model(cfg).init(jax.random.PRNGKey(0), jnp.float32)
    assert _engine_trace(cfg, params, fused=True) == _engine_trace(cfg, params, fused=False)


@pytest.mark.parametrize("cache_dtype", [jnp.int8, "int4"], ids=["int8", "int4"])
def test_paged_engine_fused_prefill_quantized_matches_gather_greedy(cache_dtype):
    """Engine-level parity on quantized pools: quantize-on-scatter with
    first-write scale (+ int4 sub-code) seeding is shared by both paths, so
    fused and gather dequantize identical codes and emit identical greedy
    tokens through multi-chunk shared-prefix prefills (DESIGN.md §6/§7/§10)."""
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config("yi-6b").reduced(num_layers=2).with_quant(softmax_impl="exaq", bits=2)
    params = build_model(cfg).init(jax.random.PRNGKey(0), jnp.float32)
    assert (_engine_trace(cfg, params, fused=True, cache_dtype=cache_dtype)
            == _engine_trace(cfg, params, fused=False, cache_dtype=cache_dtype))
