"""Pallas TPU kernel: fused paged-decode attention with EXAQ softmax.

The serving hot path this kernel deletes (DESIGN.md §3, fused paged decode):
``gather_block_kv`` materializes a dense ``(slots, KV, max_blocks*bs, Dh)``
copy of every slot's KV window in HBM each decode step — the pool is read
once to build the copy, the copy is written, then read again by attention.
Three rectangular passes of bandwidth to feed a softmax whose whole point
(paper §4, Table 3) is to be cheaper than a memcpy.

Here the block table drives the DMA directly. The kernel runs one program
per slot; the pools stay in HBM (``memory_space=pl.ANY``) and the block
table and ``kv_lens`` are *scalar-prefetched*. The program walks only the
slot's live KV: ``cdiv(kv_lens[slot], T)`` tiles of ``T`` tokens, each
tile's live pool blocks copied by async DMA from the table straight into a
VMEM double buffer while the previous tile is computed — no intermediate
copy exists, and no step of the kernel touches a dead table entry. A copy
moves one whole pool block, all kv heads ``(KV, bs, Dh)`` at once, and the
program scores all heads' query groups in one batched dot. ``T`` follows
from the shapes (``decode_tile_tokens``): the most tokens up to 512 whose K
and V double buffers fit 4 MiB of VMEM, capped at the table, at least one
block.

Combine semantics are the global grid of ``exaq_softmax_chunked`` (exact
Algo. 2, DESIGN.md §2): two loops over the live tiles — the first reduces
the global row max, the second re-reads K, quantizes every tile's scores on
the grid anchored at that max, and accumulates the PV numerator plus the
2^M-bin histogram denominator. Counts on a shared grid add exactly across
tiles, so tile and block boundaries are invisible to the softmax and the
kernel is bit-comparable to the gather-then-dispatch reference
(``kernels.ops.paged_decode_attention`` with ``use_kernel=False``) instead
of only statistically close like the online running-max kernels. V is
copied in the second loop only, so the fused path moves ~2x K + 1x V of
*live* window bytes versus the gather path's live pool read plus two
rectangular passes over the dense copy (see ``paged_decode_bytes_model``).

GQA is native: q is laid out ``(slots, KV, group, Dh)`` so one kv head's
query group forms the q-block rows — K/V are never repeated ``group`` times
in memory (the repeat the unfused path pays via ``repeat_kv``).

Int8 pools (DESIGN.md §6): when the pool stores int8 codes with
per-(block, kv-head) scales, the scales ride the *scalar-prefetch* channel
next to the block tables, and each K/V tile is dequantized in VMEM right
after its 8-bit DMAs land — ``codes.astype(f32) * scale[blk, kv_head]`` —
before the EXAQ clip/LUT stages. HBM only ever moves the 1-byte payload,
so the modeled bytes/step drop ~2x vs bf16 (~4x vs fp32); the EXAQ
histogram math downstream is unchanged, and the kernel stays bit-comparable
to the *dequantizing* gather oracle (``gather_block_kv`` with scales).

Packed int4 pools (DESIGN.md §10) halve the payload again: the pool's last
dim is ``Dh/2`` uint8 bytes (two head-dim-adjacent nibbles per byte), and
the per-(block, kv-head, sub-block) uint8 scale codes join the block scales
on the scalar-prefetch channel. The DMA lands the *packed* block in VMEM;
nibbles are split, re-biased, and scaled by
``block_scale * sub_code / 15`` per sub-block row group right there —
no dense dequantized (or even unpacked) copy ever exists in HBM. The split
is not re-interleaved: low nibbles (even head dims) fill lanes
``[0, lane_pad(Dh/2))`` and high nibbles (odd dims) the next as many, and
the wrapper lays q out the same way and restores the output's dim order,
so the kernel never shuffles lanes. q/out/acc stay at the unpacked width
``2 * lane_pad(Dh/2)``; q's zero lane-padding nulls the K-side garbage
that padded nibbles decode to, and the V-side garbage lands in output
lanes the final slice drops.

Layouts: q ``(S, H, 1, Dh)``; pool_k/pool_v the whole stacked planes
``(L, N, KV, bs, Dh)`` (int4: ``(L, N, KV, bs, Dh/2)`` uint8) and
``layer``, the scalar-prefetched index of the layer to read: the model
carries the pool through its layer loop and writes each layer's rows in
place (DESIGN.md §3), so no per-layer slice of the pool is ever cut out to
feed the kernel; block_tables ``(S, MB)`` int32; kv_lens ``(S,)`` int32;
optional k_scale/v_scale ``(L, N, KV)`` fp32 and int4-only k_sub/v_sub
``(L, N, KV, n_sub)`` uint8, of which only ``layer``'s (kilobytes) reach
SMEM. Compiled-mode tiling wants ``bs`` a multiple of 8 and the pool's last
dim lane-padded (both hold for production shapes; a narrower pool has its
one layer sliced out and padded, and tests run interpret mode where any
shape goes).

Tensor-parallel contract (DESIGN.md §9): under a mesh whose 'model' axis
divides KV, ``kernels.ops.paged_decode_attention`` wraps this kernel in a
shard_map that splits q's H axis and the pool's KV axis by the same factor
and replicates tables/lens/scalars. The kernel itself is unchanged — inside
the shard_map its ``pool_k``/``pool_v`` are the *local* head partition,
each copy moves a block's local heads and the program computes local
heads only, so the kernel never sees a global head id (q head ``h``'s
group maps to local kv head ``h // group`` exactly as on one device).
Per-(slot, head) rows are computed whole on one shard, making the sharded
kernel bit-exact vs the single-shard dispatch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.kv_codec import INT4_BIAS, INV_SUB_LEVELS, kv4_num_sub, kv_cache_is_int4

_NEG_BIG = -1e30
_LANES = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def layer_operands(pool_k, pool_v, layer, width: int, planes):
    """What both paged kernels read of the stacked pool at ``layer``.

    Returns (pool_k, pool_v, layer (1,) int32, flat scale planes). The
    payload planes pass whole, to be indexed by ``layer`` in the kernel —
    unless their last dim is narrower than ``width`` lanes: then only this
    layer is sliced out and padded (one layer's bytes, never the whole
    pool's) and the index becomes 0. ``planes`` are the quantized pool's
    (L, N, KV[, n_sub]) scale planes: this layer's are cut out (kilobytes)
    and flattened for SMEM, which pads a 2-D array's minor dim to 128 lanes
    and would blow (N, KV=4) planes up 32x past its 1 MiB at a 1024-block
    pool; the sub codes widen to int32 (SMEM scalars are 32-bit)."""
    layer = jnp.asarray(layer, jnp.int32)
    flat = tuple(
        jax.lax.dynamic_index_in_dim(p, layer, 0, keepdims=False)
        .astype(jnp.float32 if jnp.issubdtype(p.dtype, jnp.floating) else jnp.int32)
        .reshape(-1)
        for p in planes
    )
    if pool_k.shape[-1] != width:
        pad = ((0, 0),) * 4 + ((0, width - pool_k.shape[-1]),)
        pool_k, pool_v = (jnp.pad(jax.lax.dynamic_index_in_dim(p, layer, 0), pad)
                          for p in (pool_k, pool_v))
        layer = jnp.zeros((), jnp.int32)
    return pool_k, pool_v, layer.reshape(1), flat


def exaq_accumulate_stage(s, m, valid, *, levels: int, clip: float, lut: tuple[float, ...]):
    """The EXAQ quantize + LUT + histogram stage shared by BOTH paged
    kernels' accumulate passes (decode and prefill — they must stay
    bit-identical for the decode-vs-prefill parity contract to hold).

    s: (..., rows, n) raw scores (masked lanes already at -inf); m:
    (..., rows, 1) the global row max from pass 1 — the shared quantization
    anchor; valid: the live-lane mask, broadcast against s. Returns
    (e, dden): the LUT-reconstructed unnormalized weights (masked lanes
    zeroed) and this chunk's partial histogram denominator — integer counts
    on the shared grid add exactly across chunks (DESIGN.md §2), so no
    rescale term exists.
    """
    inv_delta = levels / (-clip)
    codes = jnp.clip(jnp.floor((s - m - clip) * inv_delta), 0, levels - 1).astype(jnp.int32)
    # LUT as a select chain (VPU-friendly; gathers would leave the vector unit)
    e = jnp.full(s.shape, lut[0], jnp.float32)
    for kk in range(1, levels):
        e = jnp.where(codes == kk, lut[kk], e)
    e = jnp.where(valid, e, 0.0)
    dden = jnp.zeros(s.shape[:-1] + (1,), jnp.float32)
    for kk in range(levels):
        cnt = jnp.sum(jnp.where(valid & (codes == kk), 1, 0).astype(jnp.int32),
                      axis=-1, keepdims=True)
        dden = dden + cnt.astype(jnp.float32) * lut[kk]
    return e, dden


def load_pool_block(ref, sc_ref, sub_ref, row, *, bs: int, kv_quant: bool, kv_int4: bool,
                    n_sub: int, sub_bs: int):
    """One pool block from its VMEM ref to fp32 rows, dequantized — shared by
    BOTH paged kernels, which must dequantize identically.

    ``row`` is the block's flat (block, kv-head) index into the scale planes,
    which are scalar-prefetched as 1-D arrays. int8: ``codes * scale``. int4:
    the ref holds packed nibbles (bs, Pp); split/re-bias them to (bs, 2*Pp)
    codes and scale each sub_bs-row group by its effective scale
    ``block_scale * sub_code / 15`` — the same multiply order as
    ``kv_codec.kv4_effective_scale``, so kernel and gather oracle agree to
    fp32 roundoff. The per-row scale column is built by a static loop over
    the n_sub scalar codes (scalar broadcasts, no gather)."""
    x = ref[0, 0]
    if kv_int4:
        xi = x.astype(jnp.int32)  # Mosaic shifts 32-bit lanes, not uint8
        lo = (xi & 0xF) - INT4_BIAS
        hi = (xi >> 4) - INT4_BIAS
        codes = jnp.stack([lo, hi], axis=-1).reshape(bs, 2 * x.shape[-1])
        parts = []
        for sg in range(n_sub):
            s_eff = sc_ref[row] * sub_ref[row * n_sub + sg].astype(jnp.float32) * INV_SUB_LEVELS
            parts.append(s_eff * jnp.ones((sub_bs, 1), jnp.float32))
        row_scale = jnp.concatenate(parts, axis=0) if n_sub > 1 else parts[0]
        return codes.astype(jnp.float32) * row_scale
    x = x.astype(jnp.float32)
    if kv_quant:
        x = x * sc_ref[row]  # dequant in VMEM: HBM moved 1 byte/elt
    return x


def load_pool_tile(buf, sl, head, sc_ref, sub_ref, rows, *, bs: int, kv_quant: bool,
                   kv_int4: bool, n_sub: int, sub_bs: int):
    """One kv head of a tile of pool blocks to dequantized fp32 rows — the
    decode kernel's tile-wide twin of ``load_pool_block``, with the same
    per-element arithmetic.

    ``buf`` is a VMEM buffer (2, tb, KV, bs, W) of whole pool blocks and
    ``sl`` its (dynamic) half; the result stacks the tb blocks' rows of
    head ``head`` into (tb*bs, W). ``rows[b]`` is block b's flat
    (block, kv-head) index into the scale planes: int8 rows are
    ``codes * scale``; int4 rows are ``codes * (block_scale * sub_code /
    15)`` per sub_bs-row group, in ``kv4_effective_scale``'s multiply order.
    int4 codes come out *split*, not interleaved: lanes [0, W) hold the low
    nibbles (even head dims), lanes [W, 2W) the high ones (odd dims) — the
    caller lays q out the same way and restores the output's dim order, which
    spares the kernel a lane shuffle per block."""
    tb = buf.shape[1]
    x = buf[sl, :, head]
    if kv_int4:
        xi = x.astype(jnp.int32).reshape(tb * bs, x.shape[-1])  # Mosaic shifts 32-bit lanes
        x = jnp.concatenate([(xi & 0xF) - INT4_BIAS, (xi >> 4) - INT4_BIAS], axis=-1)
    x = x.astype(jnp.float32).reshape(tb * bs, x.shape[-1])
    if not (kv_quant or kv_int4):
        return x
    if kv_int4:
        col = [sc_ref[r] * sub_ref[r * n_sub + g].astype(jnp.float32) * INV_SUB_LEVELS
               * jnp.ones((sub_bs, 1), jnp.float32) for r in rows for g in range(n_sub)]
    else:
        col = [sc_ref[r] * jnp.ones((bs, 1), jnp.float32) for r in rows]
    return x * jnp.concatenate(col, axis=0)


# A tile is at most this many tokens (on a v5e, 512 beat 128, 256 and 1024
# at the yi-6b.chat shape; PERF.md), and its K and V double buffers at most
# this many VMEM bytes (v5e's default scoped VMEM limit is 16 MiB).
_TILE_TOKENS = 512
_TILE_VMEM_BYTES = 4 << 20
# block copies the decode kernel issues per step of its copy loop
_COPY_GROUP = 8


def decode_tile_tokens(*, block_size: int, max_blocks: int, kv_heads: int, head_dim: int,
                       kv_dtype) -> int:
    """Tokens per tile of the decode kernel's live-bounded loop, for a pool
    of ``kv_dtype`` (a jnp dtype, or ``"int4"`` for the packed pool).

    The most whole blocks up to ``_TILE_TOKENS`` tokens whose K and V double
    buffers — ``2 * 2 * kv_heads * block_size * width * itemsize`` bytes a
    block, ``width`` the lane-padded last dim of the pool as the kernel
    reads it — fit ``_TILE_VMEM_BYTES``, capped at the table width, at least
    one block. Derived from shapes alone, so the engine's tile counters
    (``decode_kv_tiles``) and the kernel agree."""
    if kv_cache_is_int4(kv_dtype):
        width, itemsize = _round_up(max(head_dim // 2, _LANES), _LANES), 1
    else:
        width, itemsize = _round_up(max(head_dim, _LANES), _LANES), jnp.dtype(kv_dtype).itemsize
    per_block = 4 * kv_heads * block_size * width * itemsize
    blocks = min(_TILE_TOKENS // block_size, _TILE_VMEM_BYTES // per_block, max_blocks)
    return block_size * max(1, blocks)


def _paged_decode_kernel(
    tables_ref,
    lens_ref,
    layer_ref,
    *refs,
    bs: int,
    mb: int,
    tb: int,
    block_q: int,
    levels: int,
    clip: float,
    lut: tuple[float, ...],
    scale: float,
    kv: int,
    kv_quant: bool,
    kv_int4: bool = False,
    n_sub: int = 0,
    sub_bs: int = 0,
):
    """One program per slot, over all its kv heads. Two loops over the
    slot's live tiles of ``tb`` pool blocks, bounded by ``kv_lens[slot]``:
    the first reduces the global row max, the second quantizes on it and
    accumulates. The stacked pools stay in HBM (``memory_space=pl.ANY``);
    each tile's live blocks of layer ``layer_ref[0]`` are copied from the
    scalar-prefetched table into one half of a double buffer while the other
    half is computed on. ``kv_quant`` pools
    carry two extra scalar-prefetch refs — the per-(block, kv-head) dequant
    scales (DESIGN.md §6); ``kv_int4`` pools carry two more — the
    (N, KV, n_sub) sub-block scale codes — and their pools hold *packed*
    nibbles at half width (DESIGN.md §10)."""
    if kv_int4:
        ksc_ref, vsc_ref, ksub_ref, vsub_ref, *refs = refs
    elif kv_quant:
        ksc_ref, vsc_ref, *refs = refs
        ksub_ref = vsub_ref = None
    else:
        ksc_ref = vsc_ref = ksub_ref = vsub_ref = None
    (q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, k_sem, v_sem,
     m_ref, l_ref, acc_ref) = refs
    slot = pl.program_id(0)
    layer = layer_ref[0]
    # scalar index math uses lax ops: jnp's floor division and modulo trace
    # sign fix-ups that make the kernel slow to lower. A finished slot's
    # stale length may pass the table by one token
    div, rem = jax.lax.div, jax.lax.rem
    kv_len = jnp.minimum(lens_ref[slot], mb * bs)
    tile = tb * bs
    n_tiles = div(kv_len + tile - 1, tile)

    def _n_live(i):
        return jnp.minimum(tb, div(kv_len - i * tile + bs - 1, bs))

    def _start(hbm, buf, sem, i, sl):
        # only tile i's live blocks move (a tile's dead rows are masked
        # below), each whole: all kv heads in one contiguous copy. Whole
        # groups of _COPY_GROUP go back to back (faster to issue than one
        # a loop step), the rest one a step
        n_live = _n_live(i)

        def copy(b):
            blk = tables_ref[slot, i * tb + b]
            pltpu.make_async_copy(hbm.at[layer, blk], buf.at[sl, b], sem.at[sl]).start()

        def group(g, carry):
            for u in range(_COPY_GROUP):
                copy(g * _COPY_GROUP + u)
            return carry

        def single(b, carry):
            copy(b)
            return carry

        n_full = div(n_live, _COPY_GROUP)
        jax.lax.fori_loop(0, n_full, group, 0)
        jax.lax.fori_loop(n_full * _COPY_GROUP, n_live, single, 0)

    def _wait(hbm, buf, sem, i, sl):
        # every copy moved one block's bytes onto this semaphore: wait as many
        copy = pltpu.make_async_copy(hbm.at[0, 0], buf.at[sl, 0], sem.at[sl])

        def one(b, carry):
            copy.wait()
            return carry

        jax.lax.fori_loop(0, _n_live(i), one, 0)

    def _load(buf, sl, sc_ref, sub_ref, blocks):
        # every kv head of the tile, (KV, tb*bs, W): the heads' query groups
        # then run as one batched dot, not a loop
        return jnp.stack([
            load_pool_tile(buf, sl, h, sc_ref, sub_ref,
                           None if blocks is None else [blk * kv + h for blk in blocks],
                           bs=bs, kv_quant=kv_quant, kv_int4=kv_int4, n_sub=n_sub, sub_bs=sub_bs)
            for h in range(kv)
        ])

    def _blocks(i):
        # tile i's block ids for the scale planes (dead entries: the null block)
        if not (kv_quant or kv_int4):
            return None
        return [jnp.where(b < _n_live(i), tables_ref[slot, jnp.minimum(i * tb + b, mb - 1)], 0)
                for b in range(tb)]

    def _scores(sl, i):
        # (KV, block_q, tile) scores of tile i, masked past kv_len
        k = _load(k_buf, sl, ksc_ref, ksub_ref, _blocks(i))
        s = jax.lax.dot_general(
            q_ref[0].astype(jnp.float32), k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
        return jnp.where(_valid(i), s, _NEG_BIG)

    def _valid(i):
        return i * tile + jax.lax.broadcasted_iota(jnp.int32, (block_q, tile), 1) < kv_len

    m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_tiles > 0)
    def _first():
        _start(k_hbm, k_buf, k_sem, 0, 0)

    # K buffer halves alternate over the 2*n_tiles steps of both passes
    # (pass 2's tile i is step n_tiles + i); V's over pass 2's tiles alone
    def _max_step(i, carry):
        sl = rem(i, 2)
        # the next K tile: this pass's next, or pass 2's first after the last
        _start(k_hbm, k_buf, k_sem, rem(i + 1, n_tiles), 1 - sl)

        @pl.when(i + 1 == n_tiles)
        def _next_pass():
            _start(v_hbm, v_buf, v_sem, 0, 0)

        _wait(k_hbm, k_buf, k_sem, i, sl)
        s = _scores(sl, i)
        m_ref[...] = jnp.maximum(m_ref[...], jnp.max(s, axis=-1, keepdims=True))
        return carry

    def _acc_step(i, carry):
        sl = rem(n_tiles + i, 2)
        vsl = rem(i, 2)

        @pl.when(i + 1 < n_tiles)
        def _next():
            _start(k_hbm, k_buf, k_sem, i + 1, 1 - sl)
            _start(v_hbm, v_buf, v_sem, i + 1, 1 - vsl)

        _wait(k_hbm, k_buf, k_sem, i, sl)
        _wait(v_hbm, v_buf, v_sem, i, vsl)
        s = _scores(sl, i)
        m = m_ref[:, :, :1]  # global row max from pass 1 — shared quantization grid
        e, dden = exaq_accumulate_stage(s, m, _valid(i), levels=levels, clip=clip, lut=lut)
        l_ref[...] = l_ref[...] + dden
        # rows past kv_len hold whatever the buffer held before: zero them so
        # 0-weight x garbage (a NaN bit pattern) cannot reach the accumulator
        row_live = (i * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)) < kv_len
        v = jnp.where(row_live, _load(v_buf, vsl, vsc_ref, vsub_ref, _blocks(i)), 0.0)
        acc_ref[...] += jax.lax.dot_general(
            e, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        return carry

    jax.lax.fori_loop(0, n_tiles, _max_step, 0)
    jax.lax.fori_loop(0, n_tiles, _acc_step, 0)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("params", "scale", "interpret"),
)
def exaq_paged_decode_attention(
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    block_tables: jnp.ndarray,
    kv_lens: jnp.ndarray,
    params,
    scale: float,
    *,
    layer=0,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    k_sub: jnp.ndarray | None = None,
    v_sub: jnp.ndarray | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused paged-decode EXAQ attention over layer ``layer`` of a block pool.

    q: (S, H, 1, D); pool_k/pool_v: (L, N, KV, bs, D) stacked over layers;
    layer: scalar int32, the layer to read; block_tables: (S, MB) int32
    block ids (null-block padded); kv_lens: (S,) live tokens per slot. An
    int8 pool additionally takes k_scale/v_scale (L, N, KV) fp32 dequant
    scales (DESIGN.md §6), whose layer is scalar-prefetched beside the
    block tables. A packed int4 pool (uint8 payload at last dim D/2,
    DESIGN.md §10) also takes k_sub/v_sub (L, N, KV, n_sub) uint8 sub-block
    scale codes; nibbles unpack in VMEM after each half-width block DMA.
    Returns (S, H, 1, D) fp32. Global-grid (exact Algo. 2) semantics.
    """
    S, H, one, D = q.shape
    assert one == 1
    KV, bs = pool_k.shape[2:4]
    MB = block_tables.shape[1]
    group = H // KV
    kv_quant = pool_k.dtype == jnp.int8
    kv_int4 = pool_k.dtype == jnp.uint8
    want_scales = kv_quant or kv_int4
    if (k_scale is not None) != want_scales or (v_scale is not None) != want_scales:
        raise ValueError(
            "quantized (int8/int4) pools require both k_scale and v_scale; fp pools forbid them"
        )
    if (k_sub is not None) != kv_int4 or (v_sub is not None) != kv_int4:
        raise ValueError(
            "packed int4 pools require both k_sub and v_sub sub-scale planes; "
            "other pools forbid them"
        )
    q = q.reshape(S, KV, group, D)
    block_q = _round_up(max(group, 8), 8)
    if block_q != group:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, block_q - group), (0, 0)))
    if kv_int4:
        if D % 2 or pool_k.shape[-1] != D // 2:
            raise ValueError(
                f"packed int4 pool last dim must be head_dim/2 "
                f"(got pool {pool_k.shape[-1]}, head_dim {D})"
            )
        n_sub = k_sub.shape[-1]
        sub_bs = bs // n_sub
        # the packed payload lane-pads at its own (half) width; q/out/acc
        # live at the unpacked width 2*Pp. q's zero padding nulls the K
        # garbage that padded zero-nibbles decode to (code -8 * scale);
        # the V-side garbage lands in output lanes >= D, sliced off below
        p_pad = _round_up(max(D // 2, _LANES), _LANES)
        kv_width = p_pad
        d_pad = 2 * p_pad
        # the kernel unpacks low nibbles (even dims) to lanes [0, p_pad) and
        # high nibbles (odd dims) to [p_pad, 2*p_pad): split q's dims the same way
        qpad = ((0, 0), (0, 0), (0, 0), (0, p_pad - D // 2))
        q = jnp.concatenate([jnp.pad(q[..., 0::2], qpad), jnp.pad(q[..., 1::2], qpad)], axis=-1)
    else:
        n_sub = sub_bs = 0
        d_pad = _round_up(max(D, _LANES), _LANES)
        kv_width = d_pad
        if d_pad != D:
            # production head dims are lane-aligned; the pad only fires on the
            # small shapes tests use (interpret mode), never on the serving path
            q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, d_pad - D)))
    pool_k, pool_v, layer, planes = layer_operands(
        pool_k, pool_v, layer, kv_width,
        tuple(p for p in (k_scale, v_scale, k_sub, v_sub) if p is not None))

    tb = decode_tile_tokens(block_size=bs, max_blocks=MB, kv_heads=KV, head_dim=D,
                            kv_dtype="int4" if kv_int4 else pool_k.dtype) // bs
    tables = block_tables.astype(jnp.int32)
    lens = kv_lens.astype(jnp.int32)
    lut = tuple(float(x) for x in params.lut_np())

    def _q_index(s, *prefetch):
        return (s, 0, 0, 0)

    # the layer's dequant scales ride the scalar-prefetch channel, flat, and
    # the kernel indexes them by the same prefetched table entry that steered
    # the DMA (int4 adds the sub codes)
    prefetch = (tables, lens, layer) + planes
    buf = pltpu.VMEM((2, tb, KV, bs, kv_width), pool_k.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, KV, block_q, d_pad), _q_index),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, KV, block_q, d_pad), _q_index),
        scratch_shapes=[
            buf,
            buf,
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((KV, block_q, _LANES), jnp.float32),
            pltpu.VMEM((KV, block_q, _LANES), jnp.float32),
            pltpu.VMEM((KV, block_q, d_pad), jnp.float32),
        ],
    )
    kern = functools.partial(
        _paged_decode_kernel,
        bs=bs, mb=MB, tb=tb, block_q=block_q,
        levels=params.levels, clip=float(params.clip), lut=lut, scale=float(scale),
        kv=KV, kv_quant=kv_quant, kv_int4=kv_int4, n_sub=n_sub, sub_bs=sub_bs,
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, KV, block_q, d_pad), jnp.float32),
        # every program runs both passes over its own slot's tiles; programs
        # are independent and may partition across cores
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*prefetch, q, pool_k, pool_v)
    out = out[:, :, :group]
    if kv_int4:  # back from split (even dims, odd dims) lanes to head-dim order
        out = jnp.stack([out[..., : D // 2], out[..., p_pad : p_pad + D // 2]], axis=-1)
    return out[..., :D].reshape(S, H, 1, D)


KV_DTYPE_BYTES = {"fp32": 4, "bf16": 2, "int8": 1, "int4": 0.5}


def paged_decode_bytes_model(
    *,
    slots: int,
    kv_heads: int,
    max_blocks: int,
    block_size: int,
    head_dim: int,
    kv_lens,
    dtype_bytes: int = 2,
    kv_dtype: str | None = None,
    tp: int = 1,
) -> dict:
    """Modeled HBM KV bytes per decode step per layer: gather vs fused.

    gather_then_read: ``gather_block_kv`` reads each slot's *live* blocks
    from the pool (dead tails clamp to the null block), writes the dense
    rectangular per-slot copy, and attention reads the copy back — so
    (live + 2 x rect) passes over each of K and V. fused_pool_read: the
    kernel touches only live blocks — K twice (max pass + accumulate
    pass), V once. Pure arithmetic so benchmarks and tests can assert the
    >= 2x bandwidth win without hardware counters.

    ``kv_dtype`` ("fp32" | "bf16" | "int8" | "int4") sizes the pool element
    instead of the raw ``dtype_bytes`` knob. int8 (DESIGN.md §6) adds the
    4-byte per-(block, kv-head) scale to every pool-block read, and —
    because the gather oracle dequantizes during assembly — prices the
    gather path's dense intermediate copy at fp32 width, which is what
    actually crosses HBM there. int4 (DESIGN.md §10) halves the payload to
    ``block_size * head_dim / 2`` packed bytes per kv head and adds one
    uint8 sub-block scale code per ``KV_SUB_BLOCK`` tokens on top of the
    fp32 block scale; its dense gather copy is fp32-priced too.

    ``tp`` models the tensor-parallel pool split (DESIGN.md §9): the kv-head
    dim shards over the mesh's 'model' axis, so each shard reads
    ``kv_heads / tp`` heads' worth of every block (payload and scale plane
    alike) and the reported figures are *per-shard* bytes — the quantity
    that bounds a shard's step latency. ``tp`` must divide ``kv_heads``
    (non-divisible counts serve a replicated pool; model that as tp=1).
    """
    import numpy as np

    if kv_heads % tp:
        raise ValueError(f"tp={tp} must divide kv_heads={kv_heads} (replicated fallback is tp=1)")
    kv_heads //= tp
    if kv_dtype is not None:
        dtype_bytes = KV_DTYPE_BYTES[kv_dtype]
    # quantized pools carry scale planes per block read and are dequantized
    # to fp32 by the gather oracle, so the dense copy is fp32-priced
    if kv_dtype == "int4":
        payload_bytes = kv_heads * block_size * head_dim // 2  # packed nibbles
        scale_bytes = kv_heads * (4 + kv4_num_sub(block_size))
        dense_bytes_elt = 4
    elif kv_dtype == "int8":
        payload_bytes = kv_heads * block_size * head_dim
        scale_bytes = kv_heads * 4
        dense_bytes_elt = 4
    else:
        payload_bytes = kv_heads * block_size * head_dim * dtype_bytes
        scale_bytes = 0
        dense_bytes_elt = dtype_bytes

    kv_lens = np.asarray(kv_lens)
    block_bytes = payload_bytes + scale_bytes
    dense_block_bytes = kv_heads * block_size * head_dim * dense_bytes_elt
    rect_blocks = slots * max_blocks
    live_blocks = int(np.sum(-(-kv_lens // block_size)))
    # (read live pool blocks + write/read the dense rectangular copy) x (K+V)
    gather = (live_blocks * block_bytes + 2 * rect_blocks * dense_block_bytes) * 2
    fused = live_blocks * (2 + 1) * block_bytes                 # 2x K + 1x V, live only
    return {
        "kv_dtype": kv_dtype,
        "tp": tp,
        "gather_then_read_bytes": int(gather),
        "fused_pool_read_bytes": int(fused),
        "bytes_reduction_x": gather / max(fused, 1),
        "live_blocks": live_blocks,
        "rect_blocks": int(rect_blocks),
        "block_bytes": int(block_bytes),
    }
