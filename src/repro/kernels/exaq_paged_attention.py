"""Pallas TPU kernel: fused paged-decode attention with EXAQ softmax.

The serving hot path this kernel deletes (DESIGN.md §3, fused paged decode):
``gather_block_kv`` materializes a dense ``(slots, KV, max_blocks*bs, Dh)``
copy of every slot's KV window in HBM each decode step — the pool is read
once to build the copy, the copy is written, then read again by attention.
Three rectangular passes of bandwidth to feed a softmax whose whole point
(paper §4, Table 3) is to be cheaper than a memcpy.

Here the block table drives the DMA directly: the grid is
``(slot, kv_head, chunk)`` and the K/V BlockSpec index maps read the
*scalar-prefetched* block table, so each grid step pulls one pool block
``tables[slot, chunk]`` from HBM into VMEM at its natural layout — no
intermediate copy exists. Dead-tail chunks (``chunk * bs >= kv_lens[slot]``)
are remapped to the null block (id 0); consecutive identical indices collapse
to a single DMA, so bytes moved track *live* tokens, not table width.

Chunk-combine semantics are the global grid of ``exaq_softmax_chunked``
(exact Algo. 2, DESIGN.md §2): the chunk axis runs two passes over the
table — pass 1 reduces the global row max across live chunks, pass 2
re-reads K, quantizes every chunk's scores on the grid anchored at that max,
and accumulates the PV numerator plus the 2^M-bin histogram denominator.
Counts on a shared grid add exactly across chunks, so block boundaries are
invisible to the softmax and the kernel is bit-comparable to the
gather-then-dispatch reference (``kernels.ops.paged_decode_attention`` with
``use_kernel=False``) instead of only statistically close like the online
running-max kernels. V is fetched in pass 2 only (its pass-1 index map pins
the null block), so the fused path moves ~2x K + 1x V of *live* window bytes
versus the gather path's live pool read plus two rectangular passes over the
dense copy (see ``paged_decode_bytes_model``).

GQA is native: q is laid out ``(slots, KV, group, Dh)`` so one kv head's
query group forms the q-block rows — K/V are never repeated ``group`` times
in memory (the repeat the unfused path pays via ``repeat_kv``).

Int8 pools (DESIGN.md §6): when the pool stores int8 codes with
per-(block, kv-head) scales, the scales ride the *scalar-prefetch* channel
next to the block tables, and each K/V block is dequantized in VMEM right
after its 8-bit DMA lands — ``codes.astype(f32) * scale[blk, kv_head]`` —
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
no dense dequantized (or even unpacked) copy ever exists in HBM. q/out/acc
stay at the unpacked width ``2 * lane_pad(Dh/2)``; q's zero lane-padding
nulls the K-side garbage that padded nibbles decode to, and the V-side
garbage lands in output lanes >= Dh that the final slice drops.

Layouts: q ``(S, H, 1, Dh)``; pool_k/pool_v ``(N, KV, bs, Dh)`` (int4:
``(N, KV, bs, Dh/2)`` uint8); block_tables ``(S, MB)`` int32; kv_lens
``(S,)`` int32; optional k_scale/v_scale ``(N, KV)`` fp32 and int4-only
k_sub/v_sub ``(N, KV, n_sub)`` uint8. Compiled-mode tiling wants ``bs`` a
multiple of 8 and the pool's last dim lane-padded (both hold for
production shapes; tests run interpret mode where any shape goes).

Tensor-parallel contract (DESIGN.md §9): under a mesh whose 'model' axis
divides KV, ``kernels.ops.paged_decode_attention`` wraps this kernel in a
shard_map that splits q's H axis and the pool's KV axis by the same factor
and replicates tables/lens/scalars. The kernel itself is unchanged — inside
the shard_map its ``pool_k``/``pool_v`` are the *local* head partition and
its grid's kv_head axis runs over local heads only, so index maps never see
a global head id (q head ``h``'s group maps to local kv head ``h // group``
exactly as on one device). Per-(slot, head) rows are computed whole on one
shard, making the sharded kernel bit-exact vs the single-shard dispatch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.kv_codec import INT4_BIAS, INV_SUB_LEVELS, kv4_num_sub

_NEG_BIG = -1e30
_LANES = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def exaq_accumulate_stage(s, m, valid, *, levels: int, clip: float, lut: tuple[float, ...]):
    """The EXAQ quantize + LUT + histogram stage shared by BOTH paged
    kernels' accumulate passes (decode and prefill — they must stay
    bit-identical for the decode-vs-prefill parity contract to hold).

    s: (rows, bs) raw scores (masked lanes already at -inf); m: (rows, 1)
    the global row max from pass 1 — the shared quantization anchor; valid:
    (rows, bs) live-lane mask. Returns (e, dden): the LUT-reconstructed
    unnormalized weights (masked lanes zeroed) and this chunk's partial
    histogram denominator — integer counts on the shared grid add exactly
    across chunks (DESIGN.md §2), so no rescale term exists.
    """
    inv_delta = levels / (-clip)
    codes = jnp.clip(jnp.floor((s - m - clip) * inv_delta), 0, levels - 1).astype(jnp.int32)
    # LUT as a select chain (VPU-friendly; gathers would leave the vector unit)
    e = jnp.full(s.shape, lut[0], jnp.float32)
    for kk in range(1, levels):
        e = jnp.where(codes == kk, lut[kk], e)
    e = jnp.where(valid, e, 0.0)
    dden = jnp.zeros((s.shape[0], 1), jnp.float32)
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


def _paged_decode_kernel(
    tables_ref,
    lens_ref,
    *refs,
    bs: int,
    mb: int,
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
    """Grid (S, KV, 2*MB): chunks 0..MB-1 are the max pass, MB..2*MB-1 the
    quantize+accumulate pass. Scratch (m, l, acc) carries across the chunk
    axis; the BlockSpec index maps (not this body) steer the pool DMA.
    ``kv_quant`` pools carry two extra scalar-prefetch refs — the
    per-(block, kv-head) dequant scales (DESIGN.md §6); ``kv_int4`` pools
    carry two more — the (N, KV, n_sub) sub-block scale codes — and their
    K/V refs hold *packed* nibbles at half width (DESIGN.md §10)."""
    if kv_int4:
        (ksc_ref, vsc_ref, ksub_ref, vsub_ref,
         q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref) = refs
    elif kv_quant:
        ksub_ref = vsub_ref = None
        ksc_ref, vsc_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        ksc_ref = vsc_ref = ksub_ref = vsub_ref = None
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    slot = pl.program_id(0)
    head = pl.program_id(1)
    j = pl.program_id(2)
    t = j % mb  # table entry this step touches (same in both passes)
    kv_len = lens_ref[slot]
    live = t * bs < kv_len
    # the block whose payload sits in k_ref/v_ref this step (dead tails are
    # pinned to the null block, whose scale is 0 — masked lanes anyway)
    blk = jnp.where(live, tables_ref[slot, t], 0)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    col = t * bs + jax.lax.broadcasted_iota(jnp.int32, (block_q, bs), 1)
    valid = col < kv_len

    def _load_kv(ref, sc_ref, sub_ref):
        return load_pool_block(ref, sc_ref, sub_ref, blk * kv + head, bs=bs, kv_quant=kv_quant,
                               kv_int4=kv_int4, n_sub=n_sub, sub_bs=sub_bs)

    def _scores():
        q = q_ref[0, 0].astype(jnp.float32)
        k = _load_kv(k_ref, ksc_ref, ksub_ref)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        return jnp.where(valid, s, _NEG_BIG)

    @pl.when((j < mb) & live)
    def _max_pass():
        s = _scores()
        m_ref[...] = jnp.maximum(m_ref[...], jnp.max(s, axis=-1, keepdims=True))

    @pl.when((j >= mb) & live)
    def _acc_pass():
        s = _scores()
        m = m_ref[:, :1]  # global row max from pass 1 — shared quantization grid
        e, dden = exaq_accumulate_stage(s, m, valid, levels=levels, clip=clip, lut=lut)
        l_ref[...] = l_ref[...] + dden
        v = _load_kv(v_ref, vsc_ref, vsub_ref)
        acc_ref[...] += jax.lax.dot_general(
            e, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == 2 * mb - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30))[None, None].astype(o_ref.dtype)


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
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    k_sub: jnp.ndarray | None = None,
    v_sub: jnp.ndarray | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused paged-decode EXAQ attention over a block pool.

    q: (S, H, 1, D); pool_k/pool_v: (N, KV, bs, D); block_tables: (S, MB)
    int32 block ids (null-block padded); kv_lens: (S,) live tokens per slot.
    An int8 pool additionally takes k_scale/v_scale (N, KV) fp32 dequant
    scales (DESIGN.md §6), scalar-prefetched beside the block tables. A
    packed int4 pool (uint8 payload at last dim D/2, DESIGN.md §10) also
    takes k_sub/v_sub (N, KV, n_sub) uint8 sub-block scale codes; nibbles
    unpack in VMEM after each half-width block DMA.
    Returns (S, H, 1, D) fp32. Global-grid (exact Algo. 2) semantics.
    """
    S, H, one, D = q.shape
    assert one == 1
    N, KV, bs, _ = pool_k.shape
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
        if D % 2 or pool_k.shape[3] != D // 2:
            raise ValueError(
                f"packed int4 pool last dim must be head_dim/2 "
                f"(got pool {pool_k.shape[3]}, head_dim {D})"
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
        if p_pad != D // 2:
            ppad = ((0, 0), (0, 0), (0, 0), (0, p_pad - D // 2))
            pool_k = jnp.pad(pool_k, ppad)
            pool_v = jnp.pad(pool_v, ppad)
        if d_pad != D:
            q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, d_pad - D)))
    else:
        n_sub = sub_bs = 0
        d_pad = _round_up(max(D, _LANES), _LANES)
        kv_width = d_pad
        if d_pad != D:
            # production head dims are lane-aligned; the pad only fires on the
            # small shapes tests use (interpret mode), never on the serving path
            pad = ((0, 0), (0, 0), (0, 0), (0, d_pad - D))
            q = jnp.pad(q, pad)
            pool_k = jnp.pad(pool_k, pad)
            pool_v = jnp.pad(pool_v, pad)

    tables = block_tables.astype(jnp.int32)
    lens = kv_lens.astype(jnp.int32)
    lut = tuple(float(x) for x in params.lut_np())

    def _k_index(s, h, j, tbl, lns, *sc):
        # dead tail -> null block; consecutive identical indices are a
        # single DMA, so dead chunks cost ~nothing
        t = j % MB
        return (jnp.where(t * bs < lns[s], tbl[s, t], 0), h, 0, 0)

    def _v_index(s, h, j, tbl, lns, *sc):
        # V is only consumed by the accumulate pass; pin the max pass (and
        # dead chunks) to the null block so V moves over HBM exactly once
        t = j % MB
        return (jnp.where((j >= MB) & (t * bs < lns[s]), tbl[s, t], 0), h, 0, 0)

    def _q_index(s, h, j, tbl, lns, *sc):
        return (s, h, 0, 0)

    # the dequant scales ride the scalar-prefetch channel and the kernel
    # indexes them by the same prefetched table entry that steered the DMA.
    # They go in flat: SMEM pads a 2-D array's minor dim to 128 lanes, which
    # would blow (N, KV=4) planes up 32x past SMEM's 1 MiB at a 1024-block
    # pool. int4 adds the (N, KV, n_sub) sub codes, widened to int32 (SMEM
    # scalars are 32-bit)
    prefetch = (tables, lens)
    if want_scales:
        prefetch += (k_scale.astype(jnp.float32).reshape(-1),
                     v_scale.astype(jnp.float32).reshape(-1))
    if kv_int4:
        prefetch += (k_sub.astype(jnp.int32).reshape(-1), v_sub.astype(jnp.int32).reshape(-1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(S, KV, 2 * MB),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d_pad), _q_index),
            pl.BlockSpec((1, 1, bs, kv_width), _k_index),
            pl.BlockSpec((1, 1, bs, kv_width), _v_index),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d_pad), _q_index),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d_pad), jnp.float32),
        ],
    )
    kern = functools.partial(
        _paged_decode_kernel,
        bs=bs, mb=MB, block_q=block_q,
        levels=params.levels, clip=float(params.clip), lut=lut, scale=float(scale),
        kv=KV, kv_quant=kv_quant, kv_int4=kv_int4, n_sub=n_sub, sub_bs=sub_bs,
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, KV, block_q, d_pad), jnp.float32),
        # only the chunk axis carries scratch state; (slot, kv_head) programs
        # are independent and may partition across cores
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*prefetch, q, pool_k, pool_v)
    return out[:, :, :group, :D].reshape(S, H, 1, D)


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
