"""Pallas TPU kernel: fused chunked-prefill attention over a paged KV pool.

The prefill hot path this kernel deletes (DESIGN.md §7): chunked prefill
(`attention_prefill_chunk`) scatters a chunk's C projected K/V rows into the
pool — O(C·Dh) bytes, cheap — and then `gather_block_kv` assembles the
request's *entire* window `(1, KV, MB·bs, Dh)` as a dense HBM copy so
attention can read it back. Per chunk that is ~3 rectangular passes over a
window that grows with every chunk, so prefilling a P-token prompt moves
O(P²) bytes in copies alone: exactly the term that dominates time-to-first-
token for the long-prompt / shared-prefix traffic the paged engine targets.

Here the block table drives the DMA directly, mirroring the decode kernel
(`exaq_paged_attention`): the grid is ``(kv_head, chunk)`` over one request's
table, the table and the chunk's window length ride the *scalar-prefetch*
channel, and the K/V BlockSpec index maps pull one pool block per grid step
straight into VMEM. The dense window copy never exists; the scatter that
precedes the attend (quantize-on-scatter with §6 scale seeding for int8
pools) is unchanged and shared with the gather path.

Chunk-combine semantics are the two-pass global grid of
``exaq_softmax_chunked`` (exact Algo. 2, DESIGN.md §2): pass 1 reduces each
query row's max over every block it may attend to, pass 2 re-reads K,
quantizes all scores on the grid anchored at that max, and accumulates the
PV numerator plus the 2^M-bin histogram denominator. Counts on a shared grid
add exactly across blocks AND across prefill chunks (each chunk anchors at
its own rows' true global maxes), so a prompt prefilled in chunks through
this kernel is bit-identical to a one-shot prefill and matches the gather
oracle (``kernels.ops.paged_prefill_attention`` with ``use_kernel=False``)
to fp32 roundoff.

Causality is by *global position*: chunk row ``i`` sits at position
``start + i`` and attends to window columns ``<= start + i``. Table entries
at or past ``ceil((start + C) / bs)`` can never be attended (the newest row
caps the window), so their index maps pin to the null block — consecutive
identical indices collapse to one DMA and bytes moved track ``start + C``,
not the padded table width. V's pass-1 index map is pinned the same way, so
V crosses HBM once: ~2×K + 1×V of live-window bytes per chunk, vs the
gather's live pool read plus two rectangular passes over the dense copy
(see ``paged_prefill_bytes_model``).

GQA is native: q is laid out ``(KV, group·C, Dh)`` so one kv head's query
group forms the q-block rows — K/V are never repeated ``group`` times.

Int8 pools (DESIGN.md §6): the per-(block, kv-head) dequant scales ride the
scalar-prefetch channel beside the table and each K/V block is dequantized
in VMEM right after its 8-bit DMA lands, before the EXAQ clip/LUT stages —
identical to the dequantizing gather oracle, so parity holds at int8 too.

Packed int4 pools (DESIGN.md §10) mirror the decode kernel: the pool's last
dim is ``Dh/2`` packed uint8 nibbles, the (N, KV, n_sub) sub-block scale
codes join the block scales on the scalar-prefetch channel, and each block
is nibble-split and scaled ``block_scale * sub_code / 15`` per sub-block
row group in VMEM right after its half-width DMA — no unpacked or
dequantized copy ever exists in HBM. q/out/acc live at the unpacked width
``2 * lane_pad(Dh/2)``: q's zero lane-padding nulls the K-side garbage
padded nibbles decode to, and V-side garbage lands in output lanes >= Dh
that the final slice drops.

Layouts: q ``(1, H, C, Dh)``; pool_k/pool_v the whole stacked planes
``(L, N, KV, bs, Dh)`` (int4: ``(L, N, KV, bs, Dh/2)`` uint8) and ``layer``,
scalar-prefetched: the K/V index maps return ``(layer, block, head, 0, 0)``,
so the pool the model carries through its layer loop is read in place, with
no per-layer slice (DESIGN.md §3); block_table ``(MB,)`` int32; start scalar
int32 (tokens already cached); optional k_scale/v_scale ``(L, N, KV)`` fp32
and int4-only k_sub/v_sub ``(L, N, KV, n_sub)`` uint8, of which only
``layer``'s reach SMEM. Compiled-mode tiling wants ``bs`` a multiple of 8
and the pool's last dim lane-padded (production shapes satisfy both; a
narrower pool has its one layer sliced out and padded, and tests run
interpret mode where any shape goes).

Tensor-parallel contract (DESIGN.md §9): under a mesh whose 'model' axis
divides KV, ``kernels.ops.paged_prefill_attention`` wraps this kernel in a
shard_map that splits q's H axis and the pool's KV axis by the same factor
and replicates table/start/scalars. Inside the shard_map the kernel sees
the *local* head partition, its grid's kv_head axis runs over local heads
only, and GQA group alignment is preserved (H and KV shard by the same
factor) — no global-head offsets in the index maps, and per-(row, head)
outputs are computed whole on one shard, so the sharded kernel is bit-exact
vs the single-shard dispatch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# constants, block dequant and the accumulate stage are shared with the
# decode kernel: the two paged kernels must mask, pad, dequantize and
# quantize identically for the decode-vs-prefill parity contract to hold
from repro.kernels.exaq_paged_attention import (
    _LANES,
    _NEG_BIG,
    _round_up,
    exaq_accumulate_stage,
    layer_operands,
    load_pool_block,
)
from repro.kernels.kv_codec import kv4_num_sub


def _paged_prefill_kernel(
    table_ref,
    info_ref,
    *refs,
    bs: int,
    mb: int,
    block_q: int,
    chunk: int,
    group: int,
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
    """Grid (KV, 2*MB): table entries 0..MB-1 are the max pass, MB..2*MB-1
    the quantize+accumulate pass. Scratch (m, l, acc) carries across the
    chunk axis; the BlockSpec index maps (not this body) steer the pool DMA.
    ``info_ref`` is (3,): [start, start + C, layer] — row positions, the live
    window length, and the layer of the stacked pool the index maps read. ``kv_quant`` pools carry two extra scalar-prefetch refs,
    the per-(block, kv-head) dequant scales (DESIGN.md §6); ``kv_int4``
    pools carry two more — the (N, KV, n_sub) sub-block scale codes — and
    their K/V refs hold *packed* nibbles at half width (DESIGN.md §10)."""
    if kv_int4:
        (ksc_ref, vsc_ref, ksub_ref, vsub_ref,
         q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref) = refs
    elif kv_quant:
        ksub_ref = vsub_ref = None
        ksc_ref, vsc_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        ksc_ref = vsc_ref = ksub_ref = vsub_ref = None
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    head = pl.program_id(0)
    j = pl.program_id(1)
    t = j % mb  # table entry this step touches (same in both passes)
    start = info_ref[0]
    win = info_ref[1]  # start + C: the newest row caps the window
    live = t * bs < win
    blk = jnp.where(live, table_ref[t], 0)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_BIG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # q rows are (group, C) flattened as r = g*C + i: row r's global query
    # position is start + (r % C); rows past group*C are lane padding
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, bs), 0)
    col = t * bs + jax.lax.broadcasted_iota(jnp.int32, (block_q, bs), 1)
    valid = (rows < group * chunk) & (col <= start + rows % chunk)

    def _load_kv(ref, sc_ref, sub_ref):
        return load_pool_block(ref, sc_ref, sub_ref, blk * kv + head, bs=bs, kv_quant=kv_quant,
                               kv_int4=kv_int4, n_sub=n_sub, sub_bs=sub_bs)

    def _scores():
        q = q_ref[0].astype(jnp.float32)
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
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30))[None].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("params", "scale", "interpret"),
)
def exaq_paged_prefill_attention(
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    block_table: jnp.ndarray,
    start,
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
    """Fused chunked-prefill EXAQ attention for one request over layer
    ``layer`` of a block pool.

    q: (1, H, C, D) the chunk's projected queries (rows at global positions
    ``start + i``); pool_k/pool_v: (L, N, KV, bs, D) stacked over layers,
    with this chunk's K/V already scattered into ``layer``; layer: scalar
    int32; block_table: (MB,) int32 block ids (null-block padded); start:
    scalar int32 tokens cached before this chunk. An int8 pool additionally
    takes k_scale/v_scale (L, N, KV) fp32 dequant scales (DESIGN.md §6),
    whose layer is scalar-prefetched beside the table. A packed int4 pool
    (uint8 payload at last dim D/2, DESIGN.md §10) also takes k_sub/v_sub
    (L, N, KV, n_sub) uint8 sub-block scale codes; nibbles unpack in VMEM
    after each half-width block DMA. Returns
    (1, H, C, D) fp32. Global-grid (exact Algo. 2) semantics — bit-identical
    to a one-shot prefill of the same window.
    """
    _, H, C, D = q.shape
    KV, bs = pool_k.shape[2:4]
    MB = block_table.shape[0]
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
    q = q[0].reshape(KV, group, C, D).reshape(KV, group * C, D)
    block_q = _round_up(max(group * C, 8), 8)
    if block_q != group * C:
        q = jnp.pad(q, ((0, 0), (0, block_q - group * C), (0, 0)))
    if kv_int4:
        if D % 2 or pool_k.shape[-1] != D // 2:
            raise ValueError(
                f"packed int4 pool last dim must be head_dim/2 "
                f"(got pool {pool_k.shape[-1]}, head_dim {D})"
            )
        n_sub = k_sub.shape[-1]
        sub_bs = bs // n_sub
        # packed payload lane-pads at its own (half) width; q/out/acc live at
        # the unpacked width 2*Pp (zero q padding nulls K-side garbage, the
        # V-side garbage lands in output lanes >= D sliced off below)
        p_pad = _round_up(max(D // 2, _LANES), _LANES)
        kv_width = p_pad
        d_pad = 2 * p_pad
        if d_pad != D:
            q = jnp.pad(q, ((0, 0), (0, 0), (0, d_pad - D)))
    else:
        n_sub = sub_bs = 0
        d_pad = _round_up(max(D, _LANES), _LANES)
        kv_width = d_pad
        if d_pad != D:
            # production head dims are lane-aligned; the pad only fires on the
            # small shapes tests use (interpret mode), never on the serving path
            q = jnp.pad(q, ((0, 0), (0, 0), (0, d_pad - D)))
    pool_k, pool_v, layer, planes = layer_operands(
        pool_k, pool_v, layer, kv_width,
        tuple(p for p in (k_scale, v_scale, k_sub, v_sub) if p is not None))

    table = block_table.astype(jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    info = jnp.concatenate([jnp.stack([start, start + C]), layer])
    lut = tuple(float(x) for x in params.lut_np())

    def _k_index(h, j, tbl, inf, *sc):
        # future/dead-tail entries -> null block; consecutive identical
        # indices are a single DMA, so bytes track start + C, not MB*bs
        t = j % MB
        return (inf[2], jnp.where(t * bs < inf[1], tbl[t], 0), h, 0, 0)

    def _v_index(h, j, tbl, inf, *sc):
        # V is only consumed by the accumulate pass; pin the max pass (and
        # future blocks) to the null block so V moves over HBM exactly once
        t = j % MB
        return (inf[2], jnp.where((j >= MB) & (t * bs < inf[1]), tbl[t], 0), h, 0, 0)

    def _q_index(h, j, tbl, inf, *sc):
        return (h, 0, 0)

    # the layer's scale planes prefetch flat, as in the decode kernel
    prefetch = (table, info) + planes
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(KV, 2 * MB),
        in_specs=[
            pl.BlockSpec((1, block_q, d_pad), _q_index),
            pl.BlockSpec((None, 1, 1, bs, kv_width), _k_index),
            pl.BlockSpec((None, 1, 1, bs, kv_width), _v_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d_pad), _q_index),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d_pad), jnp.float32),
        ],
    )
    kern = functools.partial(
        _paged_prefill_kernel,
        bs=bs, mb=MB, block_q=block_q, chunk=C, group=group,
        levels=params.levels, clip=float(params.clip), lut=lut, scale=float(scale),
        kv=KV, kv_quant=kv_quant, kv_int4=kv_int4, n_sub=n_sub, sub_bs=sub_bs,
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KV, block_q, d_pad), jnp.float32),
        # only the chunk axis carries scratch state; kv-head programs are
        # independent and may partition across cores
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*prefetch, q, pool_k, pool_v)
    return out[:, : group * C, :D].reshape(KV * group, C, D)[None]


def paged_prefill_bytes_model(
    *,
    prompt_len: int,
    chunk: int,
    kv_heads: int,
    max_blocks: int,
    block_size: int,
    head_dim: int,
    start_cached: int = 0,
    dtype_bytes: int = 2,
    kv_dtype: str | None = None,
    tp: int = 1,
) -> dict:
    """Modeled HBM KV bytes per layer to prefill one prompt, gather vs fused.

    Chunked prefill runs ``ceil((prompt_len - start_cached) / chunk)`` chunks;
    at each, the window is ``start + C`` tokens. gather_then_attend:
    ``gather_block_kv`` reads the window's *live* blocks from the pool,
    writes the dense rectangular ``max_blocks``-wide copy, and attention
    reads the copy back — (live + 2 × rect) passes over each of K and V,
    every chunk, so copy bytes grow O(prompt²). fused_pool_read: the kernel
    touches live blocks only — K twice (max + accumulate pass), V once. The
    O(C·Dh) scatter is identical on both paths and excluded. Pure arithmetic
    so benchmarks and tests can assert the ≥2x bandwidth win without
    hardware counters.

    ``kv_dtype`` ("fp32" | "bf16" | "int8" | "int4") sizes the pool element
    instead of the raw ``dtype_bytes`` knob; int8 (DESIGN.md §6) adds the
    4-byte per-(block, kv-head) scale to every pool-block read and prices
    the gather path's dense dequantized copy at fp32 width. int4
    (DESIGN.md §10) halves the payload to packed nibbles and adds one uint8
    sub-block scale code per ``KV_SUB_BLOCK`` tokens on top of the fp32
    block scale; its dense copy is fp32-priced too.

    ``tp`` models the tensor-parallel pool split (DESIGN.md §9): each shard
    reads ``kv_heads / tp`` heads of every block, so the figures are
    per-shard bytes. ``tp`` must divide ``kv_heads`` (non-divisible counts
    serve a replicated pool; model that as tp=1).
    """
    from repro.kernels.exaq_paged_attention import KV_DTYPE_BYTES

    if kv_heads % tp:
        raise ValueError(f"tp={tp} must divide kv_heads={kv_heads} (replicated fallback is tp=1)")
    kv_heads //= tp
    if kv_dtype is not None:
        dtype_bytes = KV_DTYPE_BYTES[kv_dtype]
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
    block_bytes = payload_bytes + scale_bytes
    dense_block_bytes = kv_heads * block_size * head_dim * dense_bytes_elt

    gather = fused = live_sum = chunks = 0
    start = start_cached
    while start < prompt_len:
        c = min(chunk, prompt_len - start)
        live = -(-(start + c) // block_size)
        gather += (live * block_bytes + 2 * max_blocks * dense_block_bytes) * 2
        fused += live * (2 + 1) * block_bytes  # 2x K + 1x V, live blocks only
        live_sum += live
        start += c
        chunks += 1
    return {
        "kv_dtype": kv_dtype,
        "tp": tp,
        "prompt_len": prompt_len,
        "chunk": chunk,
        "chunks": chunks,
        "gather_then_attend_bytes": int(gather),
        "fused_pool_read_bytes": int(fused),
        "bytes_reduction_x": gather / max(fused, 1),
        "live_block_reads": int(live_sum),
        "rect_blocks_per_chunk": int(max_blocks),
        "block_bytes": int(block_bytes),
    }
