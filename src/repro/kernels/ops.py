"""Public kernel entry points.

Auto-selects Pallas (TPU) vs interpret mode (CPU validation) vs pure-jnp
reference, and provides the chunked two-pass path for rows too long for one
VMEM tile. All functions are shape-polymorphic over leading dims.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quantizer import QuantParams
from repro.kernels import ref
from repro.kernels.exaq_attention import exaq_decode_attention, flash_exaq_attention
from repro.kernels.exaq_paged_attention import exaq_paged_decode_attention
from repro.kernels.exaq_paged_prefill import exaq_paged_prefill_attention
from repro.kernels.exaq_softmax import exaq_softmax_pallas

# Rows longer than this take the chunked path (fp32 row bytes vs ~16 MiB VMEM).
MAX_FUSED_COLS = 32768


@functools.lru_cache(maxsize=1)
def on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def exaq_softmax(
    x: jnp.ndarray,
    params: QuantParams,
    lens: jnp.ndarray | None = None,
    *,
    block_rows: int = 8,
    use_kernel: bool = True,
) -> jnp.ndarray:
    """EXAQ softmax over the last axis (paper Algo. 2)."""
    n = x.shape[-1]
    if not use_kernel:
        return ref.exaq_softmax_ref(x, params, lens=lens)
    if n > MAX_FUSED_COLS:
        return exaq_softmax_chunked(x, params, lens=lens)
    return exaq_softmax_pallas(x, params, lens, block_rows=block_rows, interpret=on_cpu())


def exaq_softmax_chunked(
    x: jnp.ndarray,
    params: QuantParams,
    lens: jnp.ndarray | None = None,
    chunk: int = 16384,
) -> jnp.ndarray:
    """Two-pass EXAQ softmax for very long rows (e.g. 512k decode scores).

    Pass 1 scans ``chunk``-sized slices for the global row max. Pass 2
    re-scans, quantizing each slice on the grid anchored at that max and
    accumulating the 2^M *integer* histogram partials — counts compose exactly
    across chunks because the grid is global, the same property the
    distributed seq-parallel combine exploits (counts all-reduce). The
    savings vs the one-shot path is in the *intermediates*: no fp32
    LUT-select tensor or int32 code tensor is ever materialized row-wide —
    each scan step touches one chunk and the row-wide residue is the narrow
    integer codes (int8 up to 7-bit quantizers), which the final LUT + divide
    replays chunk-by-chunk. (The fp32 input itself stays live as the scan
    operand; XLA may alias it, but don't budget on that.)
    """
    xf = x.astype(jnp.float32)
    orig_shape = xf.shape
    n = orig_shape[-1]
    rows = 1
    for d in orig_shape[:-1]:
        rows *= d
    xf = xf.reshape(rows, n)
    eff = (lens.reshape(rows).astype(jnp.int32) if lens is not None
           else jnp.full((rows,), n, jnp.int32))
    nc = -(-n // chunk)
    if nc * chunk != n:
        xf = jnp.pad(xf, ((0, 0), (0, nc * chunk - n)))
    # chunk axis leads so lax.scan slices one (rows, chunk) tile per step
    xc = jnp.moveaxis(xf.reshape(rows, nc, chunk), 1, 0)
    cols = jnp.arange(chunk, dtype=jnp.int32)
    levels = params.levels
    inv_delta = levels / (-params.clip)
    lutv = tuple(float(v) for v in params.lut_np())
    # int8 halves the stored-codes footprint but only holds codes <= 127
    code_dtype = jnp.int8 if levels <= 128 else jnp.int32

    def chunk_valid(j):
        return (j * chunk + cols)[None, :] < eff[:, None]  # (rows, chunk)

    # ---- pass 1: global row max over chunks
    def max_body(m, xs):
        sl, j = xs
        m_j = jnp.max(jnp.where(chunk_valid(j), sl, -1e30), axis=-1)
        return jnp.maximum(m, m_j), None

    m, _ = jax.lax.scan(max_body, jnp.full((rows,), -1e30, jnp.float32),
                        (xc, jnp.arange(nc)))
    m = m[:, None]

    # ---- pass 2: per-chunk quantize + histogram partials (int accumulators)
    def quant_body(counts, xs):
        sl, j = xs
        valid = chunk_valid(j)
        codes = jnp.clip(
            jnp.floor((sl - m - params.clip) * inv_delta), 0, levels - 1
        ).astype(code_dtype)
        onehot = (codes[..., None] == jnp.arange(levels, dtype=code_dtype)) & valid[..., None]
        counts = counts + jnp.sum(onehot, axis=1, dtype=jnp.int32)  # (rows, levels)
        return counts, codes

    counts, codes = jax.lax.scan(
        quant_body, jnp.zeros((rows, levels), jnp.int32), (xc, jnp.arange(nc))
    )
    denom = counts.astype(jnp.float32) @ jnp.asarray(lutv, jnp.float32)  # (rows,)
    denom = jnp.maximum(denom, 1e-30)[:, None]

    # ---- emit: LUT + normalize, replayed from the stored int8 codes
    def emit_body(_, xs):
        cj, j = xs
        e = jnp.where(chunk_valid(j), ref._lut_select(cj, lutv), 0.0)
        return None, e / denom

    _, out = jax.lax.scan(emit_body, None, (codes, jnp.arange(nc)))
    out = jnp.moveaxis(out, 0, 1).reshape(rows, nc * chunk)[:, :n]
    return out.reshape(orig_shape).astype(x.dtype)


def exaq_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    params: QuantParams,
    scale: float,
    causal: bool = True,
    *,
    block_q: int = 128,
    block_kv: int = 128,
    use_kernel: bool = True,
) -> jnp.ndarray:
    """Fused flash attention with EXAQ softmax. q:(B,H,Sq,D) k,v:(B,Hkv,Skv,D)."""
    if not use_kernel:
        kr, vr = _repeat_kv(q, k, v)
        return ref.flash_exaq_attention_ref(q, kr, vr, params, scale, causal=causal, block_kv=block_kv)
    return flash_exaq_attention(
        q, k, v, params, scale, causal, block_q=block_q, block_kv=block_kv, interpret=on_cpu()
    )


def window_valid_mask(width: int, upto: jnp.ndarray) -> jnp.ndarray:
    """Per-row live-window mask for paged attention windows.

    ``upto``: (S, Q) int32 *exclusive* upper bounds — decode passes
    ``kv_lens[:, None]`` (each slot's single query row sees [0, len)),
    chunked prefill passes ``start + row + 1`` per chunk row (causal by
    global position). Returns (S, 1, Q, width) bool, broadcast over heads —
    the ONE construction of the window-validity mask, shared by
    ``attention_decode_paged``, ``attention_prefill_chunk``, and the fused
    kernels' gather oracles (they must mask identically for parity to hold).
    """
    cols = jnp.arange(width, dtype=jnp.int32)
    return cols[None, None, None, :] < upto[:, None, :, None]


def exaq_weights_ref(s: jnp.ndarray, valid: jnp.ndarray, params: QuantParams) -> jnp.ndarray:
    """Global-grid Algo. 2 weights from raw scores (..., Q, W): anchor at the
    masked row max, quantize, LUT, zero masked lanes, normalize (guarded).
    The jnp oracle both paged kernels are tested against."""
    m = jnp.max(jnp.where(valid, s, -1e30), axis=-1, keepdims=True)
    inv_delta = params.levels / (-params.clip)
    codes = jnp.clip(jnp.floor((s - m - params.clip) * inv_delta), 0, params.levels - 1)
    lutv = tuple(float(v) for v in params.lut_np())
    e = jnp.where(valid, ref._lut_select(codes, lutv), 0.0)
    return e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)


def decode_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    kv_lens: jnp.ndarray,
    params: QuantParams,
    scale: float,
    *,
    block_kv: int = 512,
    use_kernel: bool = True,
) -> jnp.ndarray:
    """Single-step decode attention over a KV cache with EXAQ softmax."""
    if not use_kernel:
        kr, vr = _repeat_kv(q, k, v)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kr).astype(jnp.float32) * scale
        valid = window_valid_mask(kr.shape[2], kv_lens.astype(jnp.int32)[:, None])
        p = exaq_weights_ref(s, valid, params)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32))
    return exaq_decode_attention(q, k, v, kv_lens, params, scale, block_kv=block_kv, interpret=on_cpu())


def repeat_kv(x: jnp.ndarray, group: int) -> jnp.ndarray:
    """Broadcast kv heads to the query-head count for GQA: (…, KV, S, Dh) ->
    (…, KV*group, S, Dh). The ONE shared implementation — model paths and
    kernel references both route here; fused kernels avoid the repeat
    entirely via kv-index maps / grouped-q layouts, so any call to this is
    a materialized group-factor copy worth engineering away."""
    if group == 1:
        return x
    return jnp.repeat(x, group, axis=1)


def _repeat_kv(q, k, v):
    group = q.shape[1] // k.shape[1]
    return repeat_kv(k, group), repeat_kv(v, group)


# ------------------------------------------------- quantized KV-pool codec

# The int8 per-block-scale and packed-int4 sub-block-scale codecs live in
# kernels/kv_codec.py (the fused kernels import from there directly — an
# import of this module back would be circular) and are re-exported here as
# the public API the engine, scatter paths, and tests use.
from repro.kernels.kv_codec import (  # noqa: E402, F401
    INT4_BIAS,
    INT4_QMAX,
    INT4_SUB_LEVELS,
    INV_SUB_LEVELS,
    KV_QMAX,
    KV_SCALE_MARGIN,
    KV_SUB_BLOCK,
    kv4_dequantize_block,
    kv4_effective_scale,
    kv4_num_sub,
    kv4_quantize,
    kv4_sub_block,
    kv4_write_block_scales,
    kv4_write_sub_scales,
    kv_cache_is_int4,
    kv_cache_is_quantized,
    kv_pack_int4,
    kv_quantize,
    kv_unpack_int4,
    kv_write_scales,
)


def gather_block_kv(pool_k: jnp.ndarray, pool_v: jnp.ndarray, block_tables: jnp.ndarray,
                    kv_lens: jnp.ndarray | None = None,
                    k_scale: jnp.ndarray | None = None,
                    v_scale: jnp.ndarray | None = None,
                    k_sub: jnp.ndarray | None = None,
                    v_sub: jnp.ndarray | None = None,
                    *, layer=0):
    """Assemble per-slot contiguous KV from a paged block pool (DESIGN.md §3).

    pool_{k,v}: (L, N, KV, bs, Dh) global block pool stacked over layers, of
    which layer ``layer`` is read — indexed ``pool[layer, ids]`` in one
    gather, so no slice of the whole layer is cut out; block_tables: (S, MB)
    int32 block ids per slot -> (S, KV, MB*bs, Dh) laid out in block-table order, so
    flat position ``p`` of slot ``s`` is block ``block_tables[s, p // bs]``
    offset ``p % bs`` — the invariant every paged caller masks against via
    ``kv_lens``. Table padding (the null block, id 0) gathers garbage that the
    length mask excludes.

    ``kv_lens`` — (S,) live tokens per slot, or a scalar broadcast to every
    slot (the chunked-prefill call site passes its scalar window length
    ``start + C`` directly) — when given, clamps each slot's table to its
    live block count (ceil(len/bs)): dead-tail entries are redirected to the
    null block before the gather, so the reference path reads each slot's
    live blocks plus one shared null block instead of the full rectangular
    table (shapes stay static — the clamp is a ``where``, not a slice, so it
    works under jit with traced lengths). Results are unchanged: dead-tail
    lanes were always masked out by the caller.

    ``k_scale``/``v_scale`` (L, N, KV) fp32, required for an int8 pool
    (DESIGN.md §6): each gathered block is dequantized ``codes * scale``
    before assembly, so callers always see fp values — this is the
    *dequantizing oracle* the fused int8 kernel is tested against. A packed
    int4 pool (uint8 payload, DESIGN.md §10) additionally requires the
    ``k_sub``/``v_sub`` (L, N, KV, n_sub) uint8 sub-block scale codes; blocks
    are nibble-unpacked and dequantized at
    ``block_scale * sub_code / 15`` per sub-block during assembly — the
    dequantizing oracle of the fused int4 kernels.

    The gather still materializes each slot's window once per layer; the
    fused kernel (``kernels/exaq_paged_attention.py``) streams blocks
    through VMEM instead and is the serving hot path. This stays as the
    interpret-mode / oracle reference.
    """
    int8_pool = pool_k.dtype == jnp.int8
    int4_pool = pool_k.dtype == jnp.uint8
    want_scales = int8_pool or int4_pool
    if (k_scale is not None) != want_scales or (v_scale is not None) != want_scales:
        raise ValueError(
            "quantized (int8/int4) pools require both k_scale and v_scale; fp pools forbid them"
        )
    if (k_sub is not None) != int4_pool or (v_sub is not None) != int4_pool:
        raise ValueError(
            "packed int4 pools require both k_sub and v_sub sub-scale planes; "
            "other pools forbid them"
        )
    if kv_lens is not None:
        MB = block_tables.shape[1]
        bs = pool_k.shape[3]
        kv_lens = jnp.broadcast_to(jnp.asarray(kv_lens, jnp.int32).reshape(-1),
                                   (block_tables.shape[0],))
        live = jnp.arange(MB, dtype=jnp.int32)[None, :] * bs < kv_lens[:, None]
        block_tables = jnp.where(live, block_tables, 0)  # 0 == kv_pool.NULL_BLOCK

    def g(pool, scale, sub):
        b = pool[layer, block_tables]  # (S, MB, KV, bs, Dh) — or (…, bs, Dh//2) packed
        if sub is not None:
            b = kv4_dequantize_block(b, scale[layer, block_tables], sub[layer, block_tables])
        elif scale is not None:
            b = b.astype(jnp.float32) * scale[layer, block_tables][..., None, None]
        b = jnp.swapaxes(b, 1, 2)  # (S, KV, MB, bs, Dh)
        S, KV, MB, bs, Dh = b.shape
        return b.reshape(S, KV, MB * bs, Dh)

    return g(pool_k, k_scale, k_sub), g(pool_v, v_scale, v_sub)


# ------------------------------------------------ tensor-parallel dispatch

def _tp_mesh(num_kv_heads: int):
    """The ambient mesh, when its 'model' axis can split the kv heads.

    Trace-time discovery: the serving device layer (runtime/device_step.py)
    activates its mesh via ``sharding.use_mesh`` around every jitted call, so
    this sees it while the engine functions are being traced. No mesh, a
    1-sized 'model' axis, or a head count the axis does not divide all
    return None — the caller stays on the single-shard path, mirroring
    ``sharding.block_pool_spec``'s replicated fallback (DESIGN.md §9).
    """
    from repro.runtime import sharding as shd

    mesh = shd.current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return None
    tp = mesh.shape["model"]
    if tp <= 1 or num_kv_heads % tp != 0:
        return None
    return mesh


def _tp_paged_attention(mesh, local_fn, q, planes, table_args):
    """shard_map a fused paged kernel over the 'model' axis (DESIGN.md §9).

    ``q`` shards its head axis 1; ``planes`` — the two stacked pool planes
    and any quantized-pool scale planes, every one (L, N, KV, ...) — shard
    their kv-head axis 2, so each shard DMAs only its local heads from a
    local pool partition, and int8's (L, N, KV) block-scale planes and
    int4's (L, N, KV, n_sub) sub-code planes hold exactly its heads' scales;
    ``table_args`` (block tables, lens, start, layer) stay replicated
    scalar-prefetch inputs. Because q heads and kv heads shard by the same
    factor, a shard's query group h // group lands exactly on its local kv
    heads — the kernels' index maps need no global-head offsets, and each
    (slot, head) row is computed whole on exactly one shard, so the sharded
    kernel is *bit-exact* against the single-shard one. The output is
    re-replicated before returning: the caller's cross-head ``wo``
    contraction must run whole on every shard, or fp reassociation in a
    partitioned psum would break greedy parity vs a single-shard engine.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    heads = P(None, "model", None, None)  # q and the output are rank 4
    in_specs = (
        (heads,)
        + tuple(P(None, None, "model", *(None,) * (jnp.ndim(a) - 3)) for a in planes)
        + tuple(P(*(None,) * jnp.ndim(a)) for a in table_args)
    )
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=in_specs,
        out_specs=heads,
        check_vma=False,
    )
    out = fn(q, *planes, *table_args)
    return jax.lax.with_sharding_constraint(out, NamedSharding(mesh, P()))


def _given(**planes) -> dict:
    """The scale planes that are given, by name: none for an fp pool, two
    for int8, four for int4."""
    return {k: p for k, p in planes.items() if p is not None}


def paged_decode_attention(
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    block_tables: jnp.ndarray,
    kv_lens: jnp.ndarray,
    params: QuantParams,
    scale: float,
    *,
    layer=0,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    k_sub: jnp.ndarray | None = None,
    v_sub: jnp.ndarray | None = None,
    block_kv: int = 512,
    use_kernel: bool = True,
) -> jnp.ndarray:
    """Decode attention over layer ``layer`` of a block-paged KV cache with
    EXAQ softmax.

    ``use_kernel=True`` (the serving hot path) dispatches the fused Pallas
    kernel (``kernels/exaq_paged_attention.py``): block-table-indexed K/V
    DMA straight from the pool, EXAQ quantize + LUT accumulation in VMEM,
    and the two-pass global-grid chunk combine — no dense KV copy ever
    exists in HBM. On CPU the same kernel runs in interpret mode.

    ``use_kernel=False`` keeps the gather-then-dispatch reference: assemble
    each slot's window (live blocks only — dead tails clamp to the null
    block) and run the global-grid jnp path. Both anchor the quantization
    grid at the global row max, so per-block partial counts add exactly and
    paging composes with the DESIGN.md §2 combine — block boundaries are
    invisible to the softmax, and the two paths agree to fp32 roundoff.

    For an int8 pool (DESIGN.md §6) pass ``k_scale``/``v_scale`` (L, N, KV):
    the fused kernel scalar-prefetches the layer's and dequantizes blocks in
    VMEM; the gather path dequantizes during assembly — either way dequant
    never round-trips through HBM at fp width. For a packed int4 pool
    (DESIGN.md §10) additionally pass ``k_sub``/``v_sub`` (L, N, KV, n_sub):
    the fused kernel unpacks nibbles in VMEM right after each half-width
    block DMA.

    q: (S, H, 1, Dh); pool_{k,v}: (L, N, KV, bs, Dh); layer: scalar int32;
    block_tables: (S, MB); kv_lens: (S,) live positions per slot ->
    (S, H, 1, Dh).
    """
    if use_kernel:
        scales = _given(k_scale=k_scale, v_scale=v_scale, k_sub=k_sub, v_sub=v_sub)

        def local(q, pk, pv, *rest):
            *sc, bt, kl, lyr = rest
            return exaq_paged_decode_attention(
                q, pk, pv, bt, kl, params, scale, layer=lyr,
                **dict(zip(scales, sc)), interpret=on_cpu()
            )

        planes = (pool_k, pool_v, *scales.values())
        table_args = (block_tables, kv_lens, jnp.asarray(layer, jnp.int32))
        mesh = _tp_mesh(pool_k.shape[2])
        if mesh is not None:
            return _tp_paged_attention(mesh, local, q, planes, table_args)
        return local(q, *planes, *table_args)
    k, v = gather_block_kv(pool_k, pool_v, block_tables, kv_lens,
                           k_scale, v_scale, k_sub, v_sub, layer=layer)
    return decode_attention(q, k, v, kv_lens, params, scale, block_kv=block_kv, use_kernel=False)


def paged_prefill_attention(
    q: jnp.ndarray,
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    block_table: jnp.ndarray,
    start,
    params: QuantParams,
    scale: float,
    *,
    layer=0,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    k_sub: jnp.ndarray | None = None,
    v_sub: jnp.ndarray | None = None,
    use_kernel: bool = True,
) -> jnp.ndarray:
    """One chunk of chunked-prefill attention over layer ``layer`` of a
    block-paged KV cache.

    The prefill-side mirror of ``paged_decode_attention`` (DESIGN.md §7):
    the chunk's K/V are already scattered into the pool; this attends the
    chunk's C query rows (global positions ``start + i``) causally against
    the request's whole window, reading K/V from the pool.

    ``use_kernel=True`` (the serving hot path) dispatches the fused Pallas
    kernel (``kernels/exaq_paged_prefill.py``): block-table-indexed K/V DMA
    straight from the pool, EXAQ quantize + LUT accumulation in VMEM, and
    the two-pass global-grid combine — the dense per-chunk window copy the
    gather materializes never exists, so prefill bytes stop growing
    O(prompt²) in copies. On CPU the same kernel runs in interpret mode.

    ``use_kernel=False`` keeps the gather-then-attend reference: assemble
    the window (live blocks only — entries at/past ``ceil((start+C)/bs)``
    clamp to the null block) and run the global-grid jnp path. Both anchor
    each row's quantization grid at its true global max, so chunking is
    invisible to the softmax (§2) and the two paths agree to fp32 roundoff.

    For an int8 pool (DESIGN.md §6) pass ``k_scale``/``v_scale`` (L, N, KV):
    the fused kernel scalar-prefetches the layer's and dequantizes blocks in
    VMEM; the gather path dequantizes during assembly. For a packed int4
    pool (DESIGN.md §10) additionally pass ``k_sub``/``v_sub``
    (L, N, KV, n_sub).

    q: (1, H, C, Dh); pool_{k,v}: (L, N, KV, bs, Dh); layer: scalar int32;
    block_table: (MB,); start: scalar int32 tokens already cached ->
    (1, H, C, Dh) fp32.
    """
    if use_kernel:
        scales = _given(k_scale=k_scale, v_scale=v_scale, k_sub=k_sub, v_sub=v_sub)

        def local(q, pk, pv, *rest):
            *sc, bt, st, lyr = rest
            return exaq_paged_prefill_attention(
                q, pk, pv, bt, st, params, scale, layer=lyr,
                **dict(zip(scales, sc)), interpret=on_cpu()
            )

        planes = (pool_k, pool_v, *scales.values())
        table_args = (block_table, jnp.asarray(start, jnp.int32), jnp.asarray(layer, jnp.int32))
        mesh = _tp_mesh(pool_k.shape[2])
        if mesh is not None:
            return _tp_paged_attention(mesh, local, q, planes, table_args)
        return local(q, *planes, *table_args)
    C = q.shape[2]
    kg, vg = gather_block_kv(pool_k, pool_v, block_table[None], start + C,
                             k_scale, v_scale, k_sub, v_sub, layer=layer)  # (1, KV, W, Dh)
    kk, vv = _repeat_kv(q, kg, vg)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kk).astype(jnp.float32) * scale
    rows = start + jnp.arange(C, dtype=jnp.int32)
    valid = window_valid_mask(kk.shape[2], (rows + 1)[None, :])
    p = exaq_weights_ref(s, valid, params)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
