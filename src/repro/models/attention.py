"""GQA attention with EXAQ softmax as a first-class implementation choice.

Three softmax paths:
  * ``exact``       — jax.nn-style stable softmax (paper Algo. 1).
  * ``exaq/naive``  — paper Algo. 2 with a *traced* per-layer clip value, so a
                      scan over stacked layers can carry per-layer calibrated
                      sigmas. Global-grid semantics (quantize after the full
                      row max), shardable by XLA SPMD — this is the lowering
                      used by the multi-pod dry-run.
  * fused Pallas kernel (repro.kernels) — single-chip hot path; opted in via
                      QuantConfig.use_fused_kernel (not shardable by SPMD).

The train/prefill path scans over query blocks: softmax is row-wise, so
q-blocking is exact (no online rescale) while keeping the score tile
(B, H, bq, Skv) bounded — the pure-jnp analogue of flash attention's memory
behaviour, differentiable and remat-friendly.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.ops import repeat_kv as _repeat_kv
from repro.models.layers import apply_rope, rmsnorm, truncated_normal_init
from repro.runtime.sharding import shard_activation

_NEG_BIG = -1e30


class AttnStatics(NamedTuple):
    impl: str          # exact | exaq | naive
    bits: int
    use_fused_kernel: bool


def quantized_weights(s: jnp.ndarray, clip, bits: int, valid, ste: bool = False) -> jnp.ndarray:
    """Paper Algo. 2 with traced clip: s -> normalized attention weights.

    s: (..., n) fp32 logits; clip: traced scalar (< 0); valid: bool mask or None.
    ste=True uses a straight-through estimator (exact-softmax backward) so the
    quantized forward stays trainable — the paper leaves training to future
    work (§7.2); this is our documented extension.
    """
    e, denom = quantized_weights_unnormalized(s, clip, bits, valid)
    w = e / denom
    if ste:
        w_exact = exact_weights(s, valid)
        w = w_exact + jax.lax.stop_gradient(w - w_exact)
    return w


def quantized_weights_unnormalized(s: jnp.ndarray, clip, bits: int, valid):
    """(e, denom) with e = LUT[codes] unnormalized — callers can fold the
    row-constant normalization into the PV epilogue ((e@V)/denom), removing a
    score-sized divide materialization."""
    levels = 2**bits
    # Fold the mask into the max reduction ONLY (where->reduce fuses without
    # materializing); codes come from the RAW scores — invalid lanes produce
    # garbage codes that the select chain zeroes. Masking the scores first
    # feeds two consumers (max + quantize) and forces XLA to materialize a
    # score-sized select per block (measured ~1.1 TB/step on yi-6b prefill).
    m = jnp.max(jnp.where(valid, s, _NEG_BIG) if valid is not None else s, axis=-1, keepdims=True)
    delta = -clip / levels
    codes = jnp.clip(jnp.floor((s - m - clip) / delta), 0, levels - 1).astype(jnp.int32)
    # LUT lookup as a select chain: jnp.take lowers to a gather, which BREAKS
    # XLA fusion and materializes a score-sized tensor per layer. Selects over
    # 2^M scalars fuse into one elementwise pass — the same form the Pallas
    # kernel uses on the VPU.
    lut = jnp.exp(clip + (jnp.arange(levels, dtype=jnp.float32) + 0.5) * delta)  # (levels,)
    e = jnp.full(codes.shape, 1.0, jnp.float32) * lut[0]
    for k in range(1, levels):
        e = jnp.where(codes == k, lut[k], e)
    if valid is not None:
        e = jnp.where(valid, e, 0.0)
    denom = jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    return e, denom


def exact_weights(s: jnp.ndarray, valid) -> jnp.ndarray:
    # mask folded into the max reduce only (fuses); exp of raw invalid lanes
    # may overflow to +inf but the select replaces them before use
    m = jnp.max(jnp.where(valid, s, _NEG_BIG) if valid is not None else s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    if valid is not None:
        e = jnp.where(valid, e, 0.0)
    return e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)


def _weights(s, statics: AttnStatics, clip, valid, ste: bool = False):
    if statics.impl == "exact":
        return exact_weights(s, valid)
    return quantized_weights(s, clip, statics.bits, valid, ste=ste)


# ------------------------------------------------------------------ module

def init_attention(key, cfg, d_in: int | None = None, dtype=jnp.float32) -> dict:
    """cfg: ModelConfig-like (num_heads, num_kv_heads, resolved_head_dim, qk_norm)."""
    d_in = d_in or cfg.d_model
    dh = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": truncated_normal_init(ks[0], (d_in, cfg.num_heads * dh), d_in**-0.5, dtype),
        "wk": truncated_normal_init(ks[1], (d_in, cfg.num_kv_heads * dh), d_in**-0.5, dtype),
        "wv": truncated_normal_init(ks[2], (d_in, cfg.num_kv_heads * dh), d_in**-0.5, dtype),
        "wo": truncated_normal_init(ks[3], (cfg.num_heads * dh, cfg.d_model), (cfg.num_heads * dh) ** -0.5, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


def _project_qkv(params, x, cfg, positions, rope: bool):
    B, S, _ = x.shape
    dh = cfg.resolved_head_dim
    q = jnp.einsum("bsd,de->bse", x, params["wq"].astype(x.dtype)).reshape(B, S, cfg.num_heads, dh)
    k = jnp.einsum("bsd,de->bse", x, params["wk"].astype(x.dtype)).reshape(B, S, cfg.num_kv_heads, dh)
    v = jnp.einsum("bsd,de->bse", x, params["wv"].astype(x.dtype)).reshape(B, S, cfg.num_kv_heads, dh)
    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def blocked_attention(
    q, k, v, statics: AttnStatics, clip, *, causal: bool, block_q: int = 512, ste: bool = True,
    scores_bf16: bool = False
):
    """Exact q-blocked attention: q (B,H,Sq,Dh); k,v (B,H,Skv,Dh) -> (B,H,Sq,Dh).

    Row-wise softmax over the full kv length per q block (global grid — exact
    Algo. 2 semantics). Scans q blocks to bound the live score tile. When the
    head count doesn't divide TP, the 'qrows' rule shards the q-block rows
    over 'model' instead (sequence-parallel attention — softmax is row-wise,
    so this is exact and collective-free). scores_bf16 halves the score
    traffic; with 2-bit EXAQ quantization downstream the extra rounding is
    far below the quantization step.
    """
    B, H, Sq, Dh = q.shape
    Skv = k.shape[2]
    scale = Dh**-0.5
    offset = Skv - Sq
    nblk = -(-Sq // block_q)
    pad = nblk * block_q - Sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qb = q.reshape(B, H, nblk, block_q, Dh)
    kv_ids = jnp.arange(Skv, dtype=jnp.int32)
    sdt = jnp.bfloat16 if scores_bf16 else jnp.float32

    def body(carry, xs):
        (qi, idx) = xs
        qi = shard_activation(qi, "qrows")  # (B, H, block_q, Dh)
        s = jnp.einsum("bhqd,bhkd->bhqk", qi, k, preferred_element_type=jnp.float32)
        s = shard_activation((s * scale).astype(sdt), "score_rows").astype(jnp.float32)
        if causal:
            row = idx * block_q + jnp.arange(block_q, dtype=jnp.int32) + offset
            valid = kv_ids[None, None, None, :] <= row[None, None, :, None]
        else:
            valid = None
        if not ste and statics.impl in ("exaq", "naive"):
            # normalization folded into the PV epilogue: (e @ V) / denom —
            # the normalized-weights tensor never materializes
            e, denom = quantized_weights_unnormalized(s, clip, statics.bits, valid)
            o = jnp.einsum("bhqk,bhkd->bhqd", e.astype(v.dtype), v) / denom.astype(v.dtype)
        else:
            w = _weights(s, statics, clip, valid, ste=ste)
            o = jnp.einsum("bhqk,bhkd->bhqd", w.astype(v.dtype), v)
        o = shard_activation(o, "qrows")
        return carry, o

    _, outs = jax.lax.scan(body, None, (jnp.moveaxis(qb, 2, 0), jnp.arange(nblk)))
    out = jnp.moveaxis(outs, 0, 2).reshape(B, H, nblk * block_q, Dh)
    return out[:, :, :Sq]


def attention_score_stats(params, x, cfg):
    """Calibration probe (paper §5.1.1): sigma and min of the max-subtracted
    causal attention logits for this layer. x: (B, S, D)."""
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    q, k, v = _project_qkv(params, x, cfg, positions, rope=True)
    del v
    qh, kh = jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)
    group = cfg.num_heads // cfg.num_kv_heads
    kh = _repeat_kv(kh, group)
    dh = cfg.resolved_head_dim
    s = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32), kh.astype(jnp.float32)) * dh**-0.5
    valid = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(valid[None, None], s, jnp.nan)
    m = jnp.nanmax(s, axis=-1, keepdims=True)
    sh = s - m
    # masked streaming moments
    cnt = jnp.sum(valid) * B * cfg.num_heads
    mean = jnp.nansum(sh) / cnt
    var = jnp.nansum(jnp.where(jnp.isnan(sh), 0.0, (sh - mean) ** 2)) / cnt
    return jnp.sqrt(var), jnp.nanmin(sh)


def attention_train(params, x, cfg, statics: AttnStatics, clip, *, causal=True, block_q=512):
    """Full-sequence attention (training / encoder). x: (B, S, D_in)."""
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    q, k, v = _project_qkv(params, x, cfg, positions, rope=not cfg.enc_dec or causal)
    q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))  # (B, N, S, Dh)
    group = cfg.num_heads // cfg.num_kv_heads
    k, v = _repeat_kv(k, group), _repeat_kv(v, group)
    q = shard_activation(q, "heads")
    k = shard_activation(k, "heads")
    v = shard_activation(v, "heads")
    o = blocked_attention(q, k, v, statics, clip, causal=causal,
                          block_q=max(block_q, cfg.attn_block_q), ste=True,
                          scores_bf16=cfg.attn_scores_bf16)
    o = jnp.swapaxes(o, 1, 2).reshape(B, S, -1).astype(x.dtype)
    return jnp.einsum("bse,ed->bsd", o, params["wo"].astype(x.dtype))


def _fused_prefill_attention(qh, kh, vh, cfg, statics: AttnStatics):
    """Fused flash-EXAQ Pallas kernel for prefill — scores never leave VMEM.

    Under a mesh: shard_map over (data=batch, model=heads); each shard slices
    the kv heads its query group needs (kv replicated over 'model' — with
    few kv heads this is cheap and avoids the GQA repeat materialization).
    Static clip from the calibrated/default sigma (the kernel's LUT is a
    compile-time constant; per-layer traced clips stay on the jnp path)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.quantizer import exaq_params
    from repro.runtime import sharding as shd

    p = exaq_params(cfg.quant.sigma_default, statics.bits, rule=cfg.quant.clip_rule)
    dh = cfg.resolved_head_dim
    scale = dh**-0.5
    mesh = shd._CTX["mesh"]
    if mesh is None or "model" not in mesh.axis_names:
        return ops.exaq_attention(qh, kh, vh, p, scale, block_q=256, block_kv=512)
    tp = mesh.shape["model"]
    H, KV = cfg.num_heads, cfg.num_kv_heads
    group = H // KV
    if H % tp != 0:
        return ops.exaq_attention(qh, _repeat_kv(kh, group), _repeat_kv(vh, group), p, scale,
                                  use_kernel=False)
    hl = H // tp
    assert group % hl == 0 or hl % group == 0, (group, hl)
    dp = shd.data_axes(mesh)

    def local(q, k, v):
        i = jax.lax.axis_index("model")
        if hl <= group:
            kl = jax.lax.dynamic_slice_in_dim(k, (i * hl) // group, 1, axis=1)
            vl = jax.lax.dynamic_slice_in_dim(v, (i * hl) // group, 1, axis=1)
        else:
            cnt = hl // group
            kl = jax.lax.dynamic_slice_in_dim(k, i * cnt, cnt, axis=1)
            vl = jax.lax.dynamic_slice_in_dim(v, i * cnt, cnt, axis=1)
        return ops.exaq_attention(q, kl, vl, p, scale, block_q=256, block_kv=512)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, "model", None, None), P(dp, None, None, None), P(dp, None, None, None)),
        out_specs=P(dp, "model", None, None),
        check_vma=False,
    )
    return fn(qh, kh, vh)


def attention_prefill(params, x, cfg, statics: AttnStatics, clip, *, block_q=512):
    """Causal attention that also returns the (pre-repeat) KV cache."""
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    q, k, v = _project_qkv(params, x, cfg, positions, rope=True)
    qh, kh, vh = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    group = cfg.num_heads // cfg.num_kv_heads
    if statics.use_fused_kernel and statics.impl == "exaq":
        o = _fused_prefill_attention(qh, kh, vh, cfg, statics)
    else:
        o = blocked_attention(
            qh, _repeat_kv(kh, group), _repeat_kv(vh, group),
            statics, clip, causal=True, block_q=block_q, ste=False,
            scores_bf16=cfg.attn_scores_bf16,
        )
    o = jnp.swapaxes(o, 1, 2).reshape(B, S, -1).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", o, params["wo"].astype(x.dtype))
    return out, (kh, vh)  # cache layout (B, KV, S, Dh)


def attention_decode(params, x, cfg, statics: AttnStatics, clip, cache_k, cache_v, pos,
                     sp: bool = False):
    """One-token decode. x: (B, 1, D); cache_{k,v}: (B, KV, Smax, Dh); pos scalar.

    Returns (out, new_k, new_v). EXAQ global-grid softmax over the live cache
    prefix; the denominator is the histogram-composable form (DESIGN.md §2).
    sp=True takes the shard_map sequence-parallel path (integer-count combine).
    """
    B = x.shape[0]
    positions = jnp.full((1,), pos, jnp.int32)
    q, k, v = _project_qkv(params, x, cfg, positions, rope=True)
    if sp:
        from repro.runtime import sharding as shd

        if shd._CTX["mesh"] is not None and "model" in shd._CTX["mesh"].axis_names:
            qh = jnp.swapaxes(q, 1, 2)
            o, new_k, new_v = sp_decode_attention(
                qh, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), cache_k, cache_v, pos, cfg, statics, clip
            )
            o = jnp.swapaxes(o, 1, 2).reshape(B, 1, -1).astype(x.dtype)
            out = jnp.einsum("bse,ed->bsd", o, params["wo"].astype(x.dtype))
            return out, new_k, new_v
    # write the new kv at index pos
    new_k = jax.lax.dynamic_update_slice_in_dim(cache_k, jnp.swapaxes(k, 1, 2).astype(cache_k.dtype), pos, axis=2)
    new_v = jax.lax.dynamic_update_slice_in_dim(cache_v, jnp.swapaxes(v, 1, 2).astype(cache_v.dtype), pos, axis=2)
    qh = jnp.swapaxes(q, 1, 2)  # (B, H, 1, Dh)
    group = cfg.num_heads // cfg.num_kv_heads
    kk = _repeat_kv(new_k, group)
    vv = _repeat_kv(new_v, group)
    dh = cfg.resolved_head_dim
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kk).astype(jnp.float32) * dh**-0.5
    Smax = cache_k.shape[2]
    valid = ops.window_valid_mask(Smax, jnp.reshape(pos + 1, (1, 1)))
    w = _weights(s, statics, clip, valid)
    o = jnp.einsum("bhqk,bhkd->bhqd", w.astype(vv.dtype), vv)
    o = jnp.swapaxes(o, 1, 2).reshape(B, 1, -1).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", o, params["wo"].astype(x.dtype))
    return out, new_k, new_v


def attention_decode_ragged(params, x, cfg, statics: AttnStatics, clip, cache_k, cache_v, lens):
    """Slot-batched one-token decode over a *ragged* KV cache (serving engine).

    Unlike ``attention_decode`` (one scalar ``pos`` for the whole batch), every
    slot carries its own live length: the new token is RoPE-rotated at
    ``lens[b]``, written at cache index ``lens[b]``, and attends to the
    ``lens[b]+1`` live positions of its slot — so requests of different lengths
    share one jitted step and one attention dispatch.

    x: (S, 1, D); cache_{k,v}: (S, KV, Smax, Dh); lens: (S,) int32.
    Returns (out (S, 1, D), new_k, new_v).
    """
    B = x.shape[0]
    positions = lens.astype(jnp.int32)[:, None]  # (S, 1) per-slot rope position
    q, k, v = _project_qkv(params, x, cfg, positions, rope=True)
    kn = jnp.swapaxes(k, 1, 2)  # (S, KV, 1, Dh)
    vn = jnp.swapaxes(v, 1, 2)
    Smax = cache_k.shape[2]
    upd = jax.vmap(lambda c, u, p: jax.lax.dynamic_update_slice_in_dim(c, u, p, axis=1))
    new_k = upd(cache_k, kn.astype(cache_k.dtype), positions[:, 0])
    new_v = upd(cache_v, vn.astype(cache_v.dtype), positions[:, 0])
    qh = jnp.swapaxes(q, 1, 2)  # (S, H, 1, Dh)
    kv_lens = lens.astype(jnp.int32) + 1
    dh = cfg.resolved_head_dim
    if statics.use_fused_kernel and statics.impl == "exaq":
        # single Pallas dispatch over all slots (static clip from default sigma,
        # like the fused prefill path — traced per-layer clips stay on jnp)
        from repro.core.quantizer import exaq_params

        p = exaq_params(cfg.quant.sigma_default, statics.bits, rule=cfg.quant.clip_rule)
        o = ops.decode_attention(qh, new_k, new_v, kv_lens, p, dh**-0.5)
    else:
        group = cfg.num_heads // cfg.num_kv_heads
        kk = _repeat_kv(new_k, group)
        vv = _repeat_kv(new_v, group)
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kk).astype(jnp.float32) * dh**-0.5
        valid = ops.window_valid_mask(Smax, kv_lens[:, None])
        w = _weights(s, statics, clip, valid)
        o = jnp.einsum("bhqk,bhkd->bhqd", w.astype(vv.dtype), vv)
    o = jnp.swapaxes(o, 1, 2).reshape(B, 1, -1).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", o, params["wo"].astype(x.dtype))
    return out, new_k, new_v


def attention_decode_paged(params, x, cfg, statics: AttnStatics, clip, pool_k, pool_v,
                           layer, block_tables, lens, active, k_scale=None, v_scale=None,
                           k_sub=None, v_sub=None):
    """Slot-batched one-token decode over a *block-paged* KV cache (DESIGN.md §3).

    The paged sibling of ``attention_decode_ragged``: per-slot raggedness still
    lives in ``lens``, but KV now resides in a global block pool shared by all
    slots — each slot's window is the blocks its table names. The new token is
    RoPE-rotated at ``lens[b]`` and scattered into block
    ``block_tables[b, lens[b] // bs]`` at offset ``lens[b] % bs``; inactive
    slots scatter to the reserved null block (id 0) so a freed slot can never
    corrupt blocks that were recycled to another request.

    The pool planes are the whole stacked (L, ...) pool that the model's layer
    loop carries (DESIGN.md §3): the rows land at ``[layer, blk, :, off]``,
    so XLA updates the donated buffer in place, and the read paths index
    ``layer`` — no per-layer slice of the pool is cut out or written back.

    For an int8 pool (DESIGN.md §6) the scatter *quantizes*: the new token's
    per-kv-head codes land at the block's scale — seeding it
    (``ops.kv_write_scales``) when this is the block's first write — and the
    read paths dequantize, so fp values never reach HBM. ``k_scale``/
    ``v_scale`` are the (L, N, KV) scale planes; None means an fp pool.
    A packed int4 pool (DESIGN.md §10) additionally carries the
    ``k_sub``/``v_sub`` (L, N, KV, n_sub) sub-block scale-code planes: the token
    seeds its target sub-block's code (immutable once set, like the block
    scale) and its row lands as packed nibbles at the effective scale
    ``block_scale * sub_code / 15``. Head-dim-adjacent packing keeps the
    one-token scatter whole-byte, so no neighbour row is read-modify-written.

    Attention dispatch (DESIGN.md §3, fused paged decode): with
    ``use_fused_kernel`` + exaq the fused Pallas kernel reads K/V blocks
    straight from the pool via the scalar-prefetched block table — the dense
    per-slot KV copy the gather materializes never exists. Otherwise the
    gather-then-dispatch reference runs: assemble each slot's live blocks
    (``kernels.ops.gather_block_kv`` with ``kv_lens`` clamping dead tails to
    the null block, dequantizing when scales are given) and apply the EXAQ
    histogram softmax. Both anchor the quantization grid at the global row
    max, so per-block partial counts add exactly (§2 combine; block
    boundaries are invisible to the softmax) and the two paths agree to fp32
    roundoff — under the same clip: the fused kernel folds the default-sigma
    clip as a compile-time constant, so a *calibrated* per-layer qstate is
    honored by the gather path only.

    x: (S, 1, D); pool_{k,v}: (L, N, KV, bs, Dh); layer: scalar int32;
    block_tables: (S, MB) int32; lens: (S,) int32; active: (S,) bool;
    k_scale/v_scale: (L, N, KV) fp32 or None; k_sub/v_sub: (L, N, KV, n_sub)
    uint8 or None.
    Returns (out (S, 1, D), new_kv) where new_kv is (pool_k, pool_v) for fp
    pools, (pool_k, pool_v, k_scale, v_scale) for int8 pools, and
    (pool_k, pool_v, k_scale, v_scale, k_sub, v_sub) for int4 pools.
    """
    B = x.shape[0]
    bs = pool_k.shape[3]
    quantized = k_scale is not None
    int4 = k_sub is not None
    positions = lens.astype(jnp.int32)[:, None]  # (S, 1) per-slot rope position
    q, k, v = _project_qkv(params, x, cfg, positions, rope=True)
    kn, vn = k[:, 0], v[:, 0]  # (S, KV, Dh)
    blk = jnp.take_along_axis(block_tables, (lens // bs)[:, None], axis=1)[:, 0]
    blk = jnp.where(active, blk, 0)  # gate writes of inactive slots to the null block
    off = lens % bs
    if int4:
        # §6's immutable-scale scatter at the int4 range (DESIGN.md §10): the
        # block scale seeds at margin*amax/7 iff unset, the token's sub-block
        # code seeds iff unset, and the row quantizes at the effective scale
        # block * code / 15 into packed nibbles. Advanced index
        # [layer, blk, :, sub] selects each slot's one touched sub-block as
        # an (S, KV) plane.
        sub_bs = bs // k_sub.shape[-1]
        sub = off // sub_bs  # (S,) the one sub-block this token lands in
        amax_k = jnp.max(jnp.abs(kn), axis=-1)  # (S, KV)
        amax_v = jnp.max(jnp.abs(vn), axis=-1)
        ks_new = ops.kv4_write_block_scales(amax_k, k_scale[layer, blk])
        vs_new = ops.kv4_write_block_scales(amax_v, v_scale[layer, blk])
        kc_new = ops.kv4_write_sub_scales(amax_k[..., None], ks_new,
                                          k_sub[layer, blk, :, sub][..., None])[..., 0]  # (S, KV)
        vc_new = ops.kv4_write_sub_scales(amax_v[..., None], vs_new,
                                          v_sub[layer, blk, :, sub][..., None])[..., 0]
        se_k = ops.kv4_effective_scale(ks_new, kc_new[..., None])[..., 0]
        se_v = ops.kv4_effective_scale(vs_new, vc_new[..., None])[..., 0]
        new_pool_k = _write_rows(pool_k, layer, blk, off, ops.kv4_quantize(kn, se_k))
        new_pool_v = _write_rows(pool_v, layer, blk, off, ops.kv4_quantize(vn, se_v))
        k_scale = k_scale.at[layer, blk].set(ks_new)
        v_scale = v_scale.at[layer, blk].set(vs_new)
        k_sub = _write_rows(k_sub, layer, blk, sub, kc_new)
        v_sub = _write_rows(v_sub, layer, blk, sub, vc_new)
    elif quantized:
        # per-slot per-kv-head amax seeds the target block's scale iff unset;
        # a set scale is immutable (saturating append) so published prefix
        # bytes never change (DESIGN.md §6). Inactive slots land on the null
        # block, whose scale/payload are garbage sinks, never read unmasked.
        ks_new = ops.kv_write_scales(jnp.max(jnp.abs(kn), axis=-1), k_scale[layer, blk])  # (S, KV)
        vs_new = ops.kv_write_scales(jnp.max(jnp.abs(vn), axis=-1), v_scale[layer, blk])
        new_pool_k = _write_rows(pool_k, layer, blk, off, ops.kv_quantize(kn, ks_new[..., None]))
        new_pool_v = _write_rows(pool_v, layer, blk, off, ops.kv_quantize(vn, vs_new[..., None]))
        k_scale = k_scale.at[layer, blk].set(ks_new)
        v_scale = v_scale.at[layer, blk].set(vs_new)
    else:
        new_pool_k = _write_rows(pool_k, layer, blk, off, kn)
        new_pool_v = _write_rows(pool_v, layer, blk, off, vn)
    qh = jnp.swapaxes(q, 1, 2)  # (S, H, 1, Dh)
    kv_lens = lens.astype(jnp.int32) + 1
    dh = cfg.resolved_head_dim
    if statics.use_fused_kernel and statics.impl == "exaq":
        # static clip from the default sigma, like the fused ragged/prefill
        # paths: the kernel's clip/LUT are compile-time immediates, so
        # calibrated per-layer *traced* clips stay on the gather/jnp path —
        # fused-vs-gather parity holds for the default qstate only
        from repro.core.quantizer import exaq_params

        p = exaq_params(cfg.quant.sigma_default, statics.bits, rule=cfg.quant.clip_rule)
        o = ops.paged_decode_attention(qh, new_pool_k, new_pool_v, block_tables, kv_lens,
                                       p, dh**-0.5, layer=layer, k_scale=k_scale,
                                       v_scale=v_scale, k_sub=k_sub, v_sub=v_sub)
    else:
        kg, vg = ops.gather_block_kv(new_pool_k, new_pool_v, block_tables, kv_lens,
                                     k_scale, v_scale, k_sub, v_sub,
                                     layer=layer)  # (S, KV, W, Dh)
        group = cfg.num_heads // cfg.num_kv_heads
        kk = _repeat_kv(kg, group)
        vv = _repeat_kv(vg, group)
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kk).astype(jnp.float32) * dh**-0.5
        valid = ops.window_valid_mask(kk.shape[2], kv_lens[:, None])
        w = _weights(s, statics, clip, valid)
        o = jnp.einsum("bhqk,bhkd->bhqd", w.astype(vv.dtype), vv)
    o = jnp.swapaxes(o, 1, 2).reshape(B, 1, -1).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", o, params["wo"].astype(x.dtype))
    new_kv = ((new_pool_k, new_pool_v)
              + ((k_scale, v_scale) if quantized else ())
              + ((k_sub, v_sub) if int4 else ()))
    return out, new_kv


def attention_prefill_chunk(params, x, cfg, statics: AttnStatics, clip, pool_k, pool_v,
                            layer, block_table, start, blk_t, off_t, k_scale=None,
                            v_scale=None, k_sub=None, v_sub=None, seed_first_row=False):
    """One chunk of chunked prefill against a paged cache (DESIGN.md §3).

    Processes ``C`` prompt tokens at global positions ``start + i`` for one
    request: projects chunk K/V, scatters them into the pool at the host-
    computed targets (``blk_t[i]``, ``off_t[i]``; padded rows target the null
    block), then attends causally by *global position*
    (``key_pos <= start + row``) against the request's whole window — which
    now includes this chunk's keys. Because the EXAQ grid anchors at each
    row's global max, chunking the prefill leaves the softmax bit-identical
    to a one-shot prefill of the same prompt (§2: partial histograms add
    exactly). As in ``attention_decode_paged``, the pool planes are the
    whole stacked (L, ...) pool the layer loop carries: rows land at
    ``[layer, blk_t, :, off_t]`` in place and the read paths index
    ``layer``; only the quantized pool's small per-layer scale planes are
    cut out, updated and written back whole.

    Attention dispatch (DESIGN.md §7, fused paged prefill): with
    ``use_fused_kernel`` + exaq the fused Pallas kernel
    (``kernels/exaq_paged_prefill.py``) reads the window's K/V blocks
    straight from the pool via the scalar-prefetched block table — the dense
    per-chunk window copy the gather materializes (the O(prompt²) bytes term
    of chunked prefill) never exists. Otherwise the gather-then-attend
    reference runs. Both anchor at the global row max so the paths agree to
    fp32 roundoff — under the same clip: like the fused decode path, the
    kernel folds the default-sigma clip as a compile-time constant, so a
    *calibrated* per-layer qstate is honored by the gather path only.

    For an int8 pool (DESIGN.md §6) the scatter quantizes: a scatter-max
    collects each *target block's* per-kv-head amax over the rows this chunk
    writes into it, seeds still-unset block scales from that, and the rows
    quantize at their block's (now fixed) scale. The read paths dequantize
    (the fused kernel in VMEM, the gather during assembly), so
    chunked-prefill attention still runs in fp.

    A packed int4 pool (DESIGN.md §10) extends the same shape to two scale
    tiers: a scatter-max per *target sub-block* seeds still-unset sub codes
    against the (just-seeded) block scales, then each row packs to nibbles
    at its sub-block's effective scale ``block_scale * sub_code / 15``.

    ``seed_first_row`` (speculative verify, DESIGN.md §12) restricts which
    rows may *seed* scales: only the window's first row and rows landing at a
    block boundary (offset 0; sub-block boundary for int4 sub codes) feed the
    scatter-max. That is exactly the row one-token-at-a-time decode would
    have seeded each block/sub-block from, so a verify window whose tail rows
    get rejected leaves every scale bit-identical to the vanilla decode that
    never saw them — scales stay immutable once set, and the rejected rows'
    payload codes sit past ``kv_lens``, where no read path looks.

    x: (1, C, D) chunk embeddings (right-padded); pool_{k,v}:
    (L, N, KV, bs, Dh); layer: scalar int32; block_table: (MB,) int32;
    start: scalar int32 (tokens already cached); blk_t/off_t: (C,) int32;
    k_scale/v_scale: (L, N, KV) fp32 or None; k_sub/v_sub:
    (L, N, KV, n_sub) uint8 or None.
    Returns (out (1, C, D), new_kv) where new_kv is (pool_k, pool_v) for fp
    pools, (pool_k, pool_v, k_scale, v_scale) for int8 pools, and
    (pool_k, pool_v, k_scale, v_scale, k_sub, v_sub) for int4 pools.
    """
    B, C, _ = x.shape
    quantized = k_scale is not None
    int4 = k_sub is not None
    positions = (start + jnp.arange(C, dtype=jnp.int32))[None, :]  # (1, C)
    q, k, v = _project_qkv(params, x, cfg, positions, rope=True)
    # the quantized pool's scale planes of this layer, (N, KV[, n_sub]):
    # seeded below, written back whole before the read paths run
    lks, lvs, lksub, lvsub = (None if p is None else p[layer]
                              for p in (k_scale, v_scale, k_sub, v_sub))
    if int4:
        bs_pool = pool_k.shape[3]
        sub_bs = bs_pool // k_sub.shape[-1]
        sub_t = off_t // sub_bs  # (C,) each row's target sub-block
        tok_amax_k = jnp.max(jnp.abs(k[0]), axis=-1)  # (C, KV)
        tok_amax_v = jnp.max(jnp.abs(v[0]), axis=-1)
        blk_amax_k = sub_amax_k = tok_amax_k
        blk_amax_v = sub_amax_v = tok_amax_v
        if seed_first_row:
            # sequential-seeding mask (§12): only the row vanilla decode
            # would have seeded each block / sub-block from may contribute
            row = jnp.arange(C, dtype=jnp.int32)
            first_blk = ((row == 0) | (off_t == 0))[:, None]
            first_sub = ((row == 0) | (off_t % sub_bs == 0))[:, None]
            blk_amax_k = jnp.where(first_blk, tok_amax_k, 0.0)
            blk_amax_v = jnp.where(first_blk, tok_amax_v, 0.0)
            sub_amax_k = jnp.where(first_sub, tok_amax_k, 0.0)
            sub_amax_v = jnp.where(first_sub, tok_amax_v, 0.0)
        amax_k = jnp.zeros_like(lks).at[blk_t].max(blk_amax_k)
        amax_v = jnp.zeros_like(lvs).at[blk_t].max(blk_amax_v)
        lks = ops.kv4_write_block_scales(amax_k, lks)
        lvs = ops.kv4_write_block_scales(amax_v, lvs)
        amax_sub_k = jnp.zeros(lksub.shape, jnp.float32).at[blk_t, :, sub_t].max(sub_amax_k)
        amax_sub_v = jnp.zeros(lvsub.shape, jnp.float32).at[blk_t, :, sub_t].max(sub_amax_v)
        lksub = ops.kv4_write_sub_scales(amax_sub_k, lks, lksub)
        lvsub = ops.kv4_write_sub_scales(amax_sub_v, lvs, lvsub)
        se_k = ops.kv4_effective_scale(lks, lksub)[blk_t, :, sub_t]  # (C, KV)
        se_v = ops.kv4_effective_scale(lvs, lvsub)[blk_t, :, sub_t]
        new_pool_k = _write_rows(pool_k, layer, blk_t, off_t, ops.kv4_quantize(k[0], se_k))
        new_pool_v = _write_rows(pool_v, layer, blk_t, off_t, ops.kv4_quantize(v[0], se_v))
        k_sub = _with_layer(k_sub, layer, lksub)
        v_sub = _with_layer(v_sub, layer, lvsub)
    elif quantized:
        # group the chunk's rows by target block: scatter-max their per-head
        # amax onto the layer's (N, KV) scale plane, seed unset scales, then
        # quantize each row at its block's scale. Padded rows target the null
        # block.
        tok_amax_k = jnp.max(jnp.abs(k[0]), axis=-1)  # (C, KV)
        tok_amax_v = jnp.max(jnp.abs(v[0]), axis=-1)
        if seed_first_row:
            # sequential-seeding mask (§12): see the int4 branch above
            first_blk = ((jnp.arange(C, dtype=jnp.int32) == 0) | (off_t == 0))[:, None]
            tok_amax_k = jnp.where(first_blk, tok_amax_k, 0.0)
            tok_amax_v = jnp.where(first_blk, tok_amax_v, 0.0)
        amax_k = jnp.zeros_like(lks).at[blk_t].max(tok_amax_k)
        amax_v = jnp.zeros_like(lvs).at[blk_t].max(tok_amax_v)
        lks = ops.kv_write_scales(amax_k, lks)
        lvs = ops.kv_write_scales(amax_v, lvs)
        new_pool_k = _write_rows(pool_k, layer, blk_t, off_t,
                                 ops.kv_quantize(k[0], lks[blk_t][..., None]))
        new_pool_v = _write_rows(pool_v, layer, blk_t, off_t,
                                 ops.kv_quantize(v[0], lvs[blk_t][..., None]))
    else:
        new_pool_k = _write_rows(pool_k, layer, blk_t, off_t, k[0])  # (C, KV, Dh) rows
        new_pool_v = _write_rows(pool_v, layer, blk_t, off_t, v[0])
    if quantized:
        k_scale = _with_layer(k_scale, layer, lks)
        v_scale = _with_layer(v_scale, layer, lvs)

    qh = jnp.swapaxes(q, 1, 2)  # (1, H, C, Dh)
    dh = cfg.resolved_head_dim
    if statics.use_fused_kernel and statics.impl == "exaq":
        # static clip from the default sigma, like the fused decode path:
        # the kernel's clip/LUT are compile-time immediates, so calibrated
        # per-layer *traced* clips stay on the gather/jnp path
        from repro.core.quantizer import exaq_params

        p = exaq_params(cfg.quant.sigma_default, statics.bits, rule=cfg.quant.clip_rule)
        o = ops.paged_prefill_attention(qh, new_pool_k, new_pool_v, block_table, start,
                                        p, dh**-0.5, layer=layer, k_scale=k_scale,
                                        v_scale=v_scale, k_sub=k_sub, v_sub=v_sub)
    else:
        # window live length: everything cached before this chunk plus the
        # chunk itself — entries past ceil((start+C)/bs) clamp to null
        kg, vg = ops.gather_block_kv(new_pool_k, new_pool_v, block_table[None],
                                     start + C, k_scale, v_scale,
                                     k_sub, v_sub, layer=layer)  # (1, KV, W, Dh)
        group = cfg.num_heads // cfg.num_kv_heads
        kk = _repeat_kv(kg, group)
        vv = _repeat_kv(vg, group)
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kk).astype(jnp.float32) * dh**-0.5
        rows = start + jnp.arange(C, dtype=jnp.int32)
        valid = ops.window_valid_mask(kk.shape[2], (rows + 1)[None, :])
        w = _weights(s, statics, clip, valid)
        o = jnp.einsum("bhqk,bhkd->bhqd", w.astype(vv.dtype), vv)
    o = jnp.swapaxes(o, 1, 2).reshape(B, C, -1).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", o, params["wo"].astype(x.dtype))
    new_kv = ((new_pool_k, new_pool_v)
              + ((k_scale, v_scale) if quantized else ())
              + ((k_sub, v_sub) if int4 else ()))
    return out, new_kv


def _write_rows(plane, layer, blk, off, rows):
    """A stacked pool plane (L, N, KV, n, ...) with row r of ``rows``
    (R, KV, ...) written at ``[layer, blk[r], :, off[r]]``.

    The write reads the R target blocks whole, sets in each every row that
    lands in it (the last, where two land on one place — which only the
    null block's garbage sink sees), and writes the blocks back whole, so
    targets named twice get the same bytes. A write of whole blocks keeps
    the carried pool in the layout the fused kernels read and updates it in
    place; a scatter of single rows makes XLA lay the whole pool out anew
    for every layer's kernel call (DESIGN.md §3)."""
    old = plane[layer, blk]  # (R, KV, n, ...)
    n = plane.shape[3]
    lands = (blk[:, None, None] == blk[None, :, None]) \
        & (off[None, :, None] == jnp.arange(n, dtype=off.dtype)[None, None, :])  # (R, R, n)
    src = jnp.max(jnp.where(lands, jnp.arange(blk.shape[0])[None, :, None], -1), axis=1)
    new = jnp.moveaxis(rows.astype(plane.dtype)[jnp.maximum(src, 0)], 1, 2)  # (R, KV, n, ...)
    keep = (src < 0).reshape(src.shape[:1] + (1,) + src.shape[1:] + (1,) * (old.ndim - 3))
    return plane.at[layer, blk].set(jnp.where(keep, old, new))


def _with_layer(plane, layer, value):
    """A stacked (L, ...) pool plane with layer ``layer`` set to ``value``."""
    return jax.lax.dynamic_update_index_in_dim(plane, value, layer, 0)


def sp_decode_attention(qh, k_new, v_new, cache_k, cache_v, pos, cfg, statics: AttnStatics, clip):
    """Sequence-parallel decode attention (beyond-paper, EXAQ-native).

    The KV cache is sequence-sharded over 'model' (the layout runtime/sharding
    picks when kv_heads don't divide TP). Baseline XLA lowering all-gathers the
    whole cache per token (~GBs); here each shard computes local scores and the
    cross-shard softmax combine is:

        max:         one f32 pmax per row
        denominator: psum of 2^M *integer counts* per row (the EXAQ histogram
                     composes exactly across shards — calibrated C makes the
                     quantization grid shard-invariant)
        numerator:   psum of the (B,H,1,Dh) weighted-V partials

    Total wire bytes per layer: O(B*H*(2^M + Dh)) instead of O(B*KV*S*Dh).
    The cache write also happens shard-locally (no resharding copy).

    qh: (B,H,1,Dh); k_new/v_new: (B,KV,1,Dh); cache_{k,v}: (B,KV,Smax,Dh).
    Returns (out (B,H,1,Dh) fp32, new_cache_k, new_cache_v).
    """
    from repro.runtime import sharding as shd

    mesh = shd._CTX["mesh"]
    dp = shd.data_axes(mesh)
    group = cfg.num_heads // cfg.num_kv_heads
    dh = cfg.resolved_head_dim
    levels = 2**statics.bits
    quantized = statics.impl in ("exaq", "naive")

    def local(q, kn, vn, ck, cv, posv, clipv):
        i = jax.lax.axis_index("model")
        Sl = ck.shape[2]
        # shard-local cache write
        lpos = posv - i * Sl
        in_range = (lpos >= 0) & (lpos < Sl)
        lpos_c = jnp.clip(lpos, 0, Sl - 1)
        ck2 = jax.lax.dynamic_update_slice_in_dim(ck, kn.astype(ck.dtype), lpos_c, axis=2)
        cv2 = jax.lax.dynamic_update_slice_in_dim(cv, vn.astype(cv.dtype), lpos_c, axis=2)
        ck2 = jnp.where(in_range, ck2, ck)
        cv2 = jnp.where(in_range, cv2, cv)
        # grouped-query einsum — NOT repeat_kv: broadcasting kv to H heads
        # materializes a group-factor-sized copy of the cache shard per layer
        # (measured 86 GB/step on qwen3 decode_32k)
        B = q.shape[0]
        qg = q.reshape(B, ck2.shape[1], group, 1, dh)  # (B, KV, G, 1, Dh)
        s = jnp.einsum("bkgqd,bksd->bkgqs", qg.astype(jnp.float32), ck2.astype(jnp.float32)) * dh**-0.5
        cols = i * Sl + jnp.arange(Sl, dtype=jnp.int32)
        valid = (cols <= posv)[None, None, None, None, :]
        m = jax.lax.pmax(jnp.max(jnp.where(valid, s, _NEG_BIG), axis=-1, keepdims=True), "model")
        if quantized:
            delta = -clipv / levels
            codes = jnp.clip(jnp.floor((s - m - clipv) / delta), 0, levels - 1).astype(jnp.int32)
            lut = jnp.exp(clipv + (jnp.arange(levels, dtype=jnp.float32) + 0.5) * delta)
            e = jnp.full(codes.shape, 1.0, jnp.float32) * lut[0]
            for kk_ in range(1, levels):
                e = jnp.where(codes == kk_, lut[kk_], e)
            e = jnp.where(valid, e, 0.0)
            onehot = (codes[..., None] == jnp.arange(levels)) & valid[..., None]
            counts = jnp.sum(onehot, axis=4, dtype=jnp.int32)           # (B,KV,G,1,levels)
            counts = jax.lax.psum(counts, "model")                       # integer combine
            denom = jnp.einsum("bkgql,l->bkgq", counts.astype(jnp.float32), lut)[..., None]
        else:
            e = jnp.exp(s - m)
            e = jnp.where(valid, e, 0.0)
            denom = jax.lax.psum(jnp.sum(e, axis=-1, keepdims=True), "model")
        part = jnp.einsum("bkgqs,bksd->bkgqd", e, cv2.astype(jnp.float32))
        out = jax.lax.psum(part, "model") / jnp.maximum(denom, 1e-30)
        out = out.reshape(B, ck2.shape[1] * group, 1, dh)
        return out, ck2, cv2

    from jax.sharding import PartitionSpec as P

    q_spec = P(dp, None, None, None)
    kv_spec = P(dp, None, "model", None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(q_spec, q_spec, q_spec, kv_spec, kv_spec, P(), P()),
        out_specs=(q_spec, kv_spec, kv_spec),
        check_vma=False,
    )
    return fn(qh, k_new, v_new, cache_k, cache_v, pos, jnp.asarray(clip, jnp.float32))


def cross_attention(params, x, enc_kv, cfg, statics: AttnStatics, clip):
    """Decoder cross-attention to precomputed encoder K/V (B, KV, Senc, Dh)."""
    B, S, _ = x.shape
    dh = cfg.resolved_head_dim
    q = jnp.einsum("bsd,de->bse", x, params["wq"].astype(x.dtype)).reshape(B, S, cfg.num_heads, dh)
    qh = jnp.swapaxes(q, 1, 2)
    k, v = enc_kv
    group = cfg.num_heads // cfg.num_kv_heads
    kk, vv = _repeat_kv(k, group), _repeat_kv(v, group)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kk).astype(jnp.float32) * dh**-0.5
    w = _weights(s, statics, clip, None)
    o = jnp.einsum("bhqk,bhkd->bhqd", w.astype(vv.dtype), vv)
    o = jnp.swapaxes(o, 1, 2).reshape(B, S, -1).astype(x.dtype)
    return jnp.einsum("bse,ed->bsd", o, params["wo"].astype(x.dtype))


def init_cross_kv(params, enc_out, cfg):
    """Precompute encoder K/V for cross-attention. enc_out: (B, Senc, D)."""
    B, S, _ = enc_out.shape
    dh = cfg.resolved_head_dim
    k = jnp.einsum("bsd,de->bse", enc_out, params["wk"].astype(enc_out.dtype)).reshape(B, S, cfg.num_kv_heads, dh)
    v = jnp.einsum("bsd,de->bse", enc_out, params["wv"].astype(enc_out.dtype)).reshape(B, S, cfg.num_kv_heads, dh)
    return jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
