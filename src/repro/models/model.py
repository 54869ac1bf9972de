"""Composable model definition covering all assigned architecture families.

One ``Model`` object (pure functions bound to a ModelConfig) provides:
  init / forward_train / prefill / decode_step / init_cache / default_qstate

Layer stacks are scan-over-layers (stacked params, per-layer EXAQ clip values
as scan xs) so HLO size is O(1) in depth — required for tractable 512-device
SPMD compiles. Families:

  dense | vlm    : [attn -> mlp] x L, optional patch-embed prefix (stub frontend)
  moe            : [attn -> moe_ffn] x L (+ shared experts); with
                   ``moe.first_dense`` = n, the first n layers take a dense
                   mlp instead (DeepSeekMoE): one scan over all L layers
                   whose feed-forward weights ("dense_mlp" or "moe",
                   stacked apart) are picked by layer index
  ssm            : [mamba2] x L (attention-free; EXAQ n/a)
  hybrid (zamba2): groups of `hybrid_period` mamba blocks + ONE weight-shared
                   attention block applied on concat(h, h0) after each group
  audio (whisper): enc-dec; encoder over stub frame embeddings, decoder with
                   self + cross attention
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import clipping
from repro.models import attention as attn
from repro.models import mamba2, moe
from repro.models.attention import AttnStatics
from repro.models.layers import gated_mlp, init_gated_mlp, rmsnorm, sinusoidal_positions, truncated_normal_init
from repro.runtime.sharding import shard_activation


def _statics(cfg) -> AttnStatics:
    return AttnStatics(cfg.quant.softmax_impl, cfg.quant.bits, cfg.quant.use_fused_kernel)


def default_qstate(cfg) -> dict[str, jnp.ndarray]:
    """Per-site clip values from the default sigma (pre-calibration).

    Calibration replaces these with per-layer values (core.calibration)."""
    q = cfg.quant
    if q.softmax_impl == "exact":
        c = -1.0  # unused
    elif q.softmax_impl == "naive":
        # NAIVE default: C = min/2 with min ~ -4 sigma (calibration overwrites)
        c = -2.0 * q.sigma_default
    else:
        c = clipping.get_clip_rule(q.clip_rule, q.bits)(q.sigma_default)
    qs = {}
    if cfg.family in ("dense", "vlm", "moe"):
        qs["attn_clip"] = jnp.full((cfg.num_layers,), c, jnp.float32)
    elif cfg.family == "hybrid":
        qs["shared_clip"] = jnp.full((cfg.num_layers // cfg.hybrid_period,), c, jnp.float32)
    elif cfg.family == "audio":
        qs["enc_clip"] = jnp.full((cfg.enc_layers,), c, jnp.float32)
        qs["attn_clip"] = jnp.full((cfg.num_layers,), c, jnp.float32)
        qs["cross_clip"] = jnp.full((cfg.num_layers,), c, jnp.float32)
    return qs


def qstate_from_calibrator(cfg, calib) -> dict[str, jnp.ndarray]:
    """Build per-layer clips from a core.calibration.Calibrator artifact."""
    q = cfg.quant
    qs = default_qstate(cfg)
    for key, n in (("attn_clip", cfg.num_layers), ("enc_clip", cfg.enc_layers),
                   ("cross_clip", cfg.num_layers if cfg.enc_dec else 0),
                   ("shared_clip", cfg.num_layers // cfg.hybrid_period if cfg.hybrid_period else 0)):
        if key not in qs:
            continue
        vals = []
        for i in range(n):
            site = f"{key[:-5]}/{i}"
            if site in calib.stats:
                if q.softmax_impl == "naive":
                    vals.append(calib.naive_params(site, q.bits).clip)
                else:
                    vals.append(calib.exaq_params(site, q.bits, rule=q.clip_rule).clip)
            else:
                vals.append(float(qs[key][i]))
        qs[key] = jnp.asarray(vals, jnp.float32)
    return qs


@dataclass(frozen=True)
class Model:
    cfg: object

    # ----------------------------------------------------------- init
    def init(self, key, dtype=jnp.float32) -> dict:
        cfg = self.cfg
        keys = jax.random.split(key, 8)
        vp = cfg.padded_vocab
        params: dict = {
            "embed": {"tokens": truncated_normal_init(keys[0], (vp, cfg.d_model), 1.0, dtype)},
            "final_norm": jnp.ones((cfg.d_model,), dtype),
            "head": truncated_normal_init(keys[1], (cfg.d_model, vp), cfg.d_model**-0.5, dtype),
        }
        if cfg.frontend is not None:
            params["frontend"] = {
                "frontend_proj": truncated_normal_init(keys[2], (cfg.frontend_dim, cfg.d_model), cfg.frontend_dim**-0.5, dtype)
            }
        fam = cfg.family
        if fam == "moe":
            params.update(self._init_moe_stack(keys[3], dtype))
        elif fam in ("dense", "vlm"):
            params["layers"] = self._init_decoder_stack(keys[3], cfg.num_layers, dtype)
        elif fam == "ssm":
            params["layers"] = self._init_ssm_stack(keys[3], cfg.num_layers, dtype)
        elif fam == "hybrid":
            n_groups = cfg.num_layers // cfg.hybrid_period
            params["layers"] = jax.vmap(
                lambda k: jax.vmap(lambda kk: self._init_ssm_layer(kk, dtype))(jax.random.split(k, cfg.hybrid_period))
            )(jax.random.split(keys[3], n_groups))
            params["shared"] = self._init_shared_block(keys[4], dtype)
        elif fam == "audio":
            params["enc_layers"] = jax.vmap(lambda k: self._init_enc_layer(k, dtype))(
                jax.random.split(keys[3], cfg.enc_layers)
            )
            params["layers"] = jax.vmap(lambda k: self._init_dec_layer(k, dtype))(
                jax.random.split(keys[4], cfg.num_layers)
            )
            params["enc_norm"] = jnp.ones((cfg.d_model,), dtype)
        else:
            raise ValueError(fam)
        return params

    def _init_decoder_layer(self, key, dtype):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        p = {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "attn": attn.init_attention(k1, cfg, dtype=dtype),
            "mlp": init_gated_mlp(k2, cfg.d_model, cfg.d_ff, dtype),
        }
        return p

    def _init_decoder_stack(self, key, n, dtype):
        return jax.vmap(lambda k: self._init_decoder_layer(k, dtype))(jax.random.split(key, n))

    @property
    def _first_dense(self) -> int:
        """Leading dense layers of a MoE stack."""
        return self.cfg.moe.first_dense if self.cfg.moe is not None else 0

    def _init_moe_stack(self, key, dtype):
        """A MoE stack: "layers" holds every layer's norms and attention
        (stacked over all L), "dense_mlp" the ``first_dense`` leading layers'
        MLPs (possibly none) and "moe" the other layers' expert layers."""
        cfg = self.cfg
        nd = self._first_dense
        k1, k2, k3 = jax.random.split(key, 3)

        def norms_attn(k):
            return {"ln1": jnp.ones((cfg.d_model,), dtype), "ln2": jnp.ones((cfg.d_model,), dtype),
                    "attn": attn.init_attention(k, cfg, dtype=dtype)}

        return {
            "layers": jax.vmap(norms_attn)(jax.random.split(k1, cfg.num_layers)),
            "dense_mlp": jax.vmap(lambda k: init_gated_mlp(k, cfg.d_model, cfg.d_ff, dtype))(
                jax.random.split(k2, nd)),
            "moe": jax.vmap(lambda k: moe.init_moe(k, cfg, dtype))(
                jax.random.split(k3, cfg.num_layers - nd)),
        }

    def _stack(self, params):
        """The decoder stack as scan inputs; in a MoE model each layer also
        carries its ``index``, which picks its feed-forward weights."""
        if self.cfg.moe is None:
            return params["layers"]
        return dict(params["layers"], index=jnp.arange(self.cfg.num_layers, dtype=jnp.int32))

    def _ffn_infer(self, params, lp, x, rows=None):
        """Layer ``lp``'s feed-forward on its normed input ``x``: the dense
        MLP, or in a MoE model the leading dense layer or the expert layer
        that the layer's index names. With ``rows`` (live rows, MoE only) it
        also returns the held experts' routed-row counts (zeros for a dense
        layer)."""
        cfg = self.cfg
        if cfg.moe is None:
            return gated_mlp(lp["mlp"], x)
        nd, i = self._first_dense, lp["index"]

        def sparse(x):
            return moe.moe_ffn_infer(_layer_at(params["moe"], jnp.maximum(i - nd, 0)), x, cfg, rows)

        def dense(x):
            f = gated_mlp(_layer_at(params["dense_mlp"], jnp.minimum(i, nd - 1)), x)
            if rows is None:
                return f
            lo, hi = cfg.held_experts
            return f, jnp.zeros((hi - lo,), jnp.int32)

        return jax.lax.cond(i < nd, dense, sparse, x) if nd else sparse(x)

    def _ffn_train(self, params, lp, x):
        """Training twin of ``_ffn_infer``: (out, load-balance loss, z loss),
        the losses zero for a dense layer."""
        cfg = self.cfg
        if cfg.moe is None:
            return gated_mlp(lp["mlp"], x), 0.0, 0.0
        nd, i = self._first_dense, lp["index"]

        def sparse(x):
            f, aux = moe.moe_ffn(_layer_at(params["moe"], jnp.maximum(i - nd, 0)), x, cfg)
            return f, aux["moe_lb"], aux["moe_z"]

        def dense(x):
            zero = jnp.zeros((), jnp.float32)
            return gated_mlp(_layer_at(params["dense_mlp"], jnp.minimum(i, nd - 1)), x), zero, zero

        return jax.lax.cond(i < nd, dense, sparse, x) if nd else sparse(x)

    def _init_ssm_layer(self, key, dtype):
        cfg = self.cfg
        return {"ln1": jnp.ones((cfg.d_model,), dtype), "ssm": mamba2.init_mamba(key, cfg, dtype)}

    def _init_ssm_stack(self, key, n, dtype):
        return jax.vmap(lambda k: self._init_ssm_layer(k, dtype))(jax.random.split(key, n))

    def _init_shared_block(self, key, dtype):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {
            "ln1": jnp.ones((2 * cfg.d_model,), dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "attn": attn.init_attention(k1, cfg, d_in=2 * cfg.d_model, dtype=dtype),
            "mlp": init_gated_mlp(k2, cfg.d_model, cfg.d_ff, dtype),
        }

    def _init_enc_layer(self, key, dtype):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        return {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "attn": attn.init_attention(k1, cfg, dtype=dtype),
            "mlp": init_gated_mlp(k2, cfg.d_model, cfg.d_ff, dtype),
        }

    def _init_dec_layer(self, key, dtype):
        cfg = self.cfg
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "ln3": jnp.ones((cfg.d_model,), dtype),
            "attn": attn.init_attention(k1, cfg, dtype=dtype),
            "cross": attn.init_attention(k2, cfg, dtype=dtype),
            "mlp": init_gated_mlp(k3, cfg.d_model, cfg.d_ff, dtype),
        }

    # ------------------------------------------------------- embedding
    def _embed(self, params, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        h = jnp.take(params["embed"]["tokens"], tokens, axis=0)
        if cfg.frontend == "vlm":
            ve = jnp.einsum(
                "bte,ed->btd", batch["vision_embeds"].astype(h.dtype), params["frontend"]["frontend_proj"].astype(h.dtype)
            )
            ft = min(cfg.frontend_tokens, h.shape[1])
            h = jnp.concatenate([ve[:, :ft], h[:, ft:]], axis=1)
        return shard_activation(h, "btd")

    def _mask_padded_vocab(self, logits):
        cfg = self.cfg
        if cfg.padded_vocab == cfg.vocab_size:
            return logits
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        return jnp.where(iota < cfg.vocab_size, logits, jnp.asarray(-1e30, logits.dtype))

    def _remat(self, fn):
        if self.cfg.remat == "none":
            return fn
        policy = (
            jax.checkpoint_policies.nothing_saveable
            if self.cfg.remat == "full"
            else jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
        return jax.checkpoint(fn, policy=policy)

    # ------------------------------------------------------ train fwd
    def forward_train(self, params, batch, qstate=None) -> tuple[jnp.ndarray, dict]:
        """-> (logits (B,S,V), aux dict)."""
        cfg = self.cfg
        qstate = qstate or default_qstate(cfg)
        statics = _statics(cfg)
        fam = cfg.family

        if fam == "audio":
            return self._forward_whisper_train(params, batch, qstate)

        h = self._embed(params, batch)
        aux = {}
        if fam in ("dense", "vlm", "moe"):
            def body(carry, xs):
                h, aux_lb, aux_z = carry
                lp, clip = xs
                a = attn.attention_train(lp["attn"], rmsnorm(h, lp["ln1"], cfg.norm_eps), cfg, statics, clip)
                h = h + a
                f, lb, z = self._ffn_train(params, lp, rmsnorm(h, lp["ln2"], cfg.norm_eps))
                if cfg.moe is not None:
                    aux_lb = aux_lb + lb
                    aux_z = aux_z + z
                h = shard_activation(h + f, "btd")
                return (h, aux_lb, aux_z), None

            (h, lb, z), _ = jax.lax.scan(
                self._remat(body), (h, 0.0, 0.0), (self._stack(params), qstate["attn_clip"])
            )
            if cfg.moe is not None:
                n_moe = cfg.num_layers - self._first_dense
                aux = {"moe_lb": lb / n_moe, "moe_z": z / n_moe}
        elif fam == "ssm":
            def body(h, lp):
                out, _ = mamba2.mamba_forward(
                    lp["ssm"], rmsnorm(h, lp["ln1"], cfg.norm_eps), cfg, mode="train", chunk=cfg.ssm_chunk
                )
                return shard_activation(h + out, "btd"), None

            h, _ = jax.lax.scan(self._remat(body), h, params["layers"])
        elif fam == "hybrid":
            h0 = h

            def group(carry, xs):
                h = carry
                gp, clip = xs

                def inner(hh, lp):
                    out, _ = mamba2.mamba_forward(
                        lp["ssm"], rmsnorm(hh, lp["ln1"], cfg.norm_eps), cfg, mode="train", chunk=cfg.ssm_chunk
                    )
                    return hh + out, None

                h, _ = jax.lax.scan(inner, h, gp)
                h = self._shared_block_train(params["shared"], h, h0, clip, statics)
                return shard_activation(h, "btd"), None

            h, _ = jax.lax.scan(self._remat(group), h, (params["layers"], qstate["shared_clip"]))
        else:
            raise ValueError(fam)

        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", h, params["head"].astype(h.dtype))
        logits = self._mask_padded_vocab(logits)
        return shard_activation(logits, "logits"), aux

    def _shared_block_train(self, sp, h, h0, clip, statics, block_q=512):
        cfg = self.cfg
        cat = jnp.concatenate([h, h0], axis=-1)
        a = attn.attention_train(sp["attn"], rmsnorm(cat, sp["ln1"], cfg.norm_eps), cfg, statics, clip, block_q=block_q)
        h = h + a
        f = gated_mlp(sp["mlp"], rmsnorm(h, sp["ln2"], cfg.norm_eps))
        return h + f

    def _forward_whisper_train(self, params, batch, qstate):
        cfg = self.cfg
        statics = _statics(cfg)
        enc = self._encode_audio(params, batch, qstate, statics)
        # decoder
        tok = batch["tokens"]
        h = jnp.take(params["embed"]["tokens"], tok, axis=0)
        h = h + sinusoidal_positions(h.shape[1], cfg.d_model)[None].astype(h.dtype)
        h = shard_activation(h, "btd")

        def body(h, xs):
            lp, clip, cclip = xs
            a = attn.attention_train(lp["attn"], rmsnorm(h, lp["ln1"], cfg.norm_eps), cfg, statics, clip, causal=True)
            h = h + a
            kv = attn.init_cross_kv(lp["cross"], enc, cfg)
            c = attn.cross_attention(lp["cross"], rmsnorm(h, lp["ln2"], cfg.norm_eps), kv, cfg, statics, cclip)
            h = h + c
            f = gated_mlp(lp["mlp"], rmsnorm(h, lp["ln3"], cfg.norm_eps))
            return shard_activation(h + f, "btd"), None

        h, _ = jax.lax.scan(self._remat(body), h, (params["layers"], qstate["attn_clip"], qstate["cross_clip"]))
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", h, params["head"].astype(h.dtype))
        logits = self._mask_padded_vocab(logits)
        return shard_activation(logits, "logits"), {}

    def _encode_audio(self, params, batch, qstate, statics):
        cfg = self.cfg
        x = batch["audio_embeds"]
        h = jnp.einsum("bse,ed->bsd", x, params["frontend"]["frontend_proj"].astype(x.dtype))
        h = h + sinusoidal_positions(h.shape[1], cfg.d_model)[None].astype(h.dtype)
        h = shard_activation(h, "btd")

        def body(h, xs):
            lp, clip = xs
            a = attn.attention_train(lp["attn"], rmsnorm(h, lp["ln1"], cfg.norm_eps), cfg, statics, clip, causal=False)
            h = h + a
            f = gated_mlp(lp["mlp"], rmsnorm(h, lp["ln2"], cfg.norm_eps))
            return shard_activation(h + f, "btd"), None

        h, _ = jax.lax.scan(self._remat(body), h, (params["enc_layers"], qstate["enc_clip"]))
        return rmsnorm(h, params["enc_norm"], cfg.norm_eps)

    # ----------------------------------------------------- calibration
    def calibrate(self, params, batch, qstate=None) -> dict[str, jnp.ndarray]:
        """One forward pass collecting per-layer softmax-input stats
        (paper §5.1.1: sigma of the max-subtracted attention logits, plus the
        min for the NAIVE baseline). dense/vlm/moe families; other families
        fall back to defaults (noted in DESIGN.md)."""
        cfg = self.cfg
        assert cfg.family in ("dense", "vlm", "moe"), cfg.family
        qstate = qstate or default_qstate(cfg)
        statics = AttnStatics("exact", cfg.quant.bits, False)
        h = self._embed(params, batch)

        def body(h, lp):
            x = rmsnorm(h, lp["ln1"], cfg.norm_eps)
            sigma, smin = attn.attention_score_stats(lp["attn"], x, cfg)
            a = attn.attention_train(lp["attn"], x, cfg, statics, jnp.float32(-1.0))
            h = h + a
            f, _, _ = self._ffn_train(params, lp, rmsnorm(h, lp["ln2"], cfg.norm_eps))
            return h + f, (sigma, smin)

        _, (sigmas, mins) = jax.lax.scan(body, h, self._stack(params))
        return {"attn_sigma": sigmas, "attn_min": mins}

    def qstate_from_stats(self, stats: dict) -> dict[str, jnp.ndarray]:
        """Per-layer clip values from calibration stats, honoring cfg.quant."""
        cfg = self.cfg
        q = cfg.quant
        if q.softmax_impl == "naive":
            clips = jnp.minimum(0.5 * stats["attn_min"], -1e-3)  # (min+max)/2, max=0
        else:
            slope, intercept = clipping.PAPER_CLIP_COEFFS.get(q.bits, (None, None)) if q.clip_rule == "paper" else (None, None)
            if slope is None:
                clips = jnp.asarray(
                    [clipping.optimal_clip_analytic(float(s), q.bits) for s in jax.device_get(stats["attn_sigma"])],
                    jnp.float32,
                )
            else:
                clips = slope * stats["attn_sigma"] + intercept
        return {"attn_clip": clips.astype(jnp.float32)}

    # --------------------------------------------------------- serving
    def init_cache(self, batch: int, max_seq: int, dtype=jnp.bfloat16) -> dict:
        cfg = self.cfg
        dh = cfg.resolved_head_dim
        cache: dict = {"pos": jnp.zeros((), jnp.int32)}
        fam = cfg.family
        if fam in ("dense", "vlm", "moe"):
            cache["k"] = jnp.zeros((cfg.num_layers, batch, cfg.num_kv_heads, max_seq, dh), dtype)
            cache["v"] = jnp.zeros_like(cache["k"])
        elif fam == "ssm":
            cache.update(self._ssm_cache(cfg.num_layers, batch, dtype))
        elif fam == "hybrid":
            n_groups = cfg.num_layers // cfg.hybrid_period
            ssm = self._ssm_cache(cfg.num_layers, batch, dtype)
            cache["conv"] = ssm["conv"].reshape((n_groups, cfg.hybrid_period) + ssm["conv"].shape[1:])
            cache["ssm"] = ssm["ssm"].reshape((n_groups, cfg.hybrid_period) + ssm["ssm"].shape[1:])
            cache["k"] = jnp.zeros((n_groups, batch, cfg.num_kv_heads, max_seq, dh), dtype)
            cache["v"] = jnp.zeros_like(cache["k"])
        elif fam == "audio":
            cache["k"] = jnp.zeros((cfg.num_layers, batch, cfg.num_kv_heads, max_seq, dh), dtype)
            cache["v"] = jnp.zeros_like(cache["k"])
            cache["cross_k"] = jnp.zeros((cfg.num_layers, batch, cfg.num_kv_heads, cfg.enc_seq, dh), dtype)
            cache["cross_v"] = jnp.zeros_like(cache["cross_k"])
        return cache

    def init_block_pool(self, num_blocks: int, block_size: int, dtype=jnp.bfloat16,
                        mesh=None) -> dict:
        """Global paged KV pool: {"k","v"} of (L, num_blocks, KV, bs, Dh).

        The device half of the paged cache (DESIGN.md §3): blocks are the unit
        of allocation/sharing; ``runtime.kv_pool.BlockPool`` owns the ids and
        block 0 is the reserved null sink for gated writes. Attention token
        decoders only — paging needs a ragged KV sequence axis to page.

        ``dtype=jnp.int8`` builds the quantized pool (DESIGN.md §6): int8
        payloads plus "k_scale"/"v_scale" planes of (L, num_blocks, KV) fp32
        per-block per-kv-head dequant scales, zero-initialized (0 = "scale
        not yet seeded by a first write").

        ``dtype="int4"`` (string sentinel — there is no jnp int4) builds the
        packed pool (DESIGN.md §10): uint8 payloads of (L, num_blocks, KV,
        bs, Dh//2) holding two head-dim-adjacent nibbles per byte, the fp32
        block-scale planes above, plus "k_sub"/"v_sub" uint8 planes of
        (L, num_blocks, KV, n_sub) 4-bit sub-block scale codes (0 = unset).

        ``mesh`` places the pool sharded at construction (DESIGN.md §9):
        payloads per ``sharding.block_pool_spec`` (kv-heads over 'model'
        when divisible, else replicated), scale planes per
        ``sharding.block_scale_spec`` / ``sharding.block_sub_scale_spec`` —
        so each tensor-parallel shard allocates only its local head
        partition.

        ssm / hybrid families build the architecture-agnostic *StatePool*
        instead (DESIGN.md §13): per-layer plane groups keyed by what each
        layer kind needs. Mamba2 layers get a "conv" plane of
        (L, num_blocks, w-1, ch) raw conv-tail rows plus an "ssm" plane of
        (L, num_blocks, nh, hd, ds) fp32 SSD states, checkpointed at block
        granularity — block b holds the recurrent state *through the last
        live token of block b*, which is exactly what a resume, CoW fork or
        prefix hit at that block boundary must see. Hybrid (zamba2) adds the
        shared-attention "k"/"v" planes of (G, num_blocks, KV, bs, Dh) with
        G = num_layers // hybrid_period. The block axis sits at position 1
        in *every* plane, so the engine's generic block-copy / table
        machinery never inspects plane kinds — blocks are blocks. State
        planes are full-precision only (quantized pools are attention-only).
        """
        from repro.kernels import ops

        cfg = self.cfg
        fam = cfg.family
        if fam in ("ssm", "hybrid"):
            if ops.kv_cache_is_int4(dtype) or jnp.dtype(dtype) == jnp.int8:
                raise ValueError(
                    f"quantized block pools are attention-only; family={fam!r} "
                    "state planes must stay full-precision (DESIGN.md §13)"
                )
            pool = dict(self._ssm_cache(cfg.num_layers, num_blocks, dtype))
            if fam == "hybrid":
                n_groups = cfg.num_layers // cfg.hybrid_period
                dh = cfg.resolved_head_dim
                k = jnp.zeros((n_groups, num_blocks, cfg.num_kv_heads, block_size, dh), dtype)
                pool["k"], pool["v"] = k, jnp.zeros_like(k)
            if mesh is not None:
                from jax.sharding import NamedSharding

                from repro.runtime import sharding as shd

                specs = shd.state_pool_specs(cfg, mesh)
                pool["conv"] = jax.device_put(pool["conv"], NamedSharding(mesh, specs["conv"]))
                pool["ssm"] = jax.device_put(pool["ssm"], NamedSharding(mesh, specs["ssm"]))
                if "k" in pool:
                    sh = NamedSharding(mesh, shd.block_pool_spec(cfg, mesh))
                    pool["k"] = jax.device_put(pool["k"], sh)
                    pool["v"] = jax.device_put(pool["v"], sh)
            return pool
        assert fam in ("dense", "vlm", "moe"), (
            f"paged KV pool requires an attention KV cache, got family={fam!r}"
        )
        dh = cfg.resolved_head_dim
        int4 = ops.kv_cache_is_int4(dtype)
        if int4:
            if dh % 2 != 0:
                raise ValueError(f"packed int4 pool needs an even head_dim, got {dh}")
            k = jnp.zeros((cfg.num_layers, num_blocks, cfg.num_kv_heads, block_size, dh // 2),
                          jnp.uint8)
        else:
            k = jnp.zeros((cfg.num_layers, num_blocks, cfg.num_kv_heads, block_size, dh), dtype)
        pool = {"k": k, "v": jnp.zeros_like(k)}
        if int4 or jnp.dtype(dtype) == jnp.int8:
            # two distinct buffers: the engine donates the pool pytree into
            # its jitted steps, and aliased leaves can't be donated twice
            shape = (cfg.num_layers, num_blocks, cfg.num_kv_heads)
            pool["k_scale"] = jnp.zeros(shape, jnp.float32)
            pool["v_scale"] = jnp.zeros(shape, jnp.float32)
        if int4:
            sub_shape = (cfg.num_layers, num_blocks, cfg.num_kv_heads, ops.kv4_num_sub(block_size))
            pool["k_sub"] = jnp.zeros(sub_shape, jnp.uint8)
            pool["v_sub"] = jnp.zeros(sub_shape, jnp.uint8)
        if mesh is not None:
            from jax.sharding import NamedSharding

            from repro.runtime import sharding as shd

            sh = NamedSharding(mesh, shd.block_pool_spec(cfg, mesh))
            pool["k"] = jax.device_put(pool["k"], sh)
            pool["v"] = jax.device_put(pool["v"], sh)
            if "k_scale" in pool:
                ssh = NamedSharding(mesh, shd.block_scale_spec(cfg, mesh))
                pool["k_scale"] = jax.device_put(pool["k_scale"], ssh)
                pool["v_scale"] = jax.device_put(pool["v_scale"], ssh)
            if "k_sub" in pool:
                sub_sh = NamedSharding(mesh, shd.block_sub_scale_spec(cfg, mesh))
                pool["k_sub"] = jax.device_put(pool["k_sub"], sub_sh)
                pool["v_sub"] = jax.device_put(pool["v_sub"], sub_sh)
        return pool

    def _ssm_cache(self, n_layers, batch, dtype):
        cfg = self.cfg
        return {
            "conv": jnp.zeros((n_layers, batch, cfg.ssm_conv_width - 1, mamba2.conv_channels(cfg)), dtype),
            "ssm": jnp.zeros((n_layers, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
        }

    def prefill(self, params, batch, cache, qstate=None, lens=None):
        """Process the full prompt; fill the cache. Returns (last_logits, cache).

        lens: optional (B,) true prompt lengths for right-padded batches —
        logits are gathered at ``lens - 1`` per row instead of the last
        position (causal masking keeps the padded tail from affecting live
        positions; the serving engine masks it out of decode via kv_lens)."""
        cfg = self.cfg
        qstate = qstate or default_qstate(cfg)
        statics = _statics(cfg)
        fam = cfg.family
        tokens = batch["tokens"]
        B, S = tokens.shape

        if fam in ("dense", "vlm", "moe"):
            h = self._embed(params, batch)

            def body(carry, xs):
                h = carry
                lp, clip = xs
                a, (kh, vh) = attn.attention_prefill(lp["attn"], rmsnorm(h, lp["ln1"], cfg.norm_eps), cfg, statics, clip)
                h = h + a
                f = self._ffn_infer(params, lp, rmsnorm(h, lp["ln2"], cfg.norm_eps))
                return shard_activation(h + f, "btd"), (kh, vh)

            h, (ks, vs) = jax.lax.scan(body, h, (self._stack(params), qstate["attn_clip"]))
            cache = dict(cache)
            cache["k"] = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], ks.astype(cache["k"].dtype), 0, axis=3
            )
            cache["v"] = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], vs.astype(cache["v"].dtype), 0, axis=3
            )
        elif fam == "ssm":
            h = self._embed(params, batch)

            def body(h, lp):
                out, c = mamba2.mamba_forward(
                    lp["ssm"], rmsnorm(h, lp["ln1"], cfg.norm_eps), cfg, mode="prefill", chunk=cfg.ssm_chunk
                )
                return shard_activation(h + out, "btd"), c

            h, cs = jax.lax.scan(body, h, params["layers"])
            cache = dict(cache)
            cache["conv"] = cs["conv"].astype(cache["conv"].dtype)
            cache["ssm"] = cs["ssm"]
        elif fam == "hybrid":
            h = self._embed(params, batch)
            h0 = h

            def group(carry, xs):
                h = carry
                gp, clip = xs

                def inner(hh, lp):
                    out, c = mamba2.mamba_forward(
                        lp["ssm"], rmsnorm(hh, lp["ln1"], cfg.norm_eps), cfg, mode="prefill", chunk=cfg.ssm_chunk
                    )
                    return hh + out, c

                h, cs = jax.lax.scan(inner, h, gp)
                cat = jnp.concatenate([h, h0], axis=-1)
                a, (kh, vh) = attn.attention_prefill(
                    params["shared"]["attn"], rmsnorm(cat, params["shared"]["ln1"], cfg.norm_eps), cfg, statics, clip
                )
                h = h + a
                f = gated_mlp(params["shared"]["mlp"], rmsnorm(h, params["shared"]["ln2"], cfg.norm_eps))
                return shard_activation(h + f, "btd"), (cs, kh, vh)

            h, (cs, ks, vs) = jax.lax.scan(group, h, (params["layers"], qstate["shared_clip"]))
            cache = dict(cache)
            cache["conv"] = cs["conv"].astype(cache["conv"].dtype)
            cache["ssm"] = cs["ssm"]
            cache["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], ks.astype(cache["k"].dtype), 0, axis=3)
            cache["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], vs.astype(cache["v"].dtype), 0, axis=3)
        elif fam == "audio":
            enc = self._encode_audio(params, batch, qstate, statics)
            h = jnp.take(params["embed"]["tokens"], tokens, axis=0)
            h = h + sinusoidal_positions(S, cfg.d_model)[None].astype(h.dtype)

            def body(h, xs):
                lp, clip, cclip = xs
                a, (kh, vh) = attn.attention_prefill(lp["attn"], rmsnorm(h, lp["ln1"], cfg.norm_eps), cfg, statics, clip)
                h = h + a
                ckv = attn.init_cross_kv(lp["cross"], enc, cfg)
                c = attn.cross_attention(lp["cross"], rmsnorm(h, lp["ln2"], cfg.norm_eps), ckv, cfg, statics, cclip)
                h = h + c
                f = gated_mlp(lp["mlp"], rmsnorm(h, lp["ln3"], cfg.norm_eps))
                return shard_activation(h + f, "btd"), (kh, vh, ckv[0], ckv[1])

            h, (ks, vs, cks, cvs) = jax.lax.scan(
                body, h, (params["layers"], qstate["attn_clip"], qstate["cross_clip"])
            )
            cache = dict(cache)
            cache["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], ks.astype(cache["k"].dtype), 0, axis=3)
            cache["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], vs.astype(cache["v"].dtype), 0, axis=3)
            cache["cross_k"] = cks.astype(cache["cross_k"].dtype)
            cache["cross_v"] = cvs.astype(cache["cross_v"].dtype)
        else:
            raise ValueError(fam)

        cache["pos"] = jnp.asarray(S, jnp.int32)
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        if lens is None:
            h_last = h[:, -1]
        else:
            idx = jnp.clip(lens.astype(jnp.int32) - 1, 0, S - 1)
            h_last = jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]
        logits = jnp.einsum("bd,dv->bv", h_last, params["head"].astype(h.dtype))
        logits = self._mask_padded_vocab(logits)
        return logits, cache

    def decode_step_ragged(self, params, tokens, cache, lens, qstate=None):
        """Slot-batched decode over a ragged KV cache (continuous batching).

        tokens: (S, 1) one next-token per slot; cache k/v: (L, S, KV, Smax, Dh);
        lens: (S,) live cache length per slot (the new token is written at
        index lens[b] and attends to lens[b]+1 positions). Returns
        (logits (S, V), new_cache). Attention families only — SSM/hybrid/audio
        caches have no ragged sequence axis to batch over.
        """
        cfg = self.cfg
        assert cfg.family in ("dense", "vlm", "moe"), (
            f"ragged decode requires an attention KV cache, got family={cfg.family!r}"
        )
        qstate = qstate or default_qstate(cfg)
        statics = _statics(cfg)
        h = jnp.take(params["embed"]["tokens"], tokens, axis=0)
        new_cache = dict(cache)

        def body(h, xs):
            lp, clip, ck, cv = xs
            a, nk, nv = attn.attention_decode_ragged(
                lp["attn"], rmsnorm(h, lp["ln1"], cfg.norm_eps), cfg, statics, clip, ck, cv, lens
            )
            h = h + a
            f = self._ffn_infer(params, lp, rmsnorm(h, lp["ln2"], cfg.norm_eps))
            return h + f, (nk, nv)

        h, (nk, nv) = jax.lax.scan(body, h, (self._stack(params), qstate["attn_clip"], cache["k"], cache["v"]))
        new_cache["k"], new_cache["v"] = nk, nv
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", h[:, -1], params["head"].astype(h.dtype))
        logits = self._mask_padded_vocab(logits)
        return logits, new_cache

    def decode_step_paged(self, params, tokens, pool, block_tables, lens, active,
                          qstate=None, *, block_size=None):
        """Slot-batched decode over a block-paged KV pool (DESIGN.md §3).

        The paged sibling of ``decode_step_ragged``: tokens (S, 1); pool k/v
        (L, N, KV, bs, Dh) (+ "k_scale"/"v_scale" planes when the pool is
        int8 — DESIGN.md §6 — and additionally "k_sub"/"v_sub" sub-block
        scale-code planes when it is packed int4, payload dtype uint8 —
        DESIGN.md §10); block_tables (S, MB); lens (S,) live length per
        slot; active (S,) bool — inactive slots' KV writes are gated to the
        null block so recycled blocks can't be corrupted mid-chunk. With
        ``cfg.quant.use_fused_kernel`` + exaq, every layer's attention runs
        the fused Pallas paged-decode kernel (block-table-indexed pool loads,
        no HBM gather — DESIGN.md §3); otherwise the gather-then-dispatch
        reference. Returns (logits (S, V), new_pool); a MoE model adds a
        third value, (L, held experts) int32: the live rows each layer
        routed to each held expert (zeros for a leading dense layer).

        ssm / hybrid families route to the StatePool decode branch
        (DESIGN.md §13), which needs the kw-only ``block_size`` (the pure
        state planes have no block-size axis to read it from).
        """
        cfg = self.cfg
        qstate = qstate or default_qstate(cfg)
        statics = _statics(cfg)
        h = jnp.take(params["embed"]["tokens"], tokens, axis=0)
        if cfg.family in ("ssm", "hybrid"):
            return self._decode_step_paged_state(
                params, h, pool, block_tables, lens, active, qstate, statics, block_size
            )
        assert cfg.family in ("dense", "vlm", "moe"), (
            f"paged decode requires an attention KV cache, got family={cfg.family!r}"
        )
        routed = cfg.moe is not None

        def run_layer(lp, clip, i, h, pk, pv, *sc):
            a, planes = attn.attention_decode_paged(
                lp["attn"], rmsnorm(h, lp["ln1"], cfg.norm_eps), cfg, statics, clip,
                pk, pv, i, block_tables, lens, active, *sc,
            )
            h = h + a
            x = rmsnorm(h, lp["ln2"], cfg.norm_eps)
            if not routed:
                return h + self._ffn_infer(params, lp, x), planes, None
            f, counts = self._ffn_infer(params, lp, x, rows=active[:, None])
            return h + f, planes, counts

        h, new_pool, counts = self._pool_layer_loop(params, qstate, h, pool, run_layer)
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", h[:, -1], params["head"].astype(h.dtype))
        logits = self._mask_padded_vocab(logits)
        if not routed:
            return logits, new_pool
        return logits, new_pool, counts

    def _decode_step_paged_state(self, params, h, pool, block_tables, lens, active,
                                 qstate, statics, block_size):
        """State-family decode over the paged StatePool (DESIGN.md §13).

        Position ``lens[s]`` is being decoded, so the recurrent state
        *through* position ``lens[s]-1`` is read from block ``(lens-1)//bs``
        (where the previous decode step or the prefill checkpointed it) and
        the updated state through ``lens[s]`` is written to block
        ``lens//bs``. A full block's final checkpoint (its state through its
        last token) lands while the write index still points *at* that
        block; every later step writes strictly past it, so completed
        (shareable, registered) blocks are never touched again — only the
        partial tail block is overwritten in place, which is why the host
        never registers partial blocks for state pools. Because every step
        here is the same per-token ``_ssd_chunk`` / conv-window math as the
        chunked prefill, preempt-and-recompute and prefix reuse reproduce
        the uninterrupted trajectory bitwise.
        """
        cfg = self.cfg
        bs = block_size
        assert bs is not None, "state-family paged decode needs block_size"
        read_bi = jnp.maximum(lens - 1, 0) // bs
        write_bi = lens // bs
        read_blk = jnp.take_along_axis(block_tables, read_bi[:, None], axis=1)[:, 0]
        # inactive slots write to the reserved null block (id 0) so recycled
        # blocks can't be corrupted mid-chunk — same gating as the KV planes
        write_blk = jnp.where(
            active, jnp.take_along_axis(block_tables, write_bi[:, None], axis=1)[:, 0], 0
        )

        def step(lp, hh, pconv, pssm):
            cc = jnp.take(pconv, read_blk, axis=0)
            cs = jnp.take(pssm, read_blk, axis=0)
            out, c = mamba2.mamba_forward(
                lp["ssm"], rmsnorm(hh, lp["ln1"], cfg.norm_eps), cfg, mode="decode",
                cache={"conv": cc, "ssm": cs},
            )
            nconv = pconv.at[write_blk].set(c["conv"].astype(pconv.dtype))
            nssm = pssm.at[write_blk].set(c["ssm"])
            return hh + out, nconv, nssm

        if cfg.family == "ssm":
            def body(hh, xs):
                lp, pconv, pssm = xs
                hh, nconv, nssm = step(lp, hh, pconv, pssm)
                return hh, (nconv, nssm)

            h, (nconv, nssm) = jax.lax.scan(body, h, (params["layers"], pool["conv"], pool["ssm"]))
            new_pool = {"conv": nconv, "ssm": nssm}
        else:  # hybrid: groups of mamba layers + the weight-shared attention block
            ng = cfg.num_layers // cfg.hybrid_period
            pconv = pool["conv"].reshape((ng, cfg.hybrid_period) + pool["conv"].shape[1:])
            pssm = pool["ssm"].reshape((ng, cfg.hybrid_period) + pool["ssm"].shape[1:])
            h0 = h

            def group(hh, xs):
                gp, clip, gconv, gssm, pk, pv = xs

                def inner(hhh, ys):
                    lp, lconv, lssm = ys
                    hhh, nconv, nssm = step(lp, hhh, lconv, lssm)
                    return hhh, (nconv, nssm)

                hh, (nconv, nssm) = jax.lax.scan(inner, hh, (gp, gconv, gssm))
                cat = jnp.concatenate([hh, h0], axis=-1)
                a, (nk, nv) = attn.attention_decode_paged(
                    params["shared"]["attn"], rmsnorm(cat, params["shared"]["ln1"], cfg.norm_eps),
                    cfg, statics, clip, pk[None], pv[None], 0, block_tables, lens, active,
                )
                hh = hh + a
                f = gated_mlp(params["shared"]["mlp"], rmsnorm(hh, params["shared"]["ln2"], cfg.norm_eps))
                return hh + f, (nconv, nssm, nk[0], nv[0])

            h, (nconv, nssm, nk, nv) = jax.lax.scan(
                group, h,
                (params["layers"], qstate["shared_clip"], pconv, pssm, pool["k"], pool["v"]),
            )
            new_pool = {
                "conv": nconv.reshape(pool["conv"].shape),
                "ssm": nssm.reshape(pool["ssm"].shape),
                "k": nk, "v": nv,
            }
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", h[:, -1], params["head"].astype(h.dtype))
        return self._mask_padded_vocab(logits), new_pool

    def prefill_paged_chunk(self, params, tokens, pool, block_table, start, chunk_len,
                            blk_t, off_t, qstate=None, *, block_size=None):
        """One fixed-size chunk of a paged prefill for a single request.

        tokens (1, C) right-padded chunk; block_table (MB,) the request's
        table; start scalar — tokens already cached (prefix hits + previous
        chunks); chunk_len scalar — live tokens in this chunk; blk_t/off_t
        (C,) host-computed scatter targets (padded rows -> null block).
        Attends causally by global position against the request's window, so
        a prompt prefilled in chunks matches a one-shot prefill bit-for-bit
        (DESIGN.md §3). With ``cfg.quant.use_fused_kernel`` + exaq, every
        layer's attention runs the fused Pallas paged-prefill kernel
        (block-table-indexed pool reads, no dense window gather —
        DESIGN.md §7); otherwise the gather-then-attend reference. int8
        pools carry "k_scale"/"v_scale" planes that the scatter seeds and
        the read paths dequantize against (DESIGN.md §6); packed int4
        pools add "k_sub"/"v_sub" sub-block scale-code planes
        (DESIGN.md §10).
        Returns (logits (1, V) at the chunk's last live row, new_pool) —
        only the final chunk's logits seed sampling.

        ssm / hybrid families route to the StatePool chunk branch
        (DESIGN.md §13): per-token SSD recurrence with block-granular
        conv/ssm checkpoints scattered to ``blk_t[::block_size]``.
        """
        cfg = self.cfg
        qstate = qstate or default_qstate(cfg)
        statics = _statics(cfg)
        h = jnp.take(params["embed"]["tokens"], tokens, axis=0)
        if cfg.family in ("ssm", "hybrid"):
            return self._prefill_paged_chunk_state(
                params, h, pool, block_table, start, chunk_len, blk_t, off_t,
                qstate, statics, block_size,
            )
        assert cfg.family in ("dense", "vlm", "moe"), (
            f"paged prefill requires an attention KV cache, got family={cfg.family!r}"
        )
        h, new_pool = self._prefill_layer_loop(params, qstate, h, pool, block_table, start,
                                               blk_t, off_t, statics)
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        idx = jnp.clip(chunk_len - 1, 0, tokens.shape[1] - 1)
        h_last = jax.lax.dynamic_index_in_dim(h[0], idx, axis=0, keepdims=False)
        logits = jnp.einsum("d,dv->v", h_last, params["head"].astype(h.dtype))[None]
        logits = self._mask_padded_vocab(logits)
        return logits, new_pool

    def _pool_layer_loop(self, params, qstate, h, pool, run_layer):
        """The attention families' layer loop over a paged KV pool
        (DESIGN.md §3): a scan whose carry holds ``h`` and the whole stacked
        (L, ...) pool planes ("k", "v" and any scale planes), with each
        layer's weights, clip and index as its inputs. ``run_layer(lp, clip, i,
        h, *planes)`` -> (h, planes, y) writes layer ``i``'s rows into the
        planes in place and reads them back by index, so the pool is neither
        sliced per layer nor rebuilt as a stacked scan output. Returns
        (h, new pool, the ys stacked over layers)."""
        keys = tuple(k for k in ("k", "v", "k_scale", "v_scale", "k_sub", "v_sub") if k in pool)

        def body(carry, xs):
            h, planes = carry
            h, planes, y = run_layer(*xs, h, *planes)
            return (h, tuple(planes)), y

        xs = (self._stack(params), qstate["attn_clip"],
              jnp.arange(self.cfg.num_layers, dtype=jnp.int32))
        (h, planes), ys = jax.lax.scan(body, (h, tuple(pool[k] for k in keys)), xs)
        return h, dict(zip(keys, planes)), ys

    def _prefill_layer_loop(self, params, qstate, h, pool, block_table, start, blk_t, off_t,
                            statics, seed_first_row=False):
        """``_pool_layer_loop`` for a chunk of one request's prompt rows, the
        body of both ``prefill_paged_chunk`` and ``verify_paged_chunk``:
        -> (h, new pool)."""
        cfg = self.cfg

        def run_layer(lp, clip, i, h, pk, pv, *sc):
            a, planes = attn.attention_prefill_chunk(
                lp["attn"], rmsnorm(h, lp["ln1"], cfg.norm_eps), cfg, statics, clip,
                pk, pv, i, block_table, start, blk_t, off_t, *sc,
                seed_first_row=seed_first_row,
            )
            h = h + a
            f = self._ffn_infer(params, lp, rmsnorm(h, lp["ln2"], cfg.norm_eps))
            return h + f, planes, None

        h, new_pool, _ = self._pool_layer_loop(params, qstate, h, pool, run_layer)
        return h, new_pool

    def _prefill_paged_chunk_state(self, params, h, pool, block_table, start, chunk_len,
                                   blk_t, off_t, qstate, statics, block_size):
        """State-family chunked prefill over the paged StatePool (DESIGN.md §13).

        Resume state: the chunk needs the conv tail / SSD state through
        global position ``start - 1``. The host keeps ``start`` block-aligned
        (prefix hits truncate to full blocks; prefill_chunk % block_size == 0
        is an engine gate), so that state is exactly the checkpoint of block
        ``(start-1)//bs``; ``start == 0`` selects zeros instead (jnp.where
        keeps the select NaN-safe regardless of what the gathered block
        holds). This chunk's checkpoints scatter to ``blk_t[::bs]`` — the
        host points pad rows at the null block, so pads-only blocks discard
        themselves. Pad rows inside a live block are dt-masked in
        ``mamba_paged_prefill_chunk``: the carried state passes through them
        bitwise, so the tail checkpoint holds the state through the last
        live token.
        """
        cfg = self.cfg
        bs = block_size
        assert bs is not None, "state-family paged prefill needs block_size"
        ckpt_blks = blk_t[::bs]
        read_blk = block_table[jnp.maximum(start - 1, 0) // bs]

        def step(lp, hh, pconv, pssm):
            cp = jnp.where(start > 0, pconv[read_blk], jnp.zeros_like(pconv[read_blk]))[None]
            h0 = jnp.where(start > 0, pssm[read_blk], jnp.zeros_like(pssm[read_blk]))[None]
            out, conv_ck, ssm_ck = mamba2.mamba_paged_prefill_chunk(
                lp["ssm"], rmsnorm(hh, lp["ln1"], cfg.norm_eps), cfg, cp, h0, chunk_len,
                block_size=bs,
            )
            nconv = pconv.at[ckpt_blks].set(conv_ck.astype(pconv.dtype))
            nssm = pssm.at[ckpt_blks].set(ssm_ck)
            return hh + out, nconv, nssm

        if cfg.family == "ssm":
            def body(hh, xs):
                lp, pconv, pssm = xs
                hh, nconv, nssm = step(lp, hh, pconv, pssm)
                return shard_activation(hh, "btd"), (nconv, nssm)

            h, (nconv, nssm) = jax.lax.scan(body, h, (params["layers"], pool["conv"], pool["ssm"]))
            new_pool = {"conv": nconv, "ssm": nssm}
        else:  # hybrid: groups of mamba layers + the weight-shared attention block
            ng = cfg.num_layers // cfg.hybrid_period
            pconv = pool["conv"].reshape((ng, cfg.hybrid_period) + pool["conv"].shape[1:])
            pssm = pool["ssm"].reshape((ng, cfg.hybrid_period) + pool["ssm"].shape[1:])
            h0_tok = h

            def group(hh, xs):
                gp, clip, gconv, gssm, pk, pv = xs

                def inner(hhh, ys):
                    lp, lconv, lssm = ys
                    hhh, nconv, nssm = step(lp, hhh, lconv, lssm)
                    return hhh, (nconv, nssm)

                hh, (nconv, nssm) = jax.lax.scan(inner, hh, (gp, gconv, gssm))
                cat = jnp.concatenate([hh, h0_tok], axis=-1)
                a, (nk, nv) = attn.attention_prefill_chunk(
                    params["shared"]["attn"], rmsnorm(cat, params["shared"]["ln1"], cfg.norm_eps),
                    cfg, statics, clip, pk[None], pv[None], 0, block_table, start, blk_t, off_t,
                )
                hh = hh + a
                f = gated_mlp(params["shared"]["mlp"], rmsnorm(hh, params["shared"]["ln2"], cfg.norm_eps))
                return shard_activation(hh + f, "btd"), (nconv, nssm, nk[0], nv[0])

            h, (nconv, nssm, nk, nv) = jax.lax.scan(
                group, h,
                (params["layers"], qstate["shared_clip"], pconv, pssm, pool["k"], pool["v"]),
            )
            new_pool = {
                "conv": nconv.reshape(pool["conv"].shape),
                "ssm": nssm.reshape(pool["ssm"].shape),
                "k": nk, "v": nv,
            }
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        idx = jnp.clip(chunk_len - 1, 0, h.shape[1] - 1)
        h_last = jax.lax.dynamic_index_in_dim(h[0], idx, axis=0, keepdims=False)
        logits = jnp.einsum("d,dv->v", h_last, params["head"].astype(h.dtype))[None]
        return self._mask_padded_vocab(logits), new_pool

    def verify_paged_chunk(self, params, tokens, pool, block_table, start,
                           blk_t, off_t, qstate=None):
        """Speculative-verify window for one request (DESIGN.md §12).

        tokens (1, C) = [pending, draft_1..draft_{C-1}] at global positions
        ``start + i``; block_table (MB,) is the slot prefix composed with the
        draft branch's blocks; blk_t/off_t (C,) host-computed scatter targets
        inside the branch. The body is ``prefill_paged_chunk`` — same fused
        paged-prefill kernel, same chunk-invariant two-pass histogram combine
        (§2/§7), so row i's attention is bit-identical to the decode step
        that would have consumed the same context — with two differences:

          * scale seeding runs under ``seed_first_row`` so rejected rows
            can't perturb quantized scales vanilla decode would have seeded
            differently (attention.py, §12);
          * logits come back for EVERY row (C, V), because the accept rule
            needs the target's argmax after each draft position, not just
            the last.

        Returns (logits (C, V), new_pool).
        """
        cfg = self.cfg
        assert cfg.family in ("dense", "vlm", "moe"), (
            f"paged verify requires an attention KV cache, got family={cfg.family!r}"
        )
        qstate = qstate or default_qstate(cfg)
        statics = _statics(cfg)
        h = jnp.take(params["embed"]["tokens"], tokens, axis=0)
        h, new_pool = self._prefill_layer_loop(params, qstate, h, pool, block_table, start,
                                               blk_t, off_t, statics, seed_first_row=True)
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("cd,dv->cv", h[0], params["head"].astype(h.dtype))
        logits = self._mask_padded_vocab(logits)
        return logits, new_pool

    def decode_step(self, params, tokens, cache, qstate=None):
        """tokens: (B, 1) -> (logits (B, V), new cache)."""
        cfg = self.cfg
        qstate = qstate or default_qstate(cfg)
        statics = _statics(cfg)
        fam = cfg.family
        pos = cache["pos"]
        h = jnp.take(params["embed"]["tokens"], tokens, axis=0)
        new_cache = dict(cache)

        if fam in ("dense", "vlm", "moe"):
            def body(h, xs):
                lp, clip, ck, cv = xs
                a, nk, nv = attn.attention_decode(
                    lp["attn"], rmsnorm(h, lp["ln1"], cfg.norm_eps), cfg, statics, clip, ck, cv, pos,
                    sp=cfg.quant.sp_decode,
                )
                h = h + a
                f = self._ffn_infer(params, lp, rmsnorm(h, lp["ln2"], cfg.norm_eps))
                return h + f, (nk, nv)

            h, (nk, nv) = jax.lax.scan(body, h, (self._stack(params), qstate["attn_clip"], cache["k"], cache["v"]))
            new_cache["k"], new_cache["v"] = nk, nv
        elif fam == "ssm":
            def body(h, xs):
                lp, cc, cs = xs
                out, c = mamba2.mamba_forward(
                    lp["ssm"], rmsnorm(h, lp["ln1"], cfg.norm_eps), cfg, mode="decode",
                    cache={"conv": cc, "ssm": cs},
                )
                return h + out, (c["conv"], c["ssm"])

            h, (ncc, ncs) = jax.lax.scan(body, h, (params["layers"], cache["conv"], cache["ssm"]))
            new_cache["conv"], new_cache["ssm"] = ncc.astype(cache["conv"].dtype), ncs
        elif fam == "hybrid":
            h0 = h

            def group(h, xs):
                gp, clip, cc, cs, ck, cv = xs

                def inner(hh, ys):
                    lp, icc, ics = ys
                    out, c = mamba2.mamba_forward(
                        lp["ssm"], rmsnorm(hh, lp["ln1"], cfg.norm_eps), cfg, mode="decode",
                        cache={"conv": icc, "ssm": ics},
                    )
                    return hh + out, (c["conv"], c["ssm"])

                h, (ncc, ncs) = jax.lax.scan(inner, h, (gp, cc, cs))
                cat = jnp.concatenate([h, h0], axis=-1)
                a, nk, nv = attn.attention_decode(
                    params["shared"]["attn"], rmsnorm(cat, params["shared"]["ln1"], cfg.norm_eps),
                    cfg, statics, clip, ck, cv, pos,
                )
                h = h + a
                f = gated_mlp(params["shared"]["mlp"], rmsnorm(h, params["shared"]["ln2"], cfg.norm_eps))
                return h + f, (ncc, ncs, nk, nv)

            h, (ncc, ncs, nk, nv) = jax.lax.scan(
                group, h, (params["layers"], qstate["shared_clip"], cache["conv"], cache["ssm"], cache["k"], cache["v"])
            )
            new_cache.update(conv=ncc.astype(cache["conv"].dtype), ssm=ncs, k=nk, v=nv)
        elif fam == "audio":
            smax = cache["k"].shape[3]
            pe = sinusoidal_positions(smax, cfg.d_model)
            h = h + jax.lax.dynamic_slice_in_dim(pe, pos, 1, axis=0)[None].astype(h.dtype)

            def body(h, xs):
                lp, clip, cclip, ck, cv, xk, xv = xs
                a, nk, nv = attn.attention_decode(
                    lp["attn"], rmsnorm(h, lp["ln1"], cfg.norm_eps), cfg, statics, clip, ck, cv, pos
                )
                h = h + a
                c = attn.cross_attention(lp["cross"], rmsnorm(h, lp["ln2"], cfg.norm_eps), (xk, xv), cfg, statics, cclip)
                h = h + c
                f = gated_mlp(lp["mlp"], rmsnorm(h, lp["ln3"], cfg.norm_eps))
                return h + f, (nk, nv)

            h, (nk, nv) = jax.lax.scan(
                body, h,
                (params["layers"], qstate["attn_clip"], qstate["cross_clip"],
                 cache["k"], cache["v"], cache["cross_k"], cache["cross_v"]),
            )
            new_cache["k"], new_cache["v"] = nk, nv
        else:
            raise ValueError(fam)

        new_cache["pos"] = pos + 1
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bd,dv->bv", h[:, -1], params["head"].astype(h.dtype))
        logits = self._mask_padded_vocab(logits)
        return logits, new_cache


def _layer_at(stack, i):
    """Layer ``i`` of a stacked parameter tree."""
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), stack)


def build_model(cfg) -> Model:
    return Model(cfg)


def init_params(cfg, key, dtype=jnp.float32, *, sharding=None) -> dict:
    """``build_model(cfg).init`` as one jitted program.

    Eagerly, each weight is drawn as an fp32 array, then scaled and cast op
    by op, so the device holds fp32 temporaries beside the result: yi-6b's
    stacked MLP input weight alone is a 10.75 GiB fp32 draw, past one 16 GiB
    TPU v5e. Under jit XLA fuses draw, scale and cast, and the peak stays at
    the parameter bytes. ``sharding`` places the whole tree (for example
    replicated over one serving replica's mesh) as it is created."""
    return jax.jit(build_model(cfg).init, static_argnums=1, out_shardings=sharding)(key, dtype)
