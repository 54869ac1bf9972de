"""Mixture-of-Experts FFN (GShard/Mixtral-style capacity routing).

Token-choice top-k with per-group capacity; dispatch/combine are einsums (the
SPMD-friendly formulation — XLA turns them into all-to-alls under expert
parallelism, experts sharded over the 'model' axis). Supports DeepSeekMoE
fine-grained experts with always-on shared experts.

The gate is a softmax over every routed expert; its top-k weights are
renormalised to sum to 1 unless ``cfg.moe.norm_topk`` is off (DeepSeekMoE
uses the raw softmax weights). A program may hold only its share of the
routed experts (``cfg.held_experts``, one chip of expert parallelism): the
router still scores all of them, and the layer computes what its own experts
add for the tokens routed to them, plus the shared experts — the partial sum
one chip gives before the exchange that no single-chip program makes
(DESIGN.md §5).

The router softmax stays exact (rank-sensitive top-k, negligible cost) —
documented design choice in DESIGN.md §5.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import init_gated_mlp, silu, truncated_normal_init
from repro.runtime.sharding import shard_activation


def init_moe(key, cfg, dtype=jnp.float32) -> dict:
    """Router over all experts, weights of the held ones (``cfg.held_experts``)."""
    m = cfg.moe
    d = cfg.d_model
    fe = m.d_expert or cfg.d_ff
    lo, hi = cfg.held_experts
    ks = jax.random.split(key, 4)
    p = {
        "router": truncated_normal_init(ks[0], (d, m.num_experts), d**-0.5, jnp.float32),
        "moe_wi": truncated_normal_init(ks[1], (hi - lo, d, 2 * fe), d**-0.5, dtype),
        "moe_wo": truncated_normal_init(ks[2], (hi - lo, fe, d), fe**-0.5, dtype),
    }
    if m.num_shared:
        p["shared"] = init_gated_mlp(ks[3], d, m.num_shared * fe, dtype)
    return p


def moe_ffn(params: dict, x: jnp.ndarray, cfg) -> tuple[jnp.ndarray, dict]:
    """x: (B, S, D) -> (out, aux) with load-balance / z losses."""
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    gs = min(m.group_size, T)
    assert T % gs == 0, f"tokens {T} not divisible by moe group {gs}"
    G = T // gs
    xg = x.reshape(G, gs, D)

    logits, probs, gate_vals, idx = _gate(params, xg, cfg)

    # capacity per expert per group
    cap = int(-(-gs * m.top_k * m.capacity_factor // m.num_experts))
    cap = max(4, -(-cap // 4) * 4)
    E = m.num_experts

    routed = jax.nn.one_hot(idx, E, dtype=jnp.int32).sum(axis=2)            # (G, gs, E) in {0,1}
    held, w = _held(idx, gate_vals, cfg)                                      # (G, gs, k, En)
    assign = held.astype(jnp.int32).sum(axis=2)                               # held experts only
    weights = w.sum(axis=2)
    pos = jnp.cumsum(assign, axis=1) - assign                                # (G, gs, En) slot ids
    keep = (pos < cap) & (assign > 0)
    # dispatch: (G, gs, E, C) one-hot of the slot; combine carries the gate
    dispatch = jax.nn.one_hot(pos, cap, dtype=x.dtype) * keep[..., None].astype(x.dtype)
    combine = dispatch * weights[..., None].astype(x.dtype)

    expert_in = jnp.einsum("gtec,gtd->egcd", dispatch, xg)                   # (E, G, C, D)
    expert_in = shard_activation(expert_in, "experts")
    h = jnp.einsum("egcd,edf->egcf", expert_in, params["moe_wi"].astype(x.dtype))
    gate, up = jnp.split(h, 2, axis=-1)
    hh = silu(gate) * up
    expert_out = jnp.einsum("egcf,efd->egcd", hh, params["moe_wo"].astype(x.dtype))
    expert_out = shard_activation(expert_out, "experts")
    out = jnp.einsum("gtec,egcd->gtd", combine, expert_out).reshape(B, S, D)

    if m.num_shared:
        from repro.models.layers import gated_mlp

        out = out + gated_mlp(params["shared"], x)

    # aux: switch load-balance + router z-loss, over every routed expert
    density = routed.astype(jnp.float32).mean(axis=1)                         # (G, E) fraction routed
    router_prob = probs.mean(axis=1)                                          # (G, E)
    lb = E * jnp.mean(jnp.sum(density * router_prob, axis=-1)) * m.top_k
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    dropped = 1.0 - keep.sum() / jnp.maximum(assign.sum(), 1)
    aux = {"moe_lb": lb, "moe_z": z, "moe_dropped": dropped}
    return out, aux


def _gate(params: dict, x: jnp.ndarray, cfg):
    """Router logits, softmax over every routed expert, and its top-k
    (weights renormalised to sum to 1 where ``cfg.moe.norm_topk``)."""
    m = cfg.moe
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)  # exact router softmax (DESIGN.md §5)
    gate_vals, idx = jax.lax.top_k(probs, m.top_k)  # (..., k)
    if m.norm_topk:
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    return logits, probs, gate_vals, idx


def _held(idx, gate_vals, cfg):
    """One-hot of each top-k choice over the held experts, and the same
    carrying its gate weight: (..., k, held). A choice outside the held
    range is all zeros (another chip's expert)."""
    lo, hi = cfg.held_experts
    onehot = jax.nn.one_hot(idx - lo, hi - lo, dtype=jnp.float32)  # out of range -> zeros
    return onehot, onehot * gate_vals[..., None]


def moe_ffn_infer(params: dict, x: jnp.ndarray, cfg, rows=None):
    """Capacity-free MoE FFN for the serving paths: x (B, S, D) -> (B, S, D).

    Inference routing drops the training-time per-group capacity grid (no aux
    losses, no token dropping): router dispatch is a dense per-token weight
    over the held experts, batched across all live tokens of the
    decode/prefill call in one einsum over every held expert, so each held
    expert's weights are read whether or not a token chose it. No token count
    / group divisibility constraints, so the jitted decode scan can route a
    ragged slot batch directly — the MoE leg of the StatePool story
    (stateless but batched, DESIGN.md §13).

    With ``rows`` ((B, S) bool: the rows that are live) it returns
    ``(out, counts)``, ``counts`` (held,) int32 the live rows routed to each
    held expert.
    """
    _, _, gate_vals, idx = _gate(params, x, cfg)
    held, w = _held(idx, gate_vals, cfg)
    weights = w.sum(axis=2)  # (B, S, held)

    h = jnp.einsum("bsd,edf->bsef", x, params["moe_wi"].astype(x.dtype))
    gate, up = jnp.split(h, 2, axis=-1)
    hh = silu(gate) * up
    expert_out = jnp.einsum("bsef,efd->bsed", hh, params["moe_wo"].astype(x.dtype))
    out = jnp.einsum("bse,bsed->bsd", weights.astype(x.dtype), expert_out)

    if cfg.moe.num_shared:
        from repro.models.layers import gated_mlp

        out = out + gated_mlp(params["shared"], x)
    if rows is None:
        return out
    live = held.sum(axis=2) * rows[..., None].astype(jnp.float32)  # (B, S, held)
    return out, live.sum(axis=(0, 1)).astype(jnp.int32)
