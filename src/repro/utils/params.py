"""Exact parameter counting (from the abstract init tree — no formula drift)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def count_params(cfg) -> int:
    from repro.models import build_model

    tree = jax.eval_shape(lambda k: build_model(cfg).init(k), jax.ShapeDtypeStruct((2,), jnp.uint32))
    return int(sum(leaf.size for leaf in jax.tree.leaves(tree)))


def count_embedding_params(cfg) -> int:
    return int(cfg.vocab_size) * int(cfg.d_model)


def per_expert_params(cfg) -> int:
    if cfg.moe is None:
        return 0
    fe = cfg.moe.d_expert or cfg.d_ff
    return cfg.d_model * 2 * fe + fe * cfg.d_model  # gated wi + wo


def count_active_params(cfg) -> int:
    """Params touched per token. MoE: of each expert layer's routed experts
    only the top-k count; the leading dense layers, the shared experts and
    the router count whole. The routed experts held are taken out and the
    top-k put back, so a program that holds one chip's share of the experts
    (``cfg.held_experts``) counts what a token touches across the deployment."""
    n = count_params(cfg)
    if cfg.moe is not None:
        lo, hi = cfg.held_experts
        moe_layers = cfg.num_layers - cfg.moe.first_dense
        n -= (hi - lo - cfg.moe.top_k) * per_expert_params(cfg) * moe_layers
    return int(n)


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS per the assignment: 6*N*D (train) / 2*N_active*D (inference),
    N excluding the embedding gather (the unembed matmul counts)."""
    n_act = count_active_params(cfg) - count_embedding_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_act * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_act * tokens
    # decode: one token per sequence
    return 2.0 * n_act * shape.global_batch
