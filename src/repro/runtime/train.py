"""Training step factory: mixed precision (fp32 masters, bf16 compute),
remat, microbatch gradient accumulation, MoE aux losses, and pjit shardings.

The gradient all-reduce over ('pod','data') is XLA-generated from the SPMD
shardings; FSDP all-gathers come from the param specs in runtime/sharding.py.
Optional cross-pod int8 gradient compression lives in optim/compression.py
(hierarchical sync — see its docstring).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import build_model, default_qstate, init_params
from repro.optim.adamw import AdamW, apply_updates
from repro.runtime import sharding as shd


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean next-token CE, sharding-aware: all vocab-axis work is expressed as
    fused iota/compare reductions so a model-sharded V never gets all-gathered
    (take_along_axis on a sharded axis would force a full fp32 logits gather
    — at 92k vocab that alone is ~24 GB/step)."""
    lf = logits.astype(jnp.float32)
    m = jnp.max(lf, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, len(lf.shape) - 1)
    ll = jnp.sum(jnp.where(iota == labels[..., None], lf, 0.0), axis=-1)
    return jnp.mean(lse - ll)


def make_loss_fn(cfg, qstate=None, compute_dtype=jnp.bfloat16):
    model = build_model(cfg)
    qstate = qstate if qstate is not None else default_qstate(cfg)

    def loss_fn(params, batch):
        compute = jax.tree.map(
            lambda p: p.astype(compute_dtype) if p.dtype == jnp.float32 and p.ndim >= 2 else p, params
        )
        logits, aux = model.forward_train(compute, batch, qstate)
        ce = cross_entropy(logits, batch["labels"])
        loss = ce
        metrics = {"ce": ce}
        if "moe_lb" in aux:
            loss = loss + 0.01 * aux["moe_lb"] + 1e-3 * aux["moe_z"]
            metrics.update(moe_lb=aux["moe_lb"], moe_z=aux["moe_z"])
        metrics["loss"] = loss
        return loss, metrics

    return loss_fn


def init_train_state(cfg, optimizer: AdamW, key, dtype=jnp.float32) -> dict:
    params = init_params(cfg, key, dtype)
    return {"params": params, "opt": optimizer.init(params), "step": jnp.zeros((), jnp.int32)}


def make_train_step(cfg, optimizer: AdamW, qstate=None, microbatches: int = 1,
                    compute_dtype=jnp.bfloat16):
    """Returns train_step(state, batch) -> (state, metrics)."""
    loss_fn = make_loss_fn(cfg, qstate, compute_dtype)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state, batch):
        params = state["params"]
        if microbatches > 1:
            def split(x):
                return x.reshape((microbatches, x.shape[0] // microbatches) + x.shape[1:])

            mb = jax.tree.map(split, batch)

            def acc(carry, one):
                g_acc, m_acc = carry
                (_, metrics), grads = grad_fn(params, one)
                g_acc = jax.tree.map(jnp.add, g_acc, jax.tree.map(lambda g: g.astype(jnp.float32), grads))
                m_acc = jax.tree.map(jnp.add, m_acc, metrics)
                return (g_acc, m_acc), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            m0 = {"ce": 0.0, "loss": 0.0}
            if cfg.moe is not None:
                m0.update(moe_lb=0.0, moe_z=0.0)
            (grads, metrics), _ = jax.lax.scan(acc, (g0, m0), mb)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            metrics = jax.tree.map(lambda m: m / microbatches, metrics)
        else:
            (_, metrics), grads = grad_fn(params, batch)

        updates, opt_state, opt_metrics = optimizer.update(grads, state["opt"], params)
        new_params = apply_updates(params, updates)
        metrics.update(opt_metrics)
        return {"params": new_params, "opt": opt_state, "step": state["step"] + 1}, metrics

    return train_step


def state_shardings(cfg, mesh, state_struct) -> Any:
    """NamedShardings for the full train state (params + adam moments)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    param_sh = shd.tree_shardings(state_struct["params"], cfg, mesh, mode="train")
    return {
        "params": param_sh,
        "opt": {
            "m": shd.tree_shardings(state_struct["opt"]["m"], cfg, mesh, mode="train"),
            "v": shd.tree_shardings(state_struct["opt"]["v"], cfg, mesh, mode="train"),
            "count": NamedSharding(mesh, P()),
        },
        "step": NamedSharding(mesh, P()),
    }


def batch_shardings(mesh, batch_struct):
    from jax.sharding import NamedSharding, PartitionSpec as P

    dp = shd.data_axes(mesh)
    return jax.tree.map(
        lambda s: NamedSharding(
            mesh, shd.validate_spec(P(dp, *([None] * (len(s.shape) - 1))), s.shape, mesh)
        ),
        batch_struct,
    )
