"""Sharding rules: logical activation/param axes -> PartitionSpec.

Mesh axes (launch/mesh.py): ('pod', 'data', 'model') multi-pod or
('data', 'model') single-pod.

  * DP  — batch over ('pod', 'data')
  * TP  — heads / ffn / vocab over 'model'
  * EP  — MoE experts over 'model'
  * SP  — KV-cache sequence over 'model' when kv_heads don't divide TP
  * FSDP — in train mode, params/opt additionally sharded over 'data'
           (ZeRO-3 style; XLA inserts the all-gathers)

Models call ``shard_activation(x, name)`` — a no-op unless a mesh context is
active, so the same code runs single-device tests and 512-chip dry-runs.
"""

from __future__ import annotations

import contextlib
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_CTX: dict[str, Any] = {"mesh": None, "rules": {}}


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis_size(mesh: Mesh) -> int:
    return mesh.shape.get("model", 1)


def data_axis_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))


@contextlib.contextmanager
def activation_rules(mesh: Mesh, rules: dict[str, P]):
    old = dict(_CTX)
    _CTX["mesh"], _CTX["rules"] = mesh, rules
    try:
        yield
    finally:
        _CTX.update(old)


def use_mesh(mesh: Mesh | None):
    """Activate ``mesh`` (no activation rules) for the duration of a call.

    The serving device layer wraps every jitted call in this so trace-time
    mesh discovery works: the shard_map dispatch around the fused paged
    kernels (kernels/ops.py) and ``shard_activation`` both read the ambient
    ``_CTX`` mesh while the function body is being traced. ``None`` is a
    no-op, so single-device engines pay nothing."""
    if mesh is None:
        return contextlib.nullcontext()
    return activation_rules(mesh, {})


def current_mesh() -> Mesh | None:
    """The ambient mesh (``activation_rules``/``use_mesh``), or None."""
    return _CTX["mesh"]


def shard_activation(x, name: str):
    mesh = _CTX["mesh"]
    if mesh is None:
        return x
    spec = _CTX["rules"].get(name)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


def make_activation_rules(cfg, mesh: Mesh) -> dict[str, P]:
    dp = data_axes(mesh)
    tp = model_axis_size(mesh)
    rules = {
        "btd": P(dp, None, None),
        "btf": P(dp, None, "model"),
        "logits": P(dp, None, "model"),
    }
    if cfg.num_heads and _div(cfg.num_heads, tp):
        rules["heads"] = P(dp, "model", None, None)
    elif cfg.num_heads:
        # TP can't split the heads — shard attention q-block rows instead
        # (sequence-parallel attention; exact, softmax is row-wise)
        rules["qrows"] = P(dp, None, "model", None)
        rules["score_rows"] = P(dp, None, "model", None)
    if cfg.moe is not None and _div(cfg.held_experts[1] - cfg.held_experts[0], tp):
        rules["experts"] = P("model", dp, None, None)  # (E held, G, C, D)
    if cfg.ssm_state and _div(cfg.ssm_heads, tp):
        rules["ssm_heads"] = P(dp, None, "model", None)  # (B, S, nh, hd)
    return rules


# ------------------------------------------------------------- param specs

def validate_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop sharding on any dim the mesh axes don't divide evenly."""
    out = []
    for i, ax in enumerate(tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            out.append(None)
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        out.append(ax if _div(shape[i], size) else None)
    return P(*out)


def param_spec_fn(cfg, mesh: Mesh, mode: str = "train"):
    """Returns path->PartitionSpec for the param tree of ``build_model(cfg)``.

    Specs are tail-aligned (leading stacking dims — layer / group — are
    unsharded) and validated for divisibility, so the same rules cover flat,
    scan-stacked and doubly-stacked (hybrid) parameters.

    mode='train' adds FSDP sharding of the non-TP dim over 'data' (ZeRO-3;
    XLA inserts the all-gathers); mode='serve' keeps params replicated over
    data (bf16 fits; avoids per-token all-gathers on decode).
    """
    fsdp = "data" if (mode == "train" and "data" in mesh.axis_names) else None

    # tail specs: rightmost dims of the unstacked parameter
    TAIL: dict[str, tuple] = {
        # column parallel (in, out_tp)
        "wq": (fsdp, "model"), "wk": (fsdp, "model"), "wv": (fsdp, "model"), "wi": (fsdp, "model"),
        # row parallel (in_tp, out)
        "wo": ("model", fsdp),
        "router": (fsdp, None),
        "moe_wi": ("model", fsdp, None),   # (E, D, Fe): EP over experts
        "moe_wo": ("model", None, fsdp),   # (E, Fe, D)
        "in_proj": (fsdp, "model"),
        "out_proj": ("model", fsdp),
        "conv_w": (None, "model"),         # (w, channels_tp)
        "conv_b": ("model",),
        "A_log": ("model",), "D_skip": ("model",), "dt_bias": ("model",),
        "ssm_norm": ("model",),
        "tokens": ("model", fsdp),         # (V, D)
        "head": (fsdp, "model"),           # (D, V)
        "frontend_proj": (None, fsdp),
    }

    def spec(path: tuple[str, ...], leaf) -> P:
        name = path[-1]
        shape = leaf.shape
        tail = TAIL.get(name)
        if tail is None:
            return P()  # norms / biases / scalars: replicate
        pad = len(shape) - len(tail)
        if pad < 0:
            return P()
        full = (None,) * pad + tuple(tail)
        return validate_spec(P(*full), shape, mesh)

    return spec


def tree_shardings(tree, cfg, mesh: Mesh, mode: str = "train"):
    """NamedShardings matching ``tree`` (of arrays or ShapeDtypeStructs)."""
    fn = param_spec_fn(cfg, mesh, mode)

    def to_sharding(path, leaf):
        names = tuple(
            p.key if hasattr(p, "key") else str(p.idx) if hasattr(p, "idx") else str(p)
            for p in path
        )
        return NamedSharding(mesh, fn(names, leaf))

    return jax.tree_util.tree_map_with_path(to_sharding, tree)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(data_axes(mesh)))


def batch_spec(mesh: Mesh) -> P:
    return P(data_axes(mesh))


def cache_spec(cfg, mesh: Mesh) -> P:
    """KV cache (L, B, KV, S, Dh): batch over data; kv-heads over 'model' when
    divisible, else sequence over 'model' (SP decode)."""
    tp = model_axis_size(mesh)
    dp = data_axes(mesh)
    if cfg.num_kv_heads and _div(cfg.num_kv_heads, tp):
        return P(None, dp, "model", None, None)
    return P(None, dp, None, "model", None)


def slot_cache_spec(cfg, mesh: Mesh) -> P:
    """Serving-engine slot cache (L, max_slots, KV, max_seq, Dh).

    The slot axis takes the batch position: requests land in slots, so DP
    shards *slots* over ('pod', 'data') — each data shard runs its own slice
    of the continuous batch. Within a shard the same TP policy as the
    rectangular cache applies: kv-heads over 'model' when divisible, else
    sequence over 'model' (SP decode; the EXAQ histogram combine composes the
    softmax across sequence shards — DESIGN.md §2/§Serving)."""
    tp = model_axis_size(mesh)
    dp = data_axes(mesh)
    if cfg.num_kv_heads and _div(cfg.num_kv_heads, tp):
        return P(None, dp, "model", None, None)
    return P(None, dp, None, "model", None)


def block_pool_spec(cfg, mesh: Mesh) -> P:
    """Paged-engine block pool (L, num_blocks, KV, block_size, Dh).

    Unlike the slot cache there is no batch-like axis to hand to DP: blocks
    are a *global* pool shared by every request (that sharing is the whole
    point — DESIGN.md §3), so the block axis stays unsharded and each data
    shard would run its own engine+pool instead. Within the pool the usual TP
    policy applies to kv-heads when divisible. The in-block sequence axis
    (block_size tokens) is too small to shard — sequence parallelism at the
    paged layer happens by *distributing whole blocks*, whose partial EXAQ
    histograms combine exactly (§2); that layout is future work and needs no
    new spec here."""
    tp = model_axis_size(mesh)
    if cfg.num_kv_heads and _div(cfg.num_kv_heads, tp):
        return P(None, None, "model", None, None)
    return P(None, None, None, None, None)


def block_scale_spec(cfg, mesh: Mesh) -> P:
    """Dequant scale planes of an int8 block pool, (L, num_blocks, KV)
    (DESIGN.md §6): same policy as ``block_pool_spec`` — the block axis is a
    global shared pool (unsharded); the kv-head axis follows the payload's
    'model' sharding when divisible so each TP shard holds exactly the
    scales of the heads it owns."""
    tp = model_axis_size(mesh)
    if cfg.num_kv_heads and _div(cfg.num_kv_heads, tp):
        return P(None, None, "model")
    return P(None, None, None)


def block_sub_scale_spec(cfg, mesh: Mesh) -> P:
    """Sub-block scale-code planes of a packed int4 pool,
    (L, num_blocks, KV, n_sub) (DESIGN.md §10): ``block_scale_spec`` with a
    trailing unsharded sub-block axis — the kv-head axis follows the
    payload's 'model' sharding when divisible so each TP shard holds exactly
    the sub codes of the heads it owns."""
    tp = model_axis_size(mesh)
    if cfg.num_kv_heads and _div(cfg.num_kv_heads, tp):
        return P(None, None, "model", None)
    return P(None, None, None, None)


def state_pool_specs(cfg, mesh: Mesh) -> dict[str, P]:
    """Paged state-pool planes (DESIGN.md §13): "conv" (L, N, w-1, ch) and
    "ssm" (L, N, nh, hd, ds). Like ``block_pool_spec``, the block axis is a
    *global* pool shared by every request, so it stays unsharded; conv
    channels / ssm heads go over 'model' when divisible, matching the
    activation sharding of the mamba stack (``make_activation_rules``)."""
    tp = model_axis_size(mesh)
    ch_ax = "model" if _div(cfg.d_inner + 2 * cfg.ssm_state, tp) else None
    heads_ax = "model" if _div(cfg.ssm_heads, tp) else None
    return {
        "conv": P(None, None, None, ch_ax),
        "ssm": P(None, None, heads_ax, None, None),
    }


def ssm_cache_specs(cfg, mesh: Mesh) -> dict[str, P]:
    dp = data_axes(mesh)
    tp = model_axis_size(mesh)
    heads_ax = "model" if _div(cfg.ssm_heads, tp) else None
    return {
        "conv": P(None, dp, None, "model" if _div(cfg.d_inner + 2 * cfg.ssm_state, tp) else None),
        "ssm": P(None, dp, heads_ax, None, None),
    }
