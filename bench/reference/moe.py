"""Plain reference for the MoE decoder family (DeepSeekMoE, arXiv:2401.06066).

Written from the published architecture, not from the program. Per layer:
RMSNorm pre-norm, multi-head attention with rotary embeddings on the whole
head (the attention arithmetic, EXAQ's Algorithm 2 or the exact softmax, is
``dense.py``'s), then the feed-forward on ``u``, the RMSNorm of the residual
after attention:

* the first ``first_k_dense_replace`` layers: a SwiGLU MLP of width
  ``intermediate_size``;
* every later layer: the gate ``s = softmax(u · W_gate)`` over all the
  published routed experts, its top ``num_experts_per_tok`` taken, divided by
  their sum only where ``norm_topk_prob`` is true, and
  ``y = SwiGLU_shared(u) + Σ_{e ∈ top-k ∩ held} s_e · SwiGLU_e(u)``, with the
  ``n_shared_experts`` shared experts as one SwiGLU of width
  ``n_shared_experts · moe_intermediate_size``.

The file's ``expert_parallel`` group states the deployment: each layer's
routed experts are divided over ``chips`` chips, and this chip, ``shard``,
holds the contiguous ``n_routed_experts`` of them from ``shard ·
n_routed_experts``. The router keeps its published width; what the other
chips' experts would add is left out, here as in the program.

Departures from the published model: weights are random, drawn from the
seed (bf16, the router in float32 holding bf16 values, as the program takes
it); only the held share of the routed experts is computed; the attention
softmax is the configuration's stated arithmetic (EXAQ); no auxiliary
losses (inference).

``make_weights`` gives the program's tree: "layers" (every layer's norms and
attention, stacked over depth), "dense_mlp" (the leading dense layers' MLPs)
and "moe" (the expert layers: router, held experts' packed gate/up ``moe_wi``
and ``moe_wo``, the shared experts' ``wi``/``wo``). ``logits`` is the float32
forward of one whole sequence, layer by layer, attention in blocks of query
rows and the routed experts one at a time, so that it fits beside the
program's weights; ``precision="fp8"`` is the control. It reuses ``dense.py``'s
helpers and imports nothing of the program.
"""

from __future__ import annotations

import importlib.util
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def _dense():
    """``dense.py`` beside this file, under the name ``run.py`` gives it."""
    name = "bench_reference_dense"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, Path(__file__).with_name("dense.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


dense = _dense()


def held_range(conf: dict) -> tuple[int, int]:
    n = conf["n_routed_experts"]
    ep = conf["expert_parallel"]
    if n * ep["chips"] != ep["n_routed_experts"] or not 0 <= ep["shard"] < ep["chips"]:
        raise ValueError(f"{n} held experts are not one share of {ep}")
    return ep["shard"] * n, (ep["shard"] + 1) * n


def weight_shapes(conf: dict) -> dict:
    L, d, F, V = (conf["num_hidden_layers"], conf["hidden_size"],
                  conf["intermediate_size"], conf["vocab_size"])
    nd = conf["first_k_dense_replace"]
    Lm = L - nd
    fe = conf["moe_intermediate_size"]
    fs = conf["n_shared_experts"] * fe
    held = conf["n_routed_experts"]
    hq = conf["num_attention_heads"] * conf["head_dim"]
    hkv = conf["num_key_value_heads"] * conf["head_dim"]
    return {
        "embed": {"tokens": (V, d)},
        "final_norm": (d,),
        "head": (d, V),
        "layers": {
            "ln1": (L, d), "ln2": (L, d),
            "attn": {"wq": (L, d, hq), "wk": (L, d, hkv), "wv": (L, d, hkv), "wo": (L, hq, d)},
        },
        "dense_mlp": {"wi": (nd, d, 2 * F), "wo": (nd, F, d)},
        "moe": {
            "router": (Lm, d, conf["expert_parallel"]["n_routed_experts"]),
            "moe_wi": (Lm, held, d, 2 * fe), "moe_wo": (Lm, held, fe, d),
            "shared": {"wi": (Lm, d, 2 * fs), "wo": (Lm, fs, d)},
        },
    }


def make_weights(conf: dict, seed: int, dtype=jnp.bfloat16):
    """Random weights from ``seed``: embeddings N(0, 1), each projection
    N(0, 1/fan_in), norm scales U(0.75, 1.25); the router in float32."""
    shapes = weight_shapes(conf)
    flat = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=lambda x: isinstance(x, tuple))[0]
    tree = jax.tree.structure(shapes, is_leaf=lambda x: isinstance(x, tuple))

    def draw(key):
        out = []
        for i, (path, shape) in enumerate(flat):
            path = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, i)
            if "norm" in path or "ln" in path:
                x = jax.random.uniform(k, shape, jnp.float32, 0.75, 1.25)
            elif "embed" in path:
                x = jax.random.normal(k, shape, jnp.float32)
            else:
                x = jax.random.normal(k, shape, jnp.float32) * shape[-2] ** -0.5
            x = x.astype(dtype)
            out.append(x.astype(jnp.float32) if "router" in path else x)
        return jax.tree.unflatten(tree, out)

    return jax.jit(draw)(dense._key(seed))


def _at(stack, i):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), stack)


def _swiglu(x, wi, wo, fp8):
    gate, up = jnp.split(dense._mm(x, wi, fp8), 2, axis=-1)
    return dense._mm(gate * jax.nn.sigmoid(gate) * up, wo, fp8)


def _attention(h, lw, n_live, conf, fp8):
    """The residual after the layer's attention, and ``u``, its RMSNorm."""
    T = h.shape[0]
    H, KV, dh = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    x = dense._rmsnorm(h, lw["ln1"], eps)
    q = dense._rope(dense._mm(x, lw["attn"]["wq"], fp8).reshape(T, H, dh), theta)
    k = dense._rope(dense._mm(x, lw["attn"]["wk"], fp8).reshape(T, KV, dh), theta)
    v = dense._mm(x, lw["attn"]["wv"], fp8).reshape(T, KV, dh)
    h = h + dense._mm(dense._exaq_attention(q, k, v, n_live, conf["quant"], fp8),
                      lw["attn"]["wo"], fp8)
    return h, dense._rmsnorm(h, lw["ln2"], eps)


@partial(jax.jit, static_argnames=("conf_items", "fp8"))
def _dense_layer(h, layers, dense_mlp, index, n_live, *, conf_items, fp8):
    conf = _thaw(conf_items)
    h, u = _attention(h, _at(layers, index), n_live, conf, fp8)
    mlp = _at(dense_mlp, index)
    return h + _swiglu(u, mlp["wi"], mlp["wo"], fp8)


def gate_weights(u, router, conf: dict, fp8: bool):
    """(T, published experts): each row's gate weight on every routed
    expert, zero outside its top-k."""
    s = jax.nn.softmax(dense._mm(u, router, fp8), axis=-1)
    k = conf["num_experts_per_tok"]
    top, idx = jax.lax.top_k(s, k)
    if conf["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32) * top[..., None], axis=1)


@partial(jax.jit, static_argnames=("conf_items", "fp8"))
def _moe_layer(h, layers, moe, index, moe_index, n_live, *, conf_items, fp8):
    conf = _thaw(conf_items)
    h, u = _attention(h, _at(layers, index), n_live, conf, fp8)
    lw = _at(moe, moe_index)
    lo, hi = held_range(conf)
    w = gate_weights(u, lw["router"], conf, fp8)[:, lo:hi]          # (T, held)

    def expert(y, e):
        out = _swiglu(u, lw["moe_wi"][e], lw["moe_wo"][e], fp8)
        return y + w[:, e][:, None] * out, None

    y, _ = jax.lax.scan(expert, _swiglu(u, lw["shared"]["wi"], lw["shared"]["wo"], fp8),
                        jnp.arange(hi - lo))
    return h + y


def _frozen(conf: dict):
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
            "num_experts_per_tok", "norm_topk_prob", "n_routed_experts")
    quant = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                         for k, v in conf["quant"].items()))
    ep = tuple(sorted(conf["expert_parallel"].items()))
    return tuple((k, conf[k]) for k in keys) + (("quant", quant), ("expert_parallel", ep))


def _thaw(conf_items) -> dict:
    conf = dict(conf_items)
    conf["quant"], conf["expert_parallel"] = dict(conf["quant"]), dict(conf["expert_parallel"])
    return conf


def logits(weights, conf: dict, tokens, rows, *, precision: str = "fp32") -> np.ndarray:
    """Float32 logits at positions ``rows`` of the sequence ``tokens``
    (position p's logits predict token p + 1). ``precision="fp8"`` rounds
    every matmul operand, the router's too, to float8_e4m3 (the control)."""
    fp8 = {"fp32": False, "fp8": True}[precision]
    items = _frozen(conf)
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    T = -(-n // dense.LEN_BUCKET) * dense.LEN_BUCKET
    padded = np.zeros((T,), np.int32)
    padded[:n] = tokens
    nd = conf["first_k_dense_replace"]
    with jax.default_matmul_precision("highest"):
        h = jnp.take(weights["embed"]["tokens"], jnp.asarray(padded), axis=0).astype(jnp.float32)
        for layer in range(conf["num_hidden_layers"]):
            if layer < nd:
                h = _dense_layer(h, weights["layers"], weights["dense_mlp"], jnp.int32(layer),
                                 jnp.int32(n), conf_items=items, fp8=fp8)
            else:
                h = _moe_layer(h, weights["layers"], weights["moe"], jnp.int32(layer),
                               jnp.int32(layer - nd), jnp.int32(n), conf_items=items, fp8=fp8)
        rows = np.asarray(rows, np.int32)
        padded_rows = np.zeros((-(-len(rows) // dense.ROWS_BUCKET) * dense.ROWS_BUCKET,), np.int32)
        padded_rows[: len(rows)] = rows
        out = dense._head(h, weights["final_norm"], weights["head"], jnp.asarray(padded_rows),
                          eps=conf["rms_norm_eps"], fp8=fp8)
    return np.asarray(out, np.float32)[: len(rows)]
