"""Plain reference for the dense decoder family (Yi, InternLM2: Llama-style).

Written from the published architecture, not from the program: pre-norm
decoder layers with RMSNorm, grouped-query attention with rotary position
embeddings (half-split rotation, ``rope_theta``), a SwiGLU MLP, a final
RMSNorm and an untied LM head. The attention softmax follows the cell's
stated arithmetic, EXAQ (arXiv:2410.03185, Algorithm 2): each score row is
shifted by its maximum over the live keys, the shifted inputs are quantized
to ``bits`` bits over [C, 0] with C = slope * sigma + intercept (the
paper's Table 1 rule), each code is replaced by exp of its mid-rise level,
and the row is normalized by the sum of those values. ``"softmax":
"exact"`` in the configuration's ``quant`` takes the plain softmax instead.

Two things live here and nowhere else:

* ``make_weights``: the benchmark's weights, drawn from the seed on the
  device in one jitted call, in the type they are served in, laid out as
  the serving program takes them (``layers`` stacked over depth; ``wi``
  packs the gate and up projections).
* ``logits``: the float32 forward of one whole sequence, layer by layer and
  in blocks of query rows so that it fits beside the weights, with every
  matmul at ``highest`` precision. ``precision="fp8"`` is the control:
  the same forward with every matmul operand rounded to float8_e4m3 under a
  per-tensor scale, the step below the served bf16.

It imports nothing of the program and takes nothing the program made.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 512     # query rows per attention block
LEN_BUCKET = 1024   # sequences are padded to a multiple of this (one compile per bucket)
ROWS_BUCKET = 128   # compared rows are padded to a multiple of this
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _key(seed: int):
    """A PRNG key from any non-negative integer seed (wider than 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def weight_shapes(conf: dict) -> dict:
    L, d, F, V = (conf["num_hidden_layers"], conf["hidden_size"],
                  conf["intermediate_size"], conf["vocab_size"])
    hq = conf["num_attention_heads"] * conf["head_dim"]
    hkv = conf["num_key_value_heads"] * conf["head_dim"]
    return {
        "embed": {"tokens": (V, d)},
        "final_norm": (d,),
        "head": (d, V),
        "layers": {
            "ln1": (L, d), "ln2": (L, d),
            "attn": {"wq": (L, d, hq), "wk": (L, d, hkv), "wv": (L, d, hkv), "wo": (L, hq, d)},
            "mlp": {"wi": (L, d, 2 * F), "wo": (L, F, d)},
        },
    }


def make_weights(conf: dict, seed: int, dtype=jnp.bfloat16):
    """Random weights from ``seed``: embeddings N(0, 1), each projection
    N(0, 1/fan_in), norm scales U(0.75, 1.25)."""
    shapes = weight_shapes(conf)
    leaves, tree = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes, is_leaf=lambda x: isinstance(x, tuple))[0]]

    def draw(key):
        out = []
        for i, (shape, path) in enumerate(zip(leaves, paths)):
            k = jax.random.fold_in(key, i)
            if "norm" in path or "ln" in path:
                x = jax.random.uniform(k, shape, jnp.float32, 0.75, 1.25)
            elif "embed" in path:
                x = jax.random.normal(k, shape, jnp.float32)
            else:
                x = jax.random.normal(k, shape, jnp.float32) * shape[-2] ** -0.5
            out.append(x.astype(dtype))
        return jax.tree.unflatten(tree, out)

    return jax.jit(draw)(_key(seed))


def _fp8(x):
    """Round to float8_e4m3 under a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _mm(a, b, fp8: bool):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """x: (T, heads, Dh), position = row index; rotate the two halves."""
    T, _, dh = x.shape
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def exaq_clip(quant: dict) -> float:
    slope, intercept = quant["clip_rule"]
    return slope * quant["sigma"] + intercept


def _exaq_attention(q, k, v, n_live, quant: dict, fp8: bool):
    """q: (T, H, Dh); k, v: (T, KV, Dh); causal over the first ``n_live``
    rows. Returns (T, H*Dh)."""
    T, H, dh = q.shape
    KV = k.shape[1]
    g = H // KV
    levels = 2 ** quant["bits"]
    C = exaq_clip(quant)
    delta = -C / levels
    lut = jnp.asarray(np.exp(C + (np.arange(levels) + 0.5) * delta), jnp.float32)
    if fp8:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    qg = q.reshape(T, KV, g, dh)
    cols = jnp.arange(T)

    def block(i):
        rows = i * ROW_BLOCK + jnp.arange(ROW_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(qg, i * ROW_BLOCK, ROW_BLOCK, 0)   # (R, KV, g, Dh)
        s = jnp.einsum("rkgd,tkd->rkgt", qb, k, precision=HIGHEST) * dh ** -0.5
        valid = (cols[None, :] <= rows[:, None]) & (cols[None, :] < n_live)   # (R, T)
        valid = valid[:, None, None, :]
        m = jnp.max(jnp.where(valid, s, -jnp.inf), axis=-1, keepdims=True)
        if quant["softmax"] == "exact":
            e = jnp.where(valid, jnp.exp(s - m), 0.0)
        else:
            codes = jnp.clip(jnp.floor((s - m - C) / delta), 0, levels - 1).astype(jnp.int32)
            e = jnp.where(valid, lut[codes], 0.0)
        e = _fp8(e) if fp8 else e
        o = jnp.einsum("rkgt,tkd->rkgd", e, v, precision=HIGHEST)
        o = o / jnp.maximum(jnp.sum(e, axis=-1)[..., None], 1e-30)
        return o.reshape(ROW_BLOCK, H * dh)

    return jax.lax.map(block, jnp.arange(T // ROW_BLOCK)).reshape(T, H * dh)


@partial(jax.jit, static_argnames=("conf_items", "fp8"))
def _layer(h, layers, index, n_live, *, conf_items, fp8):
    conf = dict(conf_items)
    lw = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False), layers)
    quant = dict(conf["quant"])
    T = h.shape[0]
    H, KV, dh = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    x = _rmsnorm(h, lw["ln1"], eps)
    q = _rope(_mm(x, lw["attn"]["wq"], fp8).reshape(T, H, dh), theta)
    k = _rope(_mm(x, lw["attn"]["wk"], fp8).reshape(T, KV, dh), theta)
    v = _mm(x, lw["attn"]["wv"], fp8).reshape(T, KV, dh)
    h = h + _mm(_exaq_attention(q, k, v, n_live, quant, fp8), lw["attn"]["wo"], fp8)
    x = _rmsnorm(h, lw["ln2"], eps)
    gate, up = jnp.split(_mm(x, lw["mlp"]["wi"], fp8), 2, axis=-1)
    return h + _mm(gate * jax.nn.sigmoid(gate) * up, lw["mlp"]["wo"], fp8)


@partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(h, norm, head, rows, *, eps, fp8):
    x = _rmsnorm(h[rows], norm, eps)
    return _mm(x, head, fp8)


def _frozen(conf: dict):
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta")
    quant = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                         for k, v in conf["quant"].items()))
    return tuple((k, conf[k]) for k in keys) + (("quant", quant),)


def logits(weights, conf: dict, tokens, rows, *, precision: str = "fp32") -> np.ndarray:
    """Float32 logits at positions ``rows`` of the sequence ``tokens``
    (position p's logits predict token p + 1). ``precision="fp8"`` rounds
    every matmul operand to float8_e4m3 (the control)."""
    fp8 = {"fp32": False, "fp8": True}[precision]
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    T = -(-n // LEN_BUCKET) * LEN_BUCKET
    padded = np.zeros((T,), np.int32)
    padded[:n] = tokens
    h = jnp.take(weights["embed"]["tokens"], jnp.asarray(padded), axis=0).astype(jnp.float32)
    items = _frozen(conf)
    for layer in range(conf["num_hidden_layers"]):
        h = _layer(h, weights["layers"], jnp.int32(layer), jnp.int32(n),
                   conf_items=items, fp8=fp8)
    rows = np.asarray(rows, np.int32)
    padded_rows = np.zeros((-(-len(rows) // ROWS_BUCKET) * ROWS_BUCKET,), np.int32)
    padded_rows[: len(rows)] = rows
    out = _head(h, weights["final_norm"], weights["head"], jnp.asarray(padded_rows),
                eps=conf["rms_norm_eps"], fp8=fp8)
    return np.asarray(out, np.float32)[: len(rows)]
