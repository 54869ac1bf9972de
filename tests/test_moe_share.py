"""DeepSeekMoE's expert layer as one chip of expert parallelism serves it.

* Prefill and then paged decode through ``PagedEngine`` agree on logits with
  the plain float32 reference (``bench/reference/moe.py``) at a small size,
  and the reference in float8 does not.
* The shards' partial outputs, the shared expert counted once, add up to the
  uncut layer.
* A leading dense layer is present and used; the gate is renormalised only
  where the config says so.
* The engine's routing counters agree with a host recount of the routing.
* ``count_active_params`` knows leading dense layers, shared experts and a
  held share.
"""

import dataclasses
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import MoEConfig
from repro.models import build_model, init_params, moe
from repro.runtime.engine import PagedEngine
from repro.runtime.engine_core import EngineConfig, Request
from repro.utils.params import count_active_params, count_params, per_expert_params

REF_PATH = Path(__file__).resolve().parents[1] / "bench" / "reference" / "moe.py"
QUANT = {"softmax": "exaq", "bits": 2, "sigma": 2.0, "clip_rule": [-1.66, -1.85], "fused": True}


def _reference():
    spec = importlib.util.spec_from_file_location("bench_reference_moe", REF_PATH)
    if "bench_reference_moe" not in sys.modules:
        mod = importlib.util.module_from_spec(spec)
        sys.modules["bench_reference_moe"] = mod
        spec.loader.exec_module(mod)
    return sys.modules["bench_reference_moe"]


def _pair(*, experts=8, top_k=3, shards=2, shard=1, norm_topk=False):
    """A small DeepSeekMoE-shaped program config (MHA, one leading dense
    layer, two expert layers, one shared expert) and the reference's
    configuration for the same model and share."""
    cfg = dataclasses.replace(
        get_config("deepseek-moe-16b"), num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
        head_dim=16, d_ff=128, vocab_size=256, expert_parallel=shards, expert_shard=shard,
        moe=MoEConfig(num_experts=experts, top_k=top_k, num_shared=1, d_expert=32,
                      first_dense=1, norm_topk=norm_topk),
    ).with_quant(softmax_impl="exaq", bits=2, sigma_default=2.0, clip_rule="paper")
    conf = {
        "num_hidden_layers": 3, "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_attention_heads": 4, "num_key_value_heads": 4,
        "head_dim": 16, "vocab_size": 256, "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "n_routed_experts": experts // shards, "n_shared_experts": 1, "num_experts_per_tok": top_k,
        "first_k_dense_replace": 1, "norm_topk_prob": norm_topk,
        "expert_parallel": {"n_routed_experts": experts, "chips": shards, "shard": shard},
        "quant": QUANT,
    }
    return cfg, conf


def _served_logits(cfg, weights, prompt, max_new):
    """Serve one greedy request through ``PagedEngine`` (two prefill chunks,
    then decode chunks of 8 and 1 steps) and return its tokens and the
    logits each served token was picked from: the last prefill chunk's row,
    then each decode step's row of the live slot."""
    eng = PagedEngine(cfg, weights, EngineConfig(max_slots=2, max_seq=128, block_size=8,
                                                 prefill_chunk=16, fused=True))
    first, steps = [], []
    dev = eng._dev
    prefill = dev.prefill_chunk

    def record_prefill(*a):
        logits, pool = prefill(*a)
        first[:] = [np.asarray(logits[0], np.float32)]
        return logits, pool

    class Recording:
        """The engine's model, with each decode step's logits and live
        slots sent to the host from inside the compiled decode chunk."""

        def __getattr__(self, name):
            return getattr(model, name)

        def decode_step_paged(self, params, tokens, pool, tables, lens, active, *a, **kw):
            out = model.decode_step_paged(params, tokens, pool, tables, lens, active, *a, **kw)
            jax.debug.callback(lambda lg, act: steps.append((np.asarray(lg, np.float32), np.asarray(act))),
                               out[0], active, ordered=True)
            return out

    model = dev.model
    dev.model = Recording()
    dev.prefill_chunk = record_prefill
    uid = eng.submit(Request(np.asarray(prompt, np.int32), max_new))
    tokens = eng.run()[uid].tokens
    rows = [lg[act][0] for lg, act in steps if act.any()]
    return tokens, np.stack(first + rows)


def test_prefill_then_paged_decode_matches_the_reference_on_logits():
    """Logits, not tokens: with random weights the best logit changes on
    rounding. Tolerance: the mean absolute difference over every logit of
    every served position is held to 5% of the reference logits' spread
    (their standard deviation). The program runs bf16 weights and
    activations against the reference's float32, and a score on a 2-bit
    EXAQ boundary may take either code, which moves one key's weight by
    e^1.29 in a context of 29-38 keys: single rows then differ by up to a
    quarter of the spread, so the mean and not the largest difference is
    held (it reads 0.027 of the spread here). The same reference with
    every matmul operand in float8, one step below the served bf16, reads
    0.116 and must fail."""
    ref = _reference()
    cfg, conf = _pair()
    weights = ref.make_weights(conf, seed=2**33 + 5)
    prompt = np.random.default_rng(3).integers(0, conf["vocab_size"], 29)
    tokens, got = _served_logits(cfg, weights, prompt, max_new=10)
    assert len(tokens) == 10 and got.shape == (10, conf["vocab_size"])
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    rows = np.arange(len(prompt) - 1, len(seq))
    want = ref.logits(weights, conf, seq, rows)
    low = ref.logits(weights, conf, seq, rows, precision="fp8")
    tol = 0.05 * want.std()
    assert np.abs(got - want).mean() < tol, (np.abs(got - want).mean(), tol)
    assert np.abs(low - want).mean() > tol, (np.abs(low - want).mean(), tol)
    # the served tokens are the program's own greedy picks
    np.testing.assert_array_equal(got.argmax(axis=1), np.asarray(tokens))


@pytest.mark.parametrize("norm_topk", [False, True])
def test_expert_shards_add_up_to_the_uncut_layer(norm_topk):
    """Four chips of expert parallelism each hold two of eight experts; each
    computes its experts' part for the tokens routed to them plus the shared
    expert. Their outputs, less the shared expert counted three times too
    many, are the uncut layer, as the reference computes it."""
    ref = _reference()
    full, conf = _pair(shards=1, shard=0, norm_topk=norm_topk)
    lp = moe.init_moe(jax.random.PRNGKey(1), full)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 7, full.d_model), jnp.float32)
    whole = moe.moe_ffn_infer(lp, x, full)
    parts = []
    for s in range(4):
        cfg = dataclasses.replace(full, expert_parallel=4, expert_shard=s)
        assert cfg.held_experts == (2 * s, 2 * s + 2)
        held = dict(lp, moe_wi=lp["moe_wi"][2 * s: 2 * s + 2], moe_wo=lp["moe_wo"][2 * s: 2 * s + 2])
        parts.append(moe.moe_ffn_infer(held, x, cfg))
    from repro.models.layers import gated_mlp

    shared = gated_mlp(lp["shared"], x)
    np.testing.assert_allclose(np.asarray(sum(parts) - 3 * shared), np.asarray(whole), atol=1e-5)
    # the reference's uncut layer: its gate, every expert's SwiGLU
    u = np.asarray(x).reshape(-1, full.d_model)
    w = np.asarray(ref.gate_weights(jnp.asarray(u), lp["router"], conf, False))
    y = np.asarray(ref._swiglu(jnp.asarray(u), lp["shared"]["wi"], lp["shared"]["wo"], False))
    for e in range(full.moe.num_experts):
        y = y + w[:, e:e + 1] * np.asarray(ref._swiglu(jnp.asarray(u), lp["moe_wi"][e], lp["moe_wo"][e], False))
    np.testing.assert_allclose(np.asarray(whole).reshape(u.shape), y, atol=1e-4)


def test_a_share_must_divide_the_experts():
    cfg = dataclasses.replace(get_config("deepseek-moe-16b"), expert_parallel=4, expert_shard=3)
    assert cfg.held_experts == (48, 64)
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, expert_parallel=5).held_experts
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, expert_shard=4).held_experts


def test_leading_dense_layer_is_present_and_used():
    cfg = get_config("deepseek-moe-16b").reduced()
    assert cfg.moe.first_dense == 1 and cfg.num_layers == 4
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape[0], params["dense_mlp"]) == {"wi": 1, "wo": 1}
    assert params["moe"]["router"].shape[0] == cfg.num_layers - 1
    assert params["layers"]["ln1"].shape[0] == cfg.num_layers
    assert "mlp" not in params["layers"] and "moe" not in params["layers"]
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 16)), jnp.int32)
    base, aux = m.forward_train(params, {"tokens": tokens})
    assert np.isfinite(float(aux["moe_lb"]))
    # the dense MLP feeds layer 0; the first expert layer is layer 1
    off = dict(params, dense_mlp=jax.tree.map(jnp.zeros_like, params["dense_mlp"]))
    assert float(jnp.abs(m.forward_train(off, {"tokens": tokens})[0] - base).max()) > 1e-3
    moe0 = jax.tree.map(lambda a: a.at[0].set(0), params["moe"])
    assert float(jnp.abs(m.forward_train(dict(params, moe=moe0), {"tokens": tokens})[0] - base).max()) > 1e-3


def test_a_moe_model_without_leading_dense_layers_has_the_same_layout():
    """phi3.5-moe has no leading dense layer: its "dense_mlp" stack is
    empty and its "moe" stack covers every layer, the first included."""
    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced()
    assert cfg.moe.first_dense == 0
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape[0], params["dense_mlp"]) == {"wi": 0, "wo": 0}
    assert params["moe"]["router"].shape[0] == cfg.num_layers
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 16)), jnp.int32)
    base, aux = m.forward_train(params, {"tokens": tokens})
    assert np.isfinite(float(aux["moe_lb"])) and float(aux["moe_lb"]) > 0
    moe0 = jax.tree.map(lambda a: a.at[0].set(0), params["moe"])
    assert float(jnp.abs(m.forward_train(dict(params, moe=moe0), {"tokens": tokens})[0] - base).max()) > 1e-3


@pytest.mark.parametrize("arch,renormalised", [("deepseek-moe-16b", False),
                                               ("phi3.5-moe-42b-a6.6b", True)])
def test_gate_renormalises_only_where_the_config_says(arch, renormalised):
    cfg = get_config(arch).reduced()
    assert cfg.moe.norm_topk is renormalised
    lp = moe.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, cfg.d_model), jnp.float32)
    _, probs, gates, idx = moe._gate(lp, x, cfg)
    top = np.take_along_axis(np.asarray(probs), np.asarray(idx), axis=-1)
    if renormalised:
        np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-5)
    else:
        np.testing.assert_allclose(np.asarray(gates), top, rtol=1e-6)
        assert (np.asarray(gates).sum(-1) < 1.0 - 1e-3).all()


def test_routing_counters_agree_with_a_host_recount(monkeypatch):
    """Two requests of different lengths, so the shorter one's slot goes
    idle inside a decode chunk: the engine's ``moe_rows``,
    ``moe_experts_touched`` and ``moe_expert_slots`` equal a recount from
    the top-k choices every decode step's expert layers made, read back
    with the rows that were live."""
    cfg, conf = _pair(experts=8, top_k=3, shards=2, shard=1)
    seen = []
    infer = moe.moe_ffn_infer

    def recording(params, x, cfg, rows=None):
        if rows is not None:
            idx = moe._gate(params, x, cfg)[3]
            jax.debug.callback(lambda i, r: seen.append((np.asarray(i), np.asarray(r))), idx, rows)
        return infer(params, x, cfg, rows)

    monkeypatch.setattr(moe, "moe_ffn_infer", recording)
    weights = _reference().make_weights(conf, seed=11)
    eng = PagedEngine(cfg, weights, EngineConfig(max_slots=3, max_seq=128, block_size=8,
                                                 prefill_chunk=16, fused=True))
    rng = np.random.default_rng(5)
    eng.submit(Request(rng.integers(0, 256, 12).astype(np.int32), 10))
    eng.submit(Request(rng.integers(0, 256, 9).astype(np.int32), 4))
    eng.run()
    lo, hi = cfg.held_experts
    rows = touched = 0
    for idx, live in seen:                         # one call per decode step and expert layer
        held = (idx >= lo) & (idx < hi) & live[..., None]
        rows += int(held.sum())
        touched += len(np.unique(idx[held]))
    st = eng.stats
    n_moe = cfg.num_layers - cfg.moe.first_dense
    assert len(seen) == st["decode_steps"] * n_moe
    assert st["moe_rows"] == rows > 0
    assert st["moe_experts_touched"] == touched > 0
    assert st["moe_expert_slots"] == st["decode_steps"] * n_moe * (hi - lo)


def test_active_params_count_leading_dense_layers_shared_experts_and_shares():
    c = get_config("deepseek-moe-16b")
    # the paper's 16.4B parameters, 2.8B activated per token
    assert abs(count_params(c) - 16.4e9) < 0.05e9
    assert abs(count_active_params(c) - 2.8e9) < 0.05e9
    # one chip of 4-way expert parallelism holds a quarter of the routed
    # experts; a token still touches as many parameters
    share = dataclasses.replace(c, expert_parallel=4)
    routed = (64 - 16) * per_expert_params(c) * 27
    assert count_params(share) == count_params(c) - routed
    assert count_active_params(share) == count_active_params(c)
    # exact on a small tree: every leaf, less the routed experts no token picks
    r = c.reduced()
    pe = per_expert_params(r)
    assert count_active_params(r) == count_params(r) - (4 - 2) * pe * (r.num_layers - 1)
    p = get_config("phi3.5-moe-42b-a6.6b").reduced()
    assert count_active_params(p) == count_params(p) - (4 - 2) * per_expert_params(p) * p.num_layers


def test_jitted_init_of_a_share_has_the_reference_layout():
    """``init_params`` of one chip's share gives the tree and dtypes the
    reference's ``make_weights`` draws (what the benchmark's layout check
    compares)."""
    cfg, conf = _pair()
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    got = jax.eval_shape(lambda: _reference().make_weights(conf, seed=1))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert jax.tree.leaves(want) == jax.tree.leaves(got)
