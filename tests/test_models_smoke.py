"""Per-architecture smoke tests (required deliverable f): reduced config of
the same family, one forward/train step on CPU, output shapes + no NaNs,
plus prefill->decode parity against the train-mode forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_configs
from repro.models import build_model, init_params
from repro.optim.adamw import AdamW
from repro.runtime.train import init_train_state, make_train_step

ALL_ARCHS = [a for a in list_configs() if a != "llama1-7b"]


def _batch(cfg, B=2, S=32, seed=0):
    rng = np.random.default_rng(seed)
    b = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32),
    }
    if cfg.frontend == "vlm":
        b["vision_embeds"] = jnp.asarray(rng.normal(0, 1, (B, cfg.frontend_tokens, cfg.frontend_dim)), jnp.float32)
    if cfg.family == "audio":
        b["audio_embeds"] = jnp.asarray(rng.normal(0, 1, (B, cfg.enc_seq, cfg.frontend_dim)), jnp.float32)
    return b


def test_all_ten_assigned_archs_present():
    assert len(ALL_ARCHS) == 10


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch).reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, aux = m.forward_train(params, batch)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", ["yi-6b", "deepseek-moe-16b", "zamba2-2.7b"])
def test_jitted_init_matches_eager(arch):
    """``init_params`` (one jitted program, the only way a full-width tree fits
    a chip) draws the same keys in the same order as the eager ``init``: same
    tree, shapes and dtypes, and the same values."""
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(0)
    eager = build_model(cfg).init(key, jnp.bfloat16)
    jitted = init_params(cfg, key, jnp.bfloat16)
    assert jax.tree.structure(eager) == jax.tree.structure(jitted)
    for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(jitted)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_one_train_step(arch):
    cfg = get_config(arch).reduced()
    opt = AdamW(lr=1e-3)
    state = init_train_state(cfg, opt, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, opt))
    state2, metrics = step(state, _batch(cfg))
    assert int(state2["step"]) == 1
    assert np.isfinite(float(metrics["loss"]))
    # params actually moved
    delta = jax.tree.reduce(
        lambda a, b: a + b, jax.tree.map(lambda a, b: float(jnp.abs(a - b).sum()), state["params"], state2["params"])
    )
    assert delta > 0


@pytest.mark.parametrize("arch", ["yi-6b", "mamba2-1.3b", "zamba2-2.7b", "whisper-large-v3"])
def test_prefill_decode_parity(arch):
    """prefill(t[:n]) + decode(t[n]) logits == forward_train(t)[:, n] — the
    serving path is consistent with the training forward (exact softmax to
    isolate cache correctness from quantization semantics)."""
    cfg = get_config(arch).reduced().with_quant(softmax_impl="exact")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(1))
    B, S = 2, 16
    batch = _batch(cfg, B, S, seed=3)
    full_logits, _ = m.forward_train(params, batch)

    n = S - 1
    pre = {k: (v[:, :n] if k in ("tokens", "labels") else v) for k, v in batch.items()}
    cache = m.init_cache(B, S + 4, dtype=jnp.float32)
    lg, cache = m.prefill(params, pre, cache)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full_logits[:, n - 1]), atol=2e-2)
    lg2, cache = m.decode_step(params, batch["tokens"][:, n : n + 1], cache)
    np.testing.assert_allclose(np.asarray(lg2), np.asarray(full_logits[:, n]), atol=2e-2)


@pytest.mark.parametrize("arch", ["internlm2-1.8b"])
def test_exaq_serving_close_to_exact(arch):
    """EXAQ INT2 logits track exact-softmax logits — after calibration
    (paper §5.1.1: the clip must come from observed sigma; the uncalibrated
    default is visibly worse, which is itself part of the paper's claim)."""
    base = get_config(arch).reduced()
    m_exact = build_model(base.with_quant(softmax_impl="exact"))
    cfg_q = base.with_quant(softmax_impl="exaq", bits=2)
    m_exaq = build_model(cfg_q)
    params = m_exact.init(jax.random.PRNGKey(2))
    batch = _batch(base, 2, 16, seed=5)
    le, _ = m_exact.forward_train(params, batch)

    def corr_of(qstate):
        lq, _ = m_exaq.forward_train(params, batch, qstate)
        a, b = np.asarray(le).ravel(), np.asarray(lq).ravel()
        return np.corrcoef(a, b)[0, 1]

    corr_default = corr_of(None)
    stats = m_exact.calibrate(params, batch)
    qs = m_exaq.qstate_from_stats(stats)
    corr_calibrated = corr_of(qs)
    # paper Table 2: INT2 ~2% degradation, INT3 near-lossless
    assert corr_calibrated > 0.98, (corr_default, corr_calibrated)
    assert corr_calibrated >= corr_default - 1e-3
    m3 = build_model(base.with_quant(softmax_impl="exaq", bits=3))
    l3, _ = m3.forward_train(params, batch, m3.qstate_from_stats(stats))
    le, _ = m_exact.forward_train(params, batch)
    corr3 = np.corrcoef(np.asarray(le).ravel(), np.asarray(l3).ravel())[0, 1]
    assert corr3 > 0.995 and corr3 > corr_calibrated


def test_full_configs_match_assignment():
    """Exact assigned hyperparameters (deliverable f)."""
    c = get_config("qwen3-32b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.d_ff, c.vocab_size) == (
        64, 5120, 64, 8, 25600, 151936,
    ) and c.qk_norm
    c = get_config("deepseek-moe-16b")
    assert (c.moe.num_experts, c.moe.top_k, c.moe.num_shared) == (64, 6, 2)
    c = get_config("phi3.5-moe-42b-a6.6b")
    assert (c.moe.num_experts, c.moe.top_k) == (16, 2)
    c = get_config("mamba2-1.3b")
    assert c.ssm_state == 128 and c.num_heads == 0
    c = get_config("zamba2-2.7b")
    assert c.ssm_state == 64 and c.hybrid_period == 6
    c = get_config("whisper-large-v3")
    assert c.enc_layers == 32 and c.num_layers == 32 and c.d_model == 1280
    c = get_config("internvl2-1b")
    assert c.frontend == "vlm" and c.num_kv_heads == 2
    c = get_config("stablelm-12b")
    assert c.d_ff == 13824 and c.vocab_size == 100352
