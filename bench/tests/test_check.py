"""The comparison that decides ``correct``, driven through a whole run of a
tiny dense model on the CPU (the harness's look for a chip skipped).

* The served model passes its limits, and the control (the reference in
  float8, put in the program's place) fails them.
* With the timed path broken underneath, ``correct`` comes out false: a
  token altered where the engine produces it, and device steps that hand
  back their cache state unchanged.

These are slow (a run compiles the tiny model's programs and the Pallas
kernels in interpret mode); run them by hand: ``python -m pytest bench/tests``.
"""

import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run  # noqa: E402

SEED = 2**33 + 11


def tiny_cell() -> "run.Cell":
    conf = json.loads((BENCH / "testdata" / "tiny.json").read_text())
    mix = json.loads((BENCH / "testdata" / "tiny_chat.json").read_text())
    e2e = [{"name": "output_tokens_per_s", "unit": "tokens/s"}, {"name": "setup_s", "unit": "s"}]
    return run.Cell("tiny.chat", 1, conf, mix, e2e, [])


def _run(**kw):
    result, log = run.run_cell(tiny_cell(), seed=SEED, seconds=6, trace=False, need_tpu=False, **kw)
    return result, "\n".join(log)


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


def test_served_model_passes_and_the_float8_control_fails():
    """One run: the control (the reference in float8, in the program's
    place) is what is judged, and fails; the program's own numbers from the
    same sample pass."""
    keep = {}
    result, log = _run(control=True, keep=keep)
    v = keep["verdict"]
    assert not result["correct"], log
    assert result["checks"]["mean_gap"]["value"] == v.control.numbers()["mean_gap"]
    assert result["checks"]["mean_gap"]["value"] > result["checks"]["mean_gap"]["limit"], log
    assert dataclasses.replace(v, control=None).ok, log
    assert v.sampled_tokens >= v.sample_tokens


def test_a_token_altered_where_it_is_produced_fails(monkeypatch):
    from repro.runtime import engine_core

    orig = engine_core.HostCore._absorb_chunk

    def altered(self, tokens, lens, active, budget, emitted, masks, was_active):
        emitted = np.array(emitted)
        emitted[0] = (emitted[0] + 1) % 512   # every slot's first token of the chunk
        return orig(self, tokens, lens, active, budget, emitted, masks, was_active)

    monkeypatch.setattr(engine_core.HostCore, "_absorb_chunk", altered)
    result, log = _run()
    assert not result["correct"], log


def test_device_steps_that_keep_their_state_fail(monkeypatch):
    """Prefill and decode steps hand back the cache they were given: no
    token's keys and values ever reach it."""
    from repro.runtime import device_step

    step = device_step.PagedDeviceStep
    orig_decode, orig_prefill = step.decode_chunk, step.prefill_chunk

    def keep(pool):
        return {k: v.copy() for k, v in pool.items()}

    def decode(self, pool, *a, **kw):
        kept = keep(pool)
        return (kept,) + tuple(orig_decode(self, pool, *a, **kw)[1:])

    def prefill(self, pool, *a, **kw):
        kept = keep(pool)
        return orig_prefill(self, pool, *a, **kw)[0], kept

    monkeypatch.setattr(step, "decode_chunk", decode)
    monkeypatch.setattr(step, "prefill_chunk", prefill)
    result, log = _run()
    assert not result["correct"], log
