"""The MoE family through the harness with no edit to it: a tiny DeepSeekMoE
(one leading dense layer, two expert layers, shard 3 of 16 of the registry's
64 experts at their published width) runs whole on the CPU through
``run_cell`` (the look for a chip skipped). The served model passes its
limits against ``reference/moe.py``, the float8 control fails them, and the
window's routing counters reach the new per-layer metric."""

import dataclasses
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run  # noqa: E402

SEED = 2**33 + 29


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


def test_tiny_moe_runs_whole_and_its_control_fails():
    conf = json.loads((BENCH / "testdata" / "tiny_moe.json").read_text())
    mix = json.loads((BENCH / "testdata" / "tiny_chat.json").read_text())
    e2e = [{"name": "output_tokens_per_s", "unit": "tokens/s"}, {"name": "setup_s", "unit": "s"}]
    cell = run.Cell("tiny-moe.chat", 1, conf, mix, e2e, [])
    keep = {}
    result, log = run.run_cell(cell, seed=SEED, seconds=6, trace=False, need_tpu=False,
                               control=True, keep=keep)
    log = "\n".join(log)
    v, rd = keep["verdict"], keep["run"]
    assert not result["correct"], log                      # the control is judged, and fails
    assert result["checks"]["mean_gap"]["value"] > result["checks"]["mean_gap"]["limit"], log
    assert dataclasses.replace(v, control=None).ok, log    # the program passes
    assert v.sampled_tokens >= v.sample_tokens
    rows = run.metric_module("moe_rows_per_expert").read(rd)
    assert rows is not None and rows >= 1.0
    assert rd.delta("moe_experts_touched") <= rd.delta("moe_expert_slots")
