"""Trace reduction: busy union, idle gaps named by host spans, op time."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

tr = run.tr
CHIP = "/device:TPU:0"
MS = 1_000_000


def synthetic() -> "tr.Trace":
    """A 100 ms window: two steps, with ops that overlap inside the first."""
    ops = [
        ("fusion.1", 5 * MS, 20 * MS),
        ("exaq_paged_decode_attention.3", 10 * MS, 30 * MS),    # overlaps fusion.1
        ("fusion.1", 50 * MS, 60 * MS),
        ("exaq_paged_prefill_attention.5", 58 * MS, 70 * MS),   # overlaps the fusion
        ("fusion.2", 95 * MS, 120 * MS),               # runs past the window
        ("fusion.0", -10 * MS, 2 * MS),                # starts before it
    ]
    spans = [
        ("bench.window", 0, 100 * MS),
        ("bench.step_chunk", 3 * MS, 32 * MS),
        ("bench.read_tokens", 32 * MS, 40 * MS),
        ("bench.wait_arrival", 40 * MS, 49 * MS),
        ("bench.step_chunk", 48 * MS, 72 * MS),
        ("bench.submit", 72 * MS, 74 * MS),
    ]
    return tr.Trace({CHIP: ops}, spans)


def test_busy_is_the_union_inside_the_window():
    t = synthetic()
    # [0,2] + [5,30] + [50,70] + [95,100]
    assert t.busy_ns(CHIP) == (2 + 25 + 20 + 5) * MS
    assert t.window == (0, 100 * MS)


def test_gaps_and_what_the_host_was_doing():
    t = synthetic()
    assert t.gaps(CHIP) == [(2 * MS, 5 * MS), (30 * MS, 50 * MS), (70 * MS, 95 * MS)]
    # [70, 95]: submit covers 2 ms, nothing the other 23: the host's own time
    assert t.idle_gaps(CHIP) == [["host", 25 * MS * 1e-9], ["bench.wait_arrival", 20 * MS * 1e-9],
                                 ["bench.step_chunk", 3 * MS * 1e-9]]
    by = t.idle_by_span(CHIP)
    assert by["bench.wait_arrival"] == pytest.approx(0.020)
    assert sum(by.values()) == pytest.approx(0.048)


def test_op_time_by_name_and_top_ops():
    t = synthetic()
    assert t.op_seconds(CHIP, ("exaq_paged_decode_attention",)) == pytest.approx(0.020)
    both = ("exaq_paged_prefill_attention", "exaq_paged_decode_attention")
    assert t.op_seconds(CHIP, both) == pytest.approx(0.032)
    top = dict(t.top_ops(CHIP))
    assert top["fusion.1"] == pytest.approx(0.025)
    assert top["fusion.2"] == pytest.approx(0.005)     # clipped at the window's end
    assert list(top)[0] == "fusion.1"


def test_round_trip_through_json(tmp_path):
    t = synthetic()
    t.save(tmp_path / "t.json")
    u = tr.Trace.load(tmp_path / "t.json")
    assert u.busy_ns(CHIP) == t.busy_ns(CHIP) and u.host_spans == t.host_spans


def test_merged_intervals():
    assert tr.merged([(5, 7), (1, 3), (2, 4), (7, 9)]) == [(1, 4), (5, 9)]


def test_a_recorded_v5e_trace():
    """A slice of a real v5e trace of yi-6b (one fused prefill chunk, then a
    decode chunk), reduced by ``read_xplane`` and kept in testdata."""
    t = tr.Trace.load(BENCH / "testdata" / "v5e_yi6b_trace.json")
    (chip,) = t.device_ops
    lo, hi = t.window
    gaps = t.gaps(chip)
    assert t.busy_ns(chip) + sum(e - s for s, e in gaps) == hi - lo
    kernel = [(s, e) for n, s, e in t.device_ops[chip] if n.startswith("exaq_paged_decode_attention")]
    assert kernel and tr.merged(kernel) == sorted(kernel)          # calls never overlap
    assert t.op_seconds(chip, ("exaq_paged_decode_attention",)) == pytest.approx(
        sum(e - s for s, e in kernel) * 1e-9)
    assert t.op_seconds(chip, ("exaq_paged_prefill_attention",)) > 0
    top = t.top_ops(chip)
    assert not any(n.startswith(tr.CONTAINERS) for n, _ in top)
    assert top[0][0].startswith("exaq_paged_decode_attention")
    # the device idled only inside the one step_chunk span, between programs
    assert {n for n, _ in t.idle_gaps(chip)} <= {"bench.step_chunk", "bench.read_tokens"}


def test_op_names_are_the_instruction_names():
    text = ("%fusion.136 = bf16[16,1,22016]{2,0,1} fusion(bf16[32,4096,22016]{2,1,0} "
            "%exaq_paged_decode_attention.11, s32[] %x), kind=kOutput")
    assert tr.op_name(text) == "fusion.136"
