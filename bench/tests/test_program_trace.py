"""The engine's own spans and per-program device time (``program_trace.py``),
on a 54 ms slice of a v5e trace of yi-6b.chat recorded with ``--trace 1``:
the end of one step's decode chunk, the device idle between steps, then the
next step's admission and first prefill chunks. Kept in testdata are the
chip's operations, the ``bench.*`` and ``engine.*`` host spans and the
chip's ``XLA Modules`` events, each overlapping the slice."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import program_trace  # noqa: E402
import run  # noqa: E402

SLICE = BENCH / "testdata" / "v5e_yi6b_program_trace.json"
DECODE, PREFILL = "jit_paged_decode_chunk", "jit_paged_prefill_chunk"
NEW_METRICS = ("queue_wait_ms", "step_idle_ms", "prefill_chunk_ms", "decode_step_device_ms")


@pytest.fixture(scope="module")
def pt():
    return program_trace.ProgramTrace.load(SLICE)


@pytest.fixture(scope="module")
def chip(pt):
    (chip,) = pt.base.device_ops
    return chip


def _bench_spans(pt, *names):
    return [(s, e) for n, s, e in pt.base.host_spans if n in names]


def test_programs_and_engine_spans_nest_inside_the_benchmarks_calls(pt, chip):
    steps = _bench_spans(pt, "bench.step_chunk")
    lo, hi = pt.window
    programs = [(n, s, e) for n, s, e in pt.programs[chip] if s < hi and e > lo]
    assert {DECODE, PREFILL} <= {n for n, _, _ in programs}
    for name, s, e in programs:
        assert any(a <= s and e <= b for a, b in steps), f"{name} outside bench.step_chunk"
    calls = _bench_spans(pt, "bench.step_chunk", "bench.submit")
    inside = [(n, s, e) for n, s, e, _ in pt.engine_spans if lo <= s < hi]
    assert {"engine.step", "engine.prefill", "engine.device_wait"} <= {n for n, _, _ in inside}
    for name, s, e in inside:
        assert any(a <= s and e <= b for a, b in calls), f"{name} outside the bench calls"


def test_idle_inside_engine_steps_is_within_the_idle_under_step_chunk(pt, chip):
    engine = pt.idle_inside_ns(chip, pt.spans("engine.step"))
    bench = pt.idle_inside_ns(chip, _bench_spans(pt, "bench.step_chunk"))
    assert 0 < engine <= bench
    by_span = pt.idle_by_engine_span(chip)
    assert sum(by_span.values()) == pytest.approx(sum(e - s for s, e in pt.base.gaps(chip)) * 1e-9)


def test_prefill_and_decode_programs_fit_in_the_busy_time(pt, chip):
    prefill, decode = pt.program_seconds(chip, PREFILL), pt.program_seconds(chip, DECODE)
    assert prefill > 0 and decode > 0
    assert prefill + decode <= pt.base.busy_ns(chip) * 1e-9


def test_program_events_never_overlap_on_the_chip(pt, chip):
    events = sorted((s, e) for _, s, e in pt.programs[chip])
    assert all(e0 <= s1 for (_, e0), (s1, _) in zip(events, events[1:]))


def test_module_names_drop_the_program_id():
    assert program_trace.module_name("jit_paged_decode_chunk(12)") == DECODE
    assert program_trace.module_name(DECODE) == DECODE


def _run_over(pt, chip, **stats):
    """What the readers see of a run whose trace is the slice."""
    return SimpleNamespace(trace=pt.base, chips=[chip], cell=SimpleNamespace(name="slice"),
                           delta=lambda k: stats.get(k, 0), _program_trace=pt)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_new_metric_reads_a_number(pt, chip, name):
    decode_chunks = len(pt.program_events(chip, DECODE))
    r = _run_over(pt, chip, decode_steps=8 * decode_chunks, admitted=2, queue_wait_s=3.0)
    value = run.metric_module(name).read(r)
    assert isinstance(value, float) and value > 0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metrics_are_silent_without_what_they_read(pt, chip, name):
    """A program without the counters, spans or program names (the parent
    of the change that added them) gives no number, and no error."""
    bare = program_trace.ProgramTrace(pt.base)
    assert run.metric_module(name).read(_run_over(bare, chip)) is None
