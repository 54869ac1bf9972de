"""The engines' own instrumentation: the admission counters (``admitted``,
``queue_wait_s``), the stable names of the jitted device programs, and the
``engine.*`` profiler spans (runtime/engine.py, runtime/engine_core.py,
runtime/device_step.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.runtime.device_step import PagedDeviceStep, SlotDeviceStep
from repro.runtime.engine import DataParallelEngine, Engine, EngineConfig, PagedEngine, Request


@pytest.fixture(scope="module")
def model():
    cfg = get_config("yi-6b").reduced(num_layers=1)
    return cfg, init_params(cfg, jax.random.PRNGKey(0), jnp.float32)


def _config(slots: int) -> EngineConfig:
    return EngineConfig(max_slots=slots, max_seq=64, block_size=8, prefill_chunk=8,
                        steps_per_sync=1)


def _submit(eng, vocab: int, lengths) -> list[int]:
    rng = np.random.default_rng(len(lengths))
    return [eng.submit(Request(rng.integers(0, vocab, n), 4)) for n in lengths]


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """One slot serves a lone request, then three at once, all under the
    profiler: the counters after each phase, the results, and the
    ``engine.*`` host spans (name, start, end, uid)."""
    from jax.profiler import ProfileData

    cfg, params = model
    eng = PagedEngine(cfg, params, _config(1))
    log_dir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    phases, served = [], {}
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        for lengths in ((6,), (12, 5, 9)):
            uids = _submit(eng, cfg.vocab_size, lengths)
            served.update(eng.run())
            phases.append((uids, dict(eng.stats)))
    (path,) = log_dir.rglob("*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("engine."):
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                      dict(e.stats).get("uid")))
    return eng, phases, served, spans


# ------------------------------------------------------------------ counters


def test_admitted_counts_every_served_request(traced):
    eng, phases, served, _ = traced
    assert len(served) == 4
    assert [st["admitted"] for _, st in phases] == [1, 4]
    assert not eng._queued_at  # every stamp left with its request


def test_queue_wait_grows_when_requests_outnumber_slots(traced):
    """The wait is host-clock time from entering the queue to taking a
    slot: a lone request takes the free slot at once, three sharing one
    slot wait through each other's prefill and decode."""
    _, ((_, first), (_, both)), _, _ = traced
    lone = first["queue_wait_s"]
    crowded = (both["queue_wait_s"] - lone) / 3
    assert lone >= 0.0
    assert crowded > lone


def test_slot_engine_counts_admissions(model):
    cfg, params = model
    eng = Engine(cfg, params, _config(1))
    _submit(eng, cfg.vocab_size, (7, 4))
    assert len(eng.run()) == 2
    assert eng.stats["admitted"] == 2 and eng.stats["queue_wait_s"] > 0.0
    assert not eng._queued_at


def test_data_parallel_stats_sum_the_counters(model):
    """Admission is host work: dispatching to the replicas and admitting
    there fills the counters without running the device."""
    cfg, params = model
    fleet = DataParallelEngine(cfg, params, _config(1), replicas=2)
    _submit(fleet, cfg.vocab_size, (5, 6, 7))
    fleet._dispatch()
    for e in fleet.engines:
        e._admit()
    per = fleet.per_replica_stats
    assert [s["admitted"] for s in per] == [1, 1]
    assert fleet.stats["admitted"] == 2
    assert fleet.stats["queue_wait_s"] == sum(s["queue_wait_s"] for s in per) > 0.0


def test_preempted_continuation_is_stamped_again(model):
    """A continuation that ``_preempt`` re-queues waits anew and counts as
    another admission."""
    cfg, params = model
    eng = PagedEngine(cfg, params, _config(1))
    (uid,) = _submit(eng, cfg.vocab_size, (5,))
    eng._admit()
    assert eng.stats["admitted"] == 1 and not eng._queued_at
    eng._preempt(0)
    assert uid in eng._queued_at
    eng._admit()
    assert eng.stats["admitted"] == 2 and eng.stats["preemptions"] == 1
    assert not eng._queued_at


# ------------------------------------------------------------ program names


@pytest.fixture(scope="module")
def lowered(model):
    """Every jitted device program, lowered on small shapes: name -> text."""
    cfg, params = model
    i32 = np.int32
    paged = PagedDeviceStep(cfg, params, num_blocks=8, block_size=8, max_seq=32,
                            cache_dtype="int4")
    pool = paged.init_pool()
    S, MB, C = 2, 4, 8
    key = jax.random.PRNGKey(0)
    row = np.zeros((C,), i32)
    slot_vecs = (np.zeros((S, 1), i32), np.zeros((S,), i32), np.ones((S,), bool),
                 np.ones((S,), i32), np.zeros((S,), np.float32), np.zeros((S,), i32),
                 np.ones((S,), np.float32), key)
    out = {
        "paged_decode_chunk": paged._jit_chunk.lower(
            paged.params, pool, np.zeros((S, MB), i32), *slot_vecs, steps=2, sampler="greedy"),
        "paged_prefill_chunk": paged._jit_prefill_chunk.lower(
            paged.params, pool, np.zeros((1, C), i32), np.zeros((MB,), i32), i32(0), i32(C),
            row, row),
        "paged_verify_chunk": paged._jit_verify_chunk.lower(
            paged.params, pool, np.zeros((1, 3), i32), np.zeros((MB,), i32), i32(0),
            row[:3], row[:3]),
        "copy_block": paged.copy_block.lower(pool, i32(1), i32(2)),
        "reset_scales": paged.reset_scales.lower(pool, row),
        "trim_sub_scales": paged._jit_trim_sub.lower(pool, i32(1), i32(1)),
        "sample_first": paged._jit_sample.lower(
            np.zeros((1, cfg.vocab_size), np.float32), np.zeros((1,), np.float32),
            np.zeros((1,), i32), np.ones((1,), np.float32), key),
    }
    slot = SlotDeviceStep(cfg, params, max_slots=S, max_seq=32)
    k, v = slot.init_cache()
    part = jnp.zeros((k.shape[0], 1, k.shape[2], 16, k.shape[4]), k.dtype)
    out["slot_decode_chunk"] = slot._jit_chunk.lower(
        slot.params, {"k": k, "v": v}, *slot_vecs, steps=2, sampler="greedy")
    out["slot_prefill"] = slot._jit_prefill.lower(slot.params, np.zeros((1, 16), i32),
                                                  np.array([5], i32))
    out["slot_insert"] = slot._jit_insert.lower(k, v, part, part, 0)
    return {name: low.as_text() for name, low in out.items()}


PROGRAMS = ["paged_decode_chunk", "paged_prefill_chunk", "paged_verify_chunk", "copy_block",
            "reset_scales", "trim_sub_scales", "sample_first", "slot_decode_chunk",
            "slot_prefill", "slot_insert"]


@pytest.mark.parametrize("name", PROGRAMS)
def test_program_module_name(lowered, name):
    """A trace lists each executed program by its module name, so every
    jitted program carries a name that says what it does."""
    assert f"module @jit_{name} " in lowered[name]


# ------------------------------------------------------------------- spans


def test_engine_spans_nest_inside_the_step(traced):
    _, _, _, spans = traced
    names = {n for n, *_ in spans}
    assert {"engine.submit", "engine.step", "engine.admit", "engine.prefill",
            "engine.sample_first", "engine.reserve", "engine.decode", "engine.device_wait",
            "engine.absorb"} <= names
    steps = [(s, e) for n, s, e, _ in spans if n == "engine.step"]
    assert len(steps) >= 4
    for name, s, e, _ in spans:
        if name not in ("engine.step", "engine.submit"):
            assert any(s0 <= s and e <= e0 for s0, e0 in steps), f"{name} outside engine.step"


def test_per_request_spans_carry_the_uid(traced):
    _, _, served, spans = traced
    per_request = [(n, uid) for n, _, _, uid in spans
                   if n in ("engine.prefill", "engine.sample_first")]
    assert all(uid is not None for _, uid in per_request)
    assert {uid for n, uid in per_request if n == "engine.sample_first"} == set(served)
    assert {uid for n, uid in per_request if n == "engine.prefill"} == set(served)
