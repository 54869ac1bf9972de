"""The traffic generator: seeded, the same work for every seed, data-driven."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from traffic.generator import generate  # noqa: E402

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
VOCAB = 1000
SEEDS = (3, 2**31 + 17, 2**40 + 5)


def load_mix(path):
    return json.loads(Path(path).read_text())


def _sched(mix, seed, seconds=30):
    return generate(mix, seed=seed, seconds=seconds, vocab=VOCAB)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule_and_prompts(name):
    mix = load_mix(BENCH / "traffic" / f"{name}.json")
    a, b = _sched(mix, SEEDS[1]), _sched(mix, SEEDS[1])
    assert len(a.requests) == len(b.requests) > 0
    for x, y in zip(a.requests, b.requests):
        assert x.due_s == y.due_s and x.max_new == y.max_new and x.tag == y.tag
        assert np.array_equal(x.prompt, y.prompt)
    assert all(np.array_equal(p, q) for p, q in zip(a.setup_prompts, b.setup_prompts))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_differ_in_tokens_not_in_work(name):
    mix = load_mix(BENCH / "traffic" / f"{name}.json")
    runs = [_sched(mix, s) for s in SEEDS]
    for a, b in zip(runs, runs[1:]):
        assert [(r.due_s, len(r.prompt), r.max_new) for r in a.requests] == \
               [(r.due_s, len(r.prompt), r.max_new) for r in b.requests]
        assert [len(p) for p in a.setup_prompts] == [len(p) for p in b.setup_prompts]
        assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a.requests, b.requests))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_and_times_respect_the_mix(name):
    mix = load_mix(BENCH / "traffic" / f"{name}.json")
    s = _sched(mix, SEEDS[0])
    lead = mix.get("lead_in_s", 0.0)
    for r in s.requests:
        assert -lead <= r.due_s < 30
        assert len(r.prompt) + r.max_new <= mix["max_context"]
        assert mix["new_tokens"]["lo"] <= r.max_new <= mix["new_tokens"]["hi"]
        assert r.prompt.dtype == np.int32 and 0 <= r.prompt.min() and r.prompt.max() < VOCAB


def test_poisson_rate_and_backlog():
    base = {"message_tokens": {"median": 20, "sigma": 0.5, "lo": 4, "hi": 60},
            "new_tokens": {"median": 8, "sigma": 0.5, "lo": 2, "hi": 20}, "max_context": 200}
    mix = dict(base, arrivals={"process": "poisson", "rate_per_s": 4.0, "of": "requests"},
               lead_in_s=5)
    s = _sched(mix, 1, seconds=20)
    assert len(s.requests) == 100                        # 4/s over 25 s
    assert sum(r.due_s < 0 for r in s.requests) in range(15, 26)
    backlog = dict(base, arrivals={"process": "backlog", "requests": 12}, lead_in_s=2)
    s = _sched(backlog, 1)
    assert len(s.requests) == 12 and all(r.due_s == -2 for r in s.requests)


def test_pareto_bursts_come_in_clusters():
    mix = {"arrivals": {"process": "pareto_burst", "bursts_per_s": 1.0, "burst_max": 4, "tail": 1.5},
           "message_tokens": {"median": 20, "sigma": 0.5, "lo": 4, "hi": 60},
           "new_tokens": {"median": 8, "sigma": 0.5, "lo": 2, "hi": 20}, "max_context": 200}
    s = _sched(mix, 9, seconds=60)
    times = [r.due_s for r in s.requests]
    assert len(set(times)) < len(times)                  # several due at once
    assert len(s.requests) > len(set(times))


def test_sessions_share_their_history():
    mix = load_mix(BENCH / "traffic" / "chat.json")
    s = _sched(mix, 5, seconds=60)
    by_unit = {}
    for r in s.requests:
        unit, turn = r.tag.split("/")[1], int(r.tag.split("/t")[1])
        by_unit.setdefault(unit, []).append((turn, r))
    multi = [sorted(v, key=lambda x: x[0]) for v in by_unit.values() if len(v) > 1]
    assert multi
    for turns in multi:
        for (_, a), (_, b) in zip(turns, turns[1:]):
            assert b.due_s > a.due_s
            # turn j+1 starts with turn j's prompt, then its synthetic answer
            assert np.array_equal(b.prompt[: len(a.prompt)], a.prompt)
            assert len(b.prompt) > len(a.prompt) + a.max_new


def test_mix_files_are_plain_json():
    for p in (BENCH / "traffic").glob("*.json"):
        assert isinstance(json.loads(p.read_text())["arrivals"], dict)


def test_documents_are_prefilled_in_setup_and_asked_about():
    mix = {"arrivals": {"process": "backlog", "requests": 10},
           "documents": {"count": 3, "tokens_lo": 40, "tokens_hi": 80},
           "message_tokens": {"median": 8, "sigma": 0.5, "lo": 4, "hi": 16},
           "new_tokens": {"median": 6, "sigma": 0.5, "lo": 2, "hi": 10}, "max_context": 120}
    s = _sched(mix, 4)
    assert [len(d) for d in s.setup_prompts] == [40, 60, 80]
    for r in s.requests:
        doc = s.setup_prompts[int(r.tag.split("/")[0][3:])]
        assert np.array_equal(r.prompt[: len(doc)], doc) and len(r.prompt) > len(doc)
    assert {r.tag.split("/")[0] for r in s.requests} == {"doc0", "doc1", "doc2"}
