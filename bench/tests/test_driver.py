"""The lead-in replays its arrivals on a clock of engine steps, so every run
reaches the window in the same state, however long its steps take."""

import random
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from driver import Driver, Record  # noqa: E402


class FakeEngine:
    """Serves each request for three steps; step times vary by ``jitter``."""

    def __init__(self, jitter: random.Random):
        self.jitter = jitter
        self.steps = 0
        self.left: dict[int, int] = {}
        self.done: dict[int, object] = {}
        self.sent_at_step: dict[int, int] = {}

    def submit(self, req) -> int:
        uid = len(self.sent_at_step)
        self.sent_at_step[uid] = self.steps
        self.left[uid] = 3
        return uid

    def has_work(self) -> bool:
        return bool(self.left)

    def step_chunk(self) -> None:
        time.sleep(self.jitter.uniform(0.0, 0.004))
        self.steps += 1
        for uid in list(self.left):
            self.left[uid] -= 1
            if not self.left[uid]:
                del self.left[uid]
                self.done[uid] = type("Gen", (), {"tokens": [1, 2, 3], "finish_reason": "length"})

    def take_finished(self) -> dict:
        out, self.done = self.done, {}
        return out

    def tokens_so_far(self, uid) -> list:
        return [1] * (3 - self.left[uid])


def _records() -> list[Record]:
    due = [-9.95, -9.9, -7.01, -6.99, -4.5, -4.0, -0.5, -0.01, 0.3, 1.2]
    return [Record(i, d, np.arange(4, dtype=np.int32), 3) for i, d in enumerate(due)]


def _lead_in(jitter_seed: int):
    eng = FakeEngine(random.Random(jitter_seed))
    drv = Driver(eng, _records(), time.perf_counter())
    drv.lead_in(-10.0, 1.0)
    return eng, drv


def test_lead_in_sends_each_request_before_the_same_step_in_every_run():
    runs = [_lead_in(s) for s in (1, 2, 3)]
    sent = [eng.sent_at_step for eng, _ in runs]
    assert sent[0] == sent[1] == sent[2]
    eng, drv = runs[0]
    # a request goes before the first step that starts at or after its due
    # time; idle at -10 and at -0.95, the clock jumped to the next due time
    assert sent[0] == {0: 0, 1: 1, 2: 3, 3: 3, 4: 6, 5: 6, 6: 9}
    # the lead-in stops before a step that would end at or after 0; what is
    # due in the window, or just before it, is left for the window to send
    assert all(s.end_s < 0.0 for s in drv.steps) and len(drv.steps) == 9
    assert drv.next == 7 and drv.clock is None
    assert 0.0 <= drv.now() < 1.0
