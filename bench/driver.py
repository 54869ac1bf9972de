"""Open-loop driver: sends each request when it is due, whatever the engine
is doing, and watches the engine's serving surface.

The engine is driven as a server's pump drives it (``launch/serve.py``,
``AsyncFrontend``): ``submit`` what is due, ``step_chunk``, read each live
request's tokens with ``tokens_so_far`` and collect finished ones with
``take_finished``. Every call is wrapped in a ``TraceAnnotation`` named
``bench.*`` so that idle gaps in a device trace can be put down to what the
host was doing. Times are host-clock seconds from the window's start.

Per request the driver keeps when it was due, sent, gave its first token,
its last token, and finished; per ``step_chunk`` call, when it ran and how
many tokens each live request had before and after it. ``work.py`` turns
the latter into the work each step needed.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np
from jax.profiler import TraceAnnotation as span


@dataclass
class Record:
    index: int
    due_s: float
    prompt: np.ndarray
    max_new: int
    tag: str = ""
    uid: int | None = None
    sent_s: float | None = None
    first_s: float | None = None
    last_s: float | None = None
    done_s: float | None = None
    first_step: int | None = None
    n_tokens: int = 0
    tokens: list[int] | None = None       # final tokens, once finished
    finish_reason: str | None = None
    error: str | None = None


@dataclass
class Step:
    index: int
    start_s: float
    end_s: float
    # (record index, tokens before, tokens after) of every request live in the step
    progress: list[tuple[int, int, int]] = field(default_factory=list)


class Driver:
    def __init__(self, engine, records: list[Record], origin: float):
        self.engine = engine
        self.records = records                 # sorted by due time
        self.origin = origin                   # perf_counter() at the window's start
        self.clock: float | None = None        # the lead-in's clock, while it runs
        self.next = 0                          # first record not yet sent
        self.live: dict[int, Record] = {}      # uid -> record
        self.steps: list[Step] = []
        self.lateness: list[float] = []        # sent - due, per request sent

    def now(self) -> float:
        if self.clock is not None:
            return self.clock
        return time.perf_counter() - self.origin

    def _submit_due(self, now: float) -> None:
        from repro.runtime.engine_core import Request

        while self.next < len(self.records) and self.records[self.next].due_s <= now:
            rec = self.records[self.next]
            self.next += 1
            try:
                rec.uid = self.engine.submit(Request(rec.prompt, rec.max_new))
            except Exception as e:  # a refused request counts as failed
                rec.error = f"{type(e).__name__}: {e}"
                continue
            rec.sent_s = self.now()
            self.lateness.append(rec.sent_s - rec.due_s)
            self.live[rec.uid] = rec

    def _read_tokens(self, step: Step) -> None:
        t = step.end_s
        finished = self.engine.take_finished()
        for uid, rec in list(self.live.items()):
            before = rec.n_tokens
            if uid in finished:
                gen = finished[uid]
                rec.tokens = [int(x) for x in gen.tokens]
                rec.finish_reason = gen.finish_reason
                n = len(rec.tokens)
            else:
                n = len(self.engine.tokens_so_far(uid))
            if n > before:
                if before == 0:
                    rec.first_s, rec.first_step = t, step.index
                rec.last_s = t
                rec.n_tokens = n
            step.progress.append((rec.index, before, n))
            if uid in finished:
                rec.done_s = t
                del self.live[uid]

    def lead_in(self, start_s: float, step_s: float) -> None:
        """Replay the schedule from ``start_s`` to the window's start (0)
        against a clock that advances ``step_s`` per ``step_chunk`` call and
        jumps to the next due time when the engine is idle, not against the
        host's. Every request is then sent before the same step in every
        run, and every run opens the window on the same engine state (slots,
        queue, prefix cache); on the host's clock a request due near a step
        boundary went one step earlier or later from run to run, and the
        rest of the run followed. The window starts when this returns."""
        self.clock = start_s
        while True:
            self._submit_due(self.clock)
            if self.engine.has_work():
                if self.clock + step_s >= 0.0:
                    break
                step = Step(len(self.steps), self.clock, self.clock + step_s)
                self.engine.step_chunk()
                self._read_tokens(step)
                self.steps.append(step)
                self.clock = step.end_s
            elif self.next < len(self.records) and self.records[self.next].due_s < 0.0:
                self.clock = self.records[self.next].due_s
            else:
                break
        self.clock = None
        self.origin = time.perf_counter()

    def run_until(self, end_s: float) -> None:
        """Serve until the clock reads ``end_s`` (window seconds)."""
        while True:
            now = self.now()
            if now >= end_s:
                return
            with span("bench.submit"):
                self._submit_due(now)
            if self.engine.has_work():
                step = Step(len(self.steps), self.now(), 0.0)
                with span("bench.step_chunk"):
                    self.engine.step_chunk()
                step.end_s = self.now()
                with span("bench.read_tokens"):
                    self._read_tokens(step)
                self.steps.append(step)
            else:
                wake = self.records[self.next].due_s if self.next < len(self.records) else end_s
                with span("bench.wait_arrival"):
                    time.sleep(max(0.0, min(wake, end_s) - self.now()))

    def drain(self) -> None:
        """Serve what was sent until nothing is left (set-up only)."""
        while self.engine.has_work():
            step = Step(len(self.steps), self.now(), 0.0)
            self.engine.step_chunk()
            step.end_s = self.now()
            self._read_tokens(step)
            self.steps.append(step)
