"""Reduce a profiler trace to the numbers the per-layer metrics read.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
a ``Trace``: the device operations of each chip (name, start, end, in ns)
and the benchmark's own host spans (the ``bench.*`` ``TraceAnnotation``s
that ``run.py`` puts around submit, step_chunk, the token read-back and the
window). Everything else here is plain arithmetic on those lists, tested on
a small recorded trace in ``testdata/``:

* ``Trace.busy_ns``: the union of a chip's operation intervals inside the
  window, so overlapping or nested operations count once;
* ``op_seconds``: device time of the operations whose name starts with
  one of the given prefixes (a kernel);
* ``top_ops``: device time by operation name, largest first, loops left
  out (their bodies are listed);
* ``idle_gaps``: the stretches inside the window in which a chip ran
  nothing, each named by the innermost benchmark span that covers most of
  it ("host" when none does).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# the line of a TPU device plane that holds one event per executed operation
# (loops and their bodies both appear; asynchronous copies have a line of
# their own, "Async XLA Ops", which is left out)
OPS_LINE = "XLA Ops"
# operations that only contain others; the breakdown lists what they contain
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """An XLA Ops event is named by its HLO instruction text; keep the
    instruction's own name ("%fusion.136 = bf16[...] fusion(...)" ->
    "fusion.136"). Operands named later in the text never match."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _clip(intervals, lo: int, hi: int):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def merged(intervals) -> list[tuple[int, int]]:
    """The union of [start, end) intervals, as disjoint sorted intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclass
class Trace:
    device_ops: dict[str, list[tuple[str, int, int]]] = field(default_factory=dict)
    host_spans: list[tuple[str, int, int]] = field(default_factory=list)

    @property
    def window(self) -> tuple[int, int]:
        spans = [(s, e) for n, s, e in self.host_spans if n == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
        return min(s for s, _ in spans), max(e for _, e in spans)

    def to_json(self) -> dict:
        return {"device_ops": self.device_ops, "host_spans": self.host_spans}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({k: [tuple(e) for e in v] for k, v in d["device_ops"].items()},
                   [tuple(e) for e in d["host_spans"]])

    def save(self, path: Path) -> None:
        Path(path).write_text(json.dumps(self.to_json()))

    @classmethod
    def load(cls, path: Path) -> "Trace":
        return cls.from_json(json.loads(Path(path).read_text()))

    def busy_ns(self, chip: str) -> int:
        lo, hi = self.window
        ops = ((s, e) for _, s, e in self.device_ops[chip])
        return sum(e - s for s, e in merged(_clip(ops, lo, hi)))

    def op_seconds(self, chip: str, prefixes) -> float:
        """Device seconds inside the window of the operations whose name
        starts with any of ``prefixes``."""
        lo, hi = self.window
        ops = [(s, e) for n, s, e in self.device_ops[chip] if n.startswith(tuple(prefixes))]
        return sum(e - s for s, e in merged(_clip(ops, lo, hi))) * 1e-9

    def top_ops(self, chip: str, n: int = 10) -> list[list]:
        """Device seconds by operation name, largest first, without the
        loops that contain other operations."""
        lo, hi = self.window
        total: dict[str, int] = {}
        for name, s, e in self.device_ops[chip]:
            if name.startswith(CONTAINERS):
                continue
            for cs, ce in _clip([(s, e)], lo, hi):
                total[name] = total.get(name, 0) + ce - cs
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9] for name, ns in ranked]

    def gaps(self, chip: str) -> list[tuple[int, int]]:
        """Stretches of the window in which the chip ran no operation."""
        lo, hi = self.window
        busy = merged(_clip(((s, e) for _, s, e in self.device_ops[chip]), lo, hi))
        out, t = [], lo
        for s, e in busy:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
        return out

    def _cover_name(self, s: int, e: int) -> str:
        """What the host was doing in [s, e): the benchmark span that covers
        most of it (the innermost, latest starting, of equal ones), or
        'host' where the stretch no span covers is longer."""
        best, best_cover, best_start = "host", 0, -1
        covered = []
        for name, hs, he in self.host_spans:
            if name == WINDOW_SPAN or he <= s or hs >= e:
                continue
            cs, ce = max(hs, s), min(he, e)
            covered.append((cs, ce))
            if ce - cs > best_cover or (ce - cs == best_cover and hs > best_start):
                best, best_cover, best_start = name, ce - cs, hs
        bare = (e - s) - sum(b - a for a, b in merged(covered))
        return "host" if bare > best_cover else best

    def idle_gaps(self, chip: str, n: int = 10) -> list[list]:
        """The ``n`` longest idle stretches, longest first, as [span name, s]."""
        longest = sorted(self.gaps(chip), key=lambda g: g[0] - g[1])[:n]
        return [[self._cover_name(s, e), (e - s) * 1e-9] for s, e in longest]

    def idle_by_span(self, chip: str) -> dict[str, float]:
        """Idle seconds of the window summed by the span that covers each gap."""
        out: dict[str, float] = {}
        for s, e in self.gaps(chip):
            name = self._cover_name(s, e)
            out[name] = out.get(name, 0.0) + (e - s) * 1e-9
        return out


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read_xplane(path: Path) -> Trace:
    """Device operations per TPU chip and the benchmark's host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(op_name(e.name), int(e.start_ns), int(e.start_ns + e.duration_ns))
                            for e in line.events]
            out.device_ops[plane.name] = sorted(ops, key=lambda o: o[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out.host_spans.append((e.name, int(e.start_ns),
                                               int(e.start_ns + e.duration_ns)))
    out.host_spans.sort(key=lambda s: s[1])
    return out
