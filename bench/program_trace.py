"""Per-program device time and the engine's own host spans, from the
profiler trace of a ``--trace 1`` run.

``trace.py`` reduces the device's operations (the ``XLA Ops`` line) and
the benchmark's ``bench.*`` spans. The engine names what it runs as well:
every jitted program compiles as ``jit_<name>`` (``jit_paged_decode_chunk``,
``jit_paged_prefill_chunk``, ``jit_copy_block``, ``jit_reset_scales``,
``jit_sample_first``, ...), and each phase of a step runs under an
``engine.*`` ``TraceAnnotation`` (``engine.step`` around each
``step_chunk``, ``engine.admit``, ``engine.prefill`` and
``engine.sample_first`` with the request's ``uid``, ``engine.decode``,
``engine.device_wait``, ``engine.absorb``, ...). This reader keeps, inside
the run's window:

* the ``engine.*`` host spans, with their ``uid`` where they carry one;
* one event per executed program from each TPU plane's ``XLA Modules``
  line, named by its module;

and takes the device's idle gaps from ``trace.py``'s own arithmetic on the
run's ``Trace`` (``Trace.gaps``, ``Trace.idle_by_span``).

It reads the ``.xplane.pb`` that ``run.py`` writes under
``bench_out/trace_<cell>/`` (always there under ``main``), and gives None
where there is none, or where the file is not the run's own (its
``bench.window`` span differs from the run's). A later benchmark PR should
hand the trace path on ``RunData`` and fold this reader into ``trace.py``.
"""

from __future__ import annotations

import bisect
import importlib.util
import json
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ENGINE_PREFIX = "engine."
OUTSIDE = "outside engine spans"
# the line of a TPU device plane that holds one event per executed program
MODULES_LINE = "XLA Modules"


def _load_trace():
    """``trace.py``, by path (the standard library has a ``trace``), under the
    name ``run.load_module`` gives it, so that both share one module."""
    name = "bench_bench_trace"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, BENCH / "trace.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


tr = _load_trace()


def module_name(event_name: str) -> str:
    """An XLA Modules event is named by its module, with the program's id
    after it in parentheses ("jit_paged_decode_chunk(12)" ->
    "jit_paged_decode_chunk")."""
    return re.sub(r"\(\d+\)$", "", event_name)


@dataclass
class ProgramTrace:
    base: "tr.Trace"  # the run's device operations and bench.* spans
    # (name, start, end, uid or None)
    engine_spans: list[tuple[str, int, int, int | None]] = field(default_factory=list)
    # chip -> (module name, start, end), one per executed program
    programs: dict[str, list[tuple[str, int, int]]] = field(default_factory=dict)

    @property
    def window(self) -> tuple[int, int]:
        return self.base.window

    def spans(self, name: str) -> list[tuple[int, int]]:
        """The ``name`` spans that start inside the window."""
        lo, hi = self.window
        return [(s, e) for n, s, e, _ in self.engine_spans if n == name and lo <= s < hi]

    def program_events(self, chip: str, module: str) -> list[tuple[int, int]]:
        """The executions of ``module`` on ``chip``, clipped to the window."""
        lo, hi = self.window
        return list(tr._clip(((s, e) for n, s, e in self.programs.get(chip, []) if n == module),
                             lo, hi))

    def program_seconds(self, chip: str, module: str) -> float:
        """Device seconds of ``module`` in the window: the union of the
        operations each execution ran, so the short stalls between them,
        which the chip's idle time already counts, are left out."""
        ops = self.base.device_ops.get(chip, [])
        starts = [s for _, s, _ in ops]
        total = 0
        for s, e in self.program_events(chip, module):
            inside = ops[bisect.bisect_left(starts, s):bisect.bisect_left(starts, e)]
            total += sum(b - a for a, b in tr.merged(tr._clip(((os, oe) for _, os, oe in inside),
                                                              s, e)))
        return total * 1e-9

    def idle_inside_ns(self, chip: str, spans) -> int:
        """Device-idle nanoseconds of the window that lie inside ``spans``
        ((start, end) pairs)."""
        inside, total, j = tr.merged(spans), 0, 0
        for gs, ge in self.base.gaps(chip):  # both sorted and disjoint
            while j < len(inside) and inside[j][1] <= gs:
                j += 1
            k = j
            while k < len(inside) and inside[k][0] < ge:
                total += min(inside[k][1], ge) - max(inside[k][0], gs)
                k += 1
        return total

    def idle_by_engine_span(self, chip: str) -> dict[str, float]:
        """Idle seconds of the window by the innermost ``engine.*`` span at
        each instant of each gap (the latest started of those open then),
        and "outside engine spans" where none is open."""
        lo, hi = self.window
        bounds = []
        for i, (_, s, e, _) in enumerate(self.engine_spans):
            if s < hi and e > lo:
                bounds += [(max(s, lo), 1, i), (min(e, hi), 0, i)]
        # stretches of the window between span boundaries, each named by the
        # innermost span open over it; at one instant spans close first
        stretches, open_spans, t = [], [], lo
        for when, opens, i in sorted(bounds) + [(hi, 0, None)]:
            if when > t:
                name = self.engine_spans[open_spans[-1]][0] if open_spans else OUTSIDE
                stretches.append((t, when, name))
                t = when
            if i is not None and opens:
                open_spans.append(i)
            elif i is not None:
                open_spans.remove(i)
        out: dict[str, float] = {}
        j = 0
        for gs, ge in self.base.gaps(chip):
            while stretches[j][1] <= gs:
                j += 1
            k = j
            while k < len(stretches) and stretches[k][0] < ge:
                s, e, name = stretches[k]
                out[name] = out.get(name, 0.0) + (min(e, ge) - max(s, gs)) * 1e-9
                k += 1
        return out

    @classmethod
    def load(cls, path: Path) -> "ProgramTrace":
        d = json.loads(Path(path).read_text())
        return cls(tr.Trace.from_json(d["base"]), [tuple(s) for s in d["engine_spans"]],
                   {k: [tuple(e) for e in v] for k, v in d["programs"].items()})


def read_xplane(path: Path, base: "tr.Trace") -> ProgramTrace:
    """The engine spans and per-program events of ``path``, beside ``base``
    (the same file as ``trace.read_xplane`` reduced it). Raises ValueError
    where the file's window is not ``base``'s."""
    from jax.profiler import ProfileData

    out = ProgramTrace(base)
    window = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    out.programs[plane.name] = sorted(
                        ((module_name(e.name), int(e.start_ns), int(e.start_ns + e.duration_ns))
                         for e in line.events), key=lambda p: p[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    s, end = int(e.start_ns), int(e.start_ns + e.duration_ns)
                    if e.name.startswith(ENGINE_PREFIX):
                        uid = dict(e.stats).get("uid")
                        out.engine_spans.append((e.name, s, end, None if uid is None else int(uid)))
                    elif e.name == tr.WINDOW_SPAN:
                        window.append((s, end))
    if not window or (min(s for s, _ in window), max(e for _, e in window)) != base.window:
        raise ValueError(f"{path} is not the trace of this window")
    out.engine_spans.sort(key=lambda s: s[1])
    return out


def trace_dir(cell: str) -> Path:
    """Where ``run.py`` writes the trace of a ``--trace 1`` run."""
    return BENCH.parent / "bench_out" / f"trace_{cell}"


def for_run(run) -> ProgramTrace | None:
    """The run's ``ProgramTrace``, read once and kept on the run; None for an
    untraced run or where the run's trace file is not found."""
    if run.trace is None or not run.chips:
        return None
    cached = vars(run).get("_program_trace", False)
    if cached is False:
        try:
            cached = read_xplane(tr.find_xplane(trace_dir(run.cell.name)), run.trace)
        except (FileNotFoundError, ValueError):
            cached = None
        vars(run)["_program_trace"] = cached
    return cached
