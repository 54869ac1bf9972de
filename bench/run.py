#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip JAX finds.

    python bench/run.py --workload yi-6b.chat --seed 7 --seconds 10 --trace 0

Everything a cell needs is found by name from ``BENCHMARK.json``: the
workload names a configuration (``bench/configs/<config>.json``: sizes,
arithmetic, deployment, the reference family and the comparison's limit)
and a traffic mix (``bench/traffic/<traffic>.json``, read by
``traffic/generator.py``). Each metric is a reader of its own,
``bench/metrics/<metric>.py``, and the reference of each model family is
``bench/reference/<family>.py``.

A run:

1. set-up (``setup_s``): draws the weights from ``--seed`` on the device,
   builds the program's ``PagedEngine`` from the configuration's deployment,
   warms every program the window will call (each decode chunk length, the
   prefill chunk, first-token sampling, the block copy), prefills the mix's
   shared documents, freezes set-up's objects out of the garbage collector's
   reach and replays the mix's lead-in on a clock of engine steps
   (``driver.Driver.lead_in``), so every run opens the window on the same
   warm prefix cache and steady batch;
2. the window: ``--seconds`` of open-loop traffic (``driver.py``); with
   ``--trace 1`` the profiler records it and the per-layer metrics are
   reported instead of the end-to-end ones;
3. the check: the device's peak memory is read and the engine freed; then a
   sample of the requests finished in the window, drawn from the seed and
   holding the longest of them, is run through the float32 reference, and
   the served tokens' logit gaps from the reference's best are held to the
   configuration's limits (``correct.py``).

The last line of standard output is one JSON object. Without a TPU, or with
fewer chips than the cell asks for, the command exits non-zero and prints
no result.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path.insert(0, str(BENCH))

import correct as correct_mod  # noqa: E402
import work  # noqa: E402
from driver import Driver, Record, span  # noqa: E402
from traffic.generator import generate  # noqa: E402


class NoChip(RuntimeError):
    pass


# ----------------------------------------------------------------- lookup

def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_module(path: Path):
    """Import a reader or reference file by its path (names may hold '-' or
    '.', so they are not importable by name)."""
    name = f"bench_{path.parent.name}_{path.stem}".replace("-", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def find_cell(bench: dict, workload: str, bench_dir: Path = BENCH) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[workload]
    conf = json.loads((bench_dir / "configs" / f"{w['config']}.json").read_text())
    mix = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(metrics):
        return [m for m in metrics if workload in m.get("workloads", [workload])]

    return Cell(workload, w["chips"], conf, mix, mine(bench["end_to_end"]), mine(bench["per_layer"]))


def metric_module(name: str, bench_dir: Path = BENCH):
    """``bench/metrics/<name>.py``: its ``read(run)`` gives the value, or
    None where the run holds nothing to read."""
    return load_module(bench_dir / "metrics" / f"{name}.py")


tr = load_module(BENCH / "trace.py")  # by path: the standard library has a ``trace``


# ------------------------------------------------------------------ set-up

def use_compile_cache() -> str:
    """The program's persistent compilation cache (its fixed ``.jax_cache/``
    in the checkout, or ``JAX_COMPILATION_CACHE_DIR``), keeping every
    program however quickly it compiled, so that only a checkout's first
    run compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return enable_compile_cache()


def program_config(conf: dict):
    """The program's model configuration for this file: the registry entry
    with the published RoPE base and norm epsilon and the stated softmax
    (and any registry field the file's ``program`` key sets, as the tests'
    tiny model does). Every width must agree with the file."""
    from repro.configs import get_config
    from repro.core import clipping

    q = conf["quant"]
    cfg = dataclasses.replace(get_config(conf["registry"]), rope_theta=float(conf["rope_theta"]),
                              norm_eps=float(conf["rms_norm_eps"]), **conf.get("program", {}))
    cfg = cfg.with_quant(softmax_impl=q["softmax"], bits=q["bits"], sigma_default=q["sigma"],
                         clip_rule="paper")
    want = {"num_layers": "num_hidden_layers", "d_model": "hidden_size",
            "num_heads": "num_attention_heads", "num_kv_heads": "num_key_value_heads",
            "d_ff": "intermediate_size", "vocab_size": "vocab_size", "resolved_head_dim": "head_dim"}
    for attr, key in want.items():
        if getattr(cfg, attr) != conf[key]:
            raise ValueError(f"program's {conf['registry']} has {attr}={getattr(cfg, attr)}, "
                             f"the configuration states {key}={conf[key]}")
    slope, intercept = clipping.PAPER_CLIP_COEFFS[q["bits"]]
    if [slope, intercept] != list(q["clip_rule"]):
        raise ValueError(f"program's EXAQ clip rule {slope, intercept} differs from the "
                         f"configuration's {q['clip_rule']}")
    return cfg


def check_layout(cfg, weights) -> None:
    """The benchmark's weights must have the shapes the program takes."""
    import jax
    import jax.numpy as jnp

    from repro.models import init_params

    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), weights)
    if jax.tree.structure(want) != jax.tree.structure(got) or jax.tree.leaves(want) != jax.tree.leaves(got):
        raise ValueError(f"weight layout differs from the program's: {want} vs {got}")


def engine_config(conf: dict, seed: int):
    from repro.runtime.engine_core import EngineConfig

    s = conf["serving"]
    base = EngineConfig(max_slots=s["max_slots"], max_seq=s["max_seq"], kv_dtype=s["kv_dtype"],
                        fused=conf["quant"]["fused"], seed=seed & 0x7FFFFFFF)
    # the pool holds kv_pool_tokens at the program's own block size, plus
    # the reserved null block
    return dataclasses.replace(base, num_blocks=-(-s["kv_pool_tokens"] // base.block_size) + 1)


def warm_up(engine, vocab: int, rng) -> None:
    """Compile, or load from the cache, every program the window calls:
    decode chunks of each length 1..steps_per_sync, the prefill chunk,
    first-token sampling, and the copy-on-write block copy (a prompt sent
    twice shares its last block, which the second request must copy)."""
    import numpy as np

    from repro.runtime.engine_core import Request

    prompt = rng.integers(0, vocab, engine.config.prefill_chunk + 3).astype(np.int32)
    for steps in range(1, engine.config.steps_per_sync + 1):
        engine.submit(Request(prompt, steps + 1))
        engine.run()
    engine.take_finished()


# ------------------------------------------------------------------- a run

_COMPILES = {"/jax/core/compile/jaxpr_trace_duration": 0,
             "/jax/core/compile/backend_compile_duration": 0}
_LISTENING = []


def _count_compiles() -> None:
    """Count JAX's traces and backend compiles, so a run can show that
    none fell inside its window."""
    if _LISTENING:
        return
    import jax

    def on(name, _secs, **_kw):
        if name in _COMPILES:
            _COMPILES[name] += 1

    jax.monitoring.register_event_duration_secs_listener(on)
    _LISTENING.append(on)


@dataclasses.dataclass
class RunData:
    """What the metric readers read."""

    cell: Cell
    seconds: float
    setup_s: float
    records: list[Record]
    steps: list
    lateness: list[float]
    stats0: dict
    stats1: dict
    max_slots: int
    prefill_chunk: int
    shape: work.Shape
    device_kind: str
    trace: object = None         # trace.Trace of the window, --trace 1 only
    chips: list[str] = dataclasses.field(default_factory=list)
    needed_cached: dict = dataclasses.field(default_factory=dict)  # record index -> tokens

    @property
    def window_records(self) -> list[Record]:
        """Requests due inside the window."""
        return [r for r in self.records if 0.0 <= r.due_s < self.seconds]

    @property
    def attempted_records(self) -> list[Record]:
        """Requests the window had to serve: those due inside it and those
        sent before it and not finished when it opened (a backlog)."""
        return [r for r in self.records
                if r.due_s < self.seconds and (r.done_s is None or r.done_s >= 0.0)]

    def window_steps(self):
        """The ``step_chunk`` calls the window started."""
        return [s for s in self.steps if 0.0 <= s.start_s < self.seconds]

    @property
    def close_s(self) -> float:
        """When the window's last step ended: the window runs until then,
        and its rates are taken over all of it."""
        return max([self.seconds] + [s.end_s for s in self.window_steps()])

    @property
    def peak(self) -> dict:
        """The chip's published peaks (an unknown device is an error)."""
        return work.peaks(self.device_kind)

    def delta(self, key: str) -> float:
        return self.stats1.get(key, 0) - self.stats0.get(key, 0)


def needed_cached(records: list[Record], earlier) -> dict[int, int]:
    """Per record, the prompt tokens a cache could serve: the longest common
    prefix with any prompt sent before it (at most all but the last token,
    whose logits sampling needs). Content alone decides it, not the
    program's cache."""
    import numpy as np

    out: dict[int, int] = {}
    seen = [np.asarray(p) for p in earlier]
    for rec in sorted(records, key=lambda r: (r.sent_s if r.sent_s is not None else 1e18)):
        if rec.sent_s is None:
            continue
        p = rec.prompt
        best = 0
        for q in seen:
            n = min(len(p), len(q))
            if n <= best or q[0] != p[0]:
                continue
            neq = np.nonzero(p[:n] != q[:n])[0]
            best = max(best, int(neq[0]) if len(neq) else n)
        out[rec.index] = min(best, len(p) - 1)
        seen.append(p)
    return out


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool, control: bool = False,
             trace_dir: Path | None = None, t_start: float | None = None,
             need_tpu: bool = True, keep: dict | None = None):
    """One run; returns (result dict, stderr lines). Tests pass
    ``need_tpu=False`` to drive a tiny configuration on the CPU; a ``keep``
    dict receives the run's ``RunData`` (the calibration tool reads it)."""
    import jax
    import numpy as np

    devices = jax.devices()
    if need_tpu and devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} chips; JAX found {len(devices)}")
    t_start = T_START if t_start is None else t_start
    log: list[str] = []
    sys.path.insert(0, str(ROOT / "src"))
    cache_dir = use_compile_cache()
    _count_compiles()
    from repro.runtime.engine import PagedEngine

    conf, mix = cell.conf, cell.mix
    ref = load_module(BENCH / "reference" / f"{conf['family']}.py")
    cfg = program_config(conf)
    weights = jax.block_until_ready(ref.make_weights(conf, seed))
    check_layout(cfg, weights)
    engine = PagedEngine(cfg, weights, engine_config(conf, seed))
    rng = np.random.default_rng([seed, 0x5E7])
    warm_up(engine, conf["vocab_size"], rng)
    t_warm = time.perf_counter()

    sched = generate(mix, seed=seed, seconds=seconds, vocab=conf["vocab_size"])
    records = [Record(i, r.due_s, r.prompt, r.max_new, r.tag) for i, r in enumerate(sched.requests)]
    setup_prompts = [Record(-1 - i, float("-inf"), p, 1, f"setup{i}")
                     for i, p in enumerate(sched.setup_prompts)]
    # shared documents are prefilled before anything else is sent
    drv = Driver(engine, setup_prompts, time.perf_counter())
    drv._submit_due(float("inf"))
    drv.drain()
    # set-up's objects (traced programs above all) leave the collector's reach, so
    # that a full collection in the window walks only what the window made
    gc.collect()
    gc.freeze()
    # the lead-in replays the first lead_in_s of the schedule, then the window opens
    drv = Driver(engine, records, time.perf_counter())
    drv.lead_in(-sched.lead_in_s, float(mix.get("lead_in_step_s", 1.0)))
    setup_s = time.perf_counter() - t_start
    stats0 = dict(engine.stats)
    compiles0 = dict(_COMPILES)

    if trace:
        trace_dir = trace_dir or ROOT / "bench_out" / f"trace_{cell.name}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    pauses = _Pauses()
    gc.callbacks.append(pauses)
    with span("bench.window") if trace else contextlib.nullcontext():
        drv.run_until(float(seconds))
    gc.callbacks.remove(pauses)
    gc.unfreeze()
    if trace:
        jax.profiler.stop_trace()
    t_close = time.perf_counter()
    compiles = {k.rsplit("/", 1)[1]: _COMPILES[k] - compiles0[k] for k in _COMPILES}
    stats1 = dict(engine.stats)
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[: cell.chips])
    dev = devices[0]

    shape = work.Shape.from_config(conf)
    run = RunData(cell, float(seconds), setup_s, records, drv.steps, drv.lateness, stats0, stats1,
                  engine.config.max_slots, engine.config.prefill_chunk, shape,
                  dev.device_kind)
    run.needed_cached = needed_cached(records, [r.prompt for r in setup_prompts])
    if trace:
        run.trace = tr.read_xplane(tr.find_xplane(trace_dir))
        run.chips = sorted(run.trace.device_ops)[: cell.chips]

    # the program's state goes before the reference runs; the weights stay
    del engine, drv
    gc.collect()

    metrics_spec = cell.per_layer if trace else cell.end_to_end
    metrics, bounds = {}, []
    for m in metrics_spec:
        mod = metric_module(m["name"])
        v = mod.read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            if hasattr(mod, "bound"):
                bounds.append(f"{m['name']}: {mod.bound(run)} bound")

    verdict = correct_mod.check(run, ref, weights, conf, seed=seed, control=control)
    t_end = time.perf_counter()

    win = run.window_records
    sent = [r for r in win if r.uid is not None]
    attempted = run.attempted_records
    log += [
        f"device: {dev.platform} {dev.device_kind} x{len(devices)}; compile cache {cache_dir}",
        f"set-up {setup_s:.3f} s (warm-up done at {t_warm - t_start:.3f} s); window "
        f"{seconds} s; check {t_end - t_close:.3f} s",
        f"requests due in window: {len(win)}, sent {len(sent)}, first token "
        f"{sum(r.first_s is not None for r in sent)}, finished "
        f"{sum(r.done_s is not None for r in sent)}; attempted in all (with those carried "
        f"in): {len(attempted)}, failed {sum(r.error is not None for r in attempted)}",
        f"generator lateness (sent - due, s): median {_q(run.lateness, 0.5)!r}, "
        f"p99 {_q(run.lateness, 0.99)!r}, max {max(run.lateness, default=0.0)!r}",
        f"engine over the window: {int(run.delta('decode_steps'))} decode steps, "
        f"{int(run.delta('prefill_chunks'))} prefill chunks, {int(run.delta('tokens_out'))} "
        f"tokens out, {int(run.delta('preemptions'))} preemptions, peak device memory {mem_peak}",
        f"inside the window: {compiles}",
        f"longest step_chunk call in the window: "
        f"{max((s.end_s - s.start_s for s in run.window_steps()), default=0.0)!r} s; "
        f"garbage collections {len(pauses.s)}, {sum(pauses.s)!r} s in all, longest "
        f"{max(pauses.s, default=0.0)!r} s",
    ]
    log += correct_mod.describe(run)
    log += bounds

    result = {
        "correct": verdict.ok,
        "attempted": len(attempted),
        "failed": sum(r.error is not None for r in attempted),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
                   "memory_peak_bytes": int(mem_peak)},
    }
    if trace and run.chips:
        chips = run.chips
        result["device"]["busy_s"] = sum(run.trace.busy_ns(c) for c in chips) / len(chips) * 1e-9
        lo, hi = run.trace.window
        result["device"]["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = {"device_ops": run.trace.top_ops(chips[0]),
                               "idle_gaps": run.trace.idle_gaps(chips[0])}
        log.append("idle by host span (s): " + json.dumps(run.trace.idle_by_span(chips[0])))
    log += verdict.lines()
    result["checks"] = verdict.checks()
    if keep is not None:
        keep["run"], keep["verdict"] = run, verdict
    return result, log


class _Pauses:
    """The garbage collector's pauses, as a ``gc.callbacks`` entry."""

    def __init__(self):
        self.s: list[float] = []
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.s.append(time.perf_counter() - self._t0)


def _q(xs, q: float) -> float:
    import numpy as np

    return float(np.quantile(np.asarray(xs, float), q)) if len(xs) else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = find_cell(load_benchmark(), args.workload)
    try:
        result, log = run_cell(cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for line in log:
        print(line, file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
