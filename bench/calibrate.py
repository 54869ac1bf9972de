#!/usr/bin/env python3
"""Read the comparison's numbers over many seeds in one process, on the chip.

    python bench/calibrate.py --workload yi-6b.chat --seconds 20 --seeds 1 2 3 --control

For each seed: a whole run of the cell as ``run.py`` makes it (weights from
the seed, set-up, a window at the cell's own load, the check), then one
JSON line with the comparison's numbers (mean gap, widest gap, share off
the reference's best) of the program and, with ``--control``, of the
float8 control read at the same positions, ``correct`` and the end-to-end
metrics. The lower reading of a limit is the largest number the program
gives over a dozen seeds or more; the upper reading the smallest the
control gives. ``--rates`` runs the seeds at each of several fixed arrival
rates in place of the mix's own (the sweep that finds the knee of a
rate-limited cell), and reports how the backlog and the time to first
token grew over the window. The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import latency  # noqa: E402
import run  # noqa: E402


def backlog(r: run.RunData, t: float) -> int:
    """Requests due by ``t`` with no first token by ``t``."""
    return sum(1 for x in r.records if x.due_s <= t and (x.first_s is None or x.first_s > t))


def growth(r: run.RunData) -> dict:
    """Backlog at each quarter of the window, and the median time to first
    token of the requests due in its first and in its second half: in a
    sustained cell neither grows."""
    s = r.seconds
    halves = [[t for t, x in zip(latency.ttft_s(r), [x for x in r.window_records if x.error is None])
               if lo <= x.due_s < hi] for lo, hi in ((0, s / 2), (s / 2, s))]
    return {"backlog_q": [backlog(r, s * q / 4) for q in range(5)],
            "ttft_p50_halves_s": [latency.quantile(h, 0.5) for h in halves],
            "due": len(r.window_records)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", type=float, nargs="*", default=None,
                    help="arrival rates to sweep (per second, in the mix's own unit)")
    args = ap.parse_args()
    cell = run.find_cell(run.load_benchmark(), args.workload)
    jobs = [(s, r) for r in (args.rates or [None]) for s in args.seeds]
    for seed, rate in jobs:
        c = cell
        if rate is not None:
            mix = json.loads(json.dumps(cell.mix))
            mix["arrivals"]["rate_per_s"] = rate
            c = dataclasses.replace(cell, mix=mix)
        t0 = time.perf_counter()
        keep = {}
        result, log = run.run_cell(c, seed=seed, seconds=args.seconds, trace=False,
                                   control=args.control, t_start=t0, keep=keep)
        rd = keep["run"]
        v = keep["verdict"]
        line = {"seed": seed, "rate": rate, "correct": result["correct"],
                "program": v.program.numbers(),
                "control": v.control.numbers() if v.control is not None else None,
                "sampled": [v.sampled, v.sampled_tokens],
                "metrics": {k: x["value"] for k, x in result["metrics"].items()},
                "memory_peak_bytes": result["device"]["memory_peak_bytes"],
                **growth(rd), "wall_s": time.perf_counter() - t0}
        for x in log:
            print(x, file=sys.stderr)
        print(json.dumps(line), flush=True)
        del rd, keep, v
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
