"""What users of a served cell feel, from the driver's per-request times.

All times are host-clock seconds from the window's start, and only requests
due inside the window count. The window closes when the last step it
started has ended (``RunData.close_s``). A request's time to first token runs from when
it was due, not from when it was sent, so a stalled generator or a full
queue shows in it; a request with no first token when the window closes
counts at its elapsed time then.
"""

from __future__ import annotations

import numpy as np


def ttft_s(run) -> list[float]:
    out = []
    for r in run.window_records:
        if r.error is not None:
            continue
        t = r.first_s if r.first_s is not None and r.first_s <= run.close_s else run.close_s
        out.append(t - r.due_s)
    return out


def tpot_s(run) -> list[float]:
    """Per request with two or more tokens in the window: (last - first) /
    (tokens - 1), over the tokens seen by the window's close."""
    seen = at_close(run)
    out = []
    for r in run.window_records:
        n, last = seen.get(r.index, (0, None))
        if r.first_s is not None and r.first_s <= run.close_s and n >= 2:
            out.append((last - r.first_s) / (n - 1))
    return out


def at_close(run) -> dict[int, tuple[int, float]]:
    """Per request: the tokens it had at the window's close and when the
    last of them came."""
    seen: dict[int, tuple[int, float]] = {}
    for step in run.steps:
        if step.end_s > run.close_s:
            break
        for idx, before, after in step.progress:
            if after > before:
                seen[idx] = (after, step.end_s)
    return seen


def output_tokens(run) -> int:
    """Tokens emitted by steps that ended inside the window, whatever
    request they belong to (lead-in requests included)."""
    total = 0
    for step in run.window_steps():
        total += sum(after - before for _i, before, after in step.progress)
    return total


def quantile(xs, q: float) -> float | None:
    return float(np.quantile(np.asarray(xs, np.float64), q)) if len(xs) else None
