"""prefill_chunk_ms (device step, runtime/device_step.py): device time of
one chunked-prefill program, ``jit_paged_prefill_chunk`` in the trace's
per-program line, averaged over its executions in the window and over the
cell's chips (``program_trace.py``)."""

import program_trace

PROGRAM = "jit_paged_prefill_chunk"


def read(run):
    pt = program_trace.for_run(run)
    if pt is None:
        return None
    per_chip = []
    for c in run.chips:
        events = pt.program_events(c, PROGRAM)
        if events:
            per_chip.append(1e3 * pt.program_seconds(c, PROGRAM) / len(events))
    return sum(per_chip) / len(per_chip) if per_chip else None
