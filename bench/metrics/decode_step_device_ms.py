"""decode_step_device_ms (device step, runtime/device_step.py): device time
of the decode programs, ``jit_paged_decode_chunk`` in the trace's
per-program line, over the decode steps the engine counted in the window
(``decode_steps``), averaged over the cell's chips (``program_trace.py``).
Unlike ``decode_step_ms`` it holds no prefill work still queued on the
device when the decode chunk was sent."""

import program_trace

PROGRAM = "jit_paged_decode_chunk"


def read(run):
    pt = program_trace.for_run(run)
    steps = run.delta("decode_steps")
    if pt is None or steps <= 0:
        return None
    t = sum(pt.program_seconds(c, PROGRAM) for c in run.chips) / len(run.chips)
    return 1e3 * t / steps if t > 0 else None
