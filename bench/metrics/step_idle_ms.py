"""step_idle_ms (engine host path, runtime/engine.py): device-idle time
inside the engine's ``engine.step`` spans (one per ``step_chunk``) over the
number of such spans in the window, averaged over the cell's chips: what
the host's own work inside a step (admission, planning, uploads, the
first-token read-back, absorbing a chunk) leaves the chip waiting
(``program_trace.py``)."""

import program_trace


def read(run):
    pt = program_trace.for_run(run)
    if pt is None:
        return None
    steps = pt.spans("engine.step")
    if not steps:
        return None
    idle = sum(pt.idle_inside_ns(c, steps) for c in run.chips) / len(run.chips)
    return 1e-6 * idle / len(steps)
