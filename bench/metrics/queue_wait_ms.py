"""queue_wait_ms (host scheduler, runtime/engine_core.py): the mean time a
request waited in the engine's queue before it took a slot, from the
engine's counters ``queue_wait_s`` (host-clock seconds from entering the
queue, or re-entering it after a preemption, to admission) and
``admitted`` over the window."""


def read(run):
    admitted = run.delta("admitted")
    return None if admitted <= 0 else 1e3 * run.delta("queue_wait_s") / admitted
