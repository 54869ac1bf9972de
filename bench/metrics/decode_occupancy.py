"""decode_occupancy (host scheduler, runtime/engine_core.py): live slots per
decode step over the slots the deployment has, from the engine's counters
``occupancy_sum`` and ``decode_steps`` over the window."""


def read(run):
    steps = run.delta("decode_steps")
    return None if steps <= 0 else 100.0 * run.delta("occupancy_sum") / steps / run.max_slots
