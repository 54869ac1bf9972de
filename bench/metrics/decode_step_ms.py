"""decode_step_ms (device step, runtime/engine.py + device_step.py): the
engine's own timer around each decode chunk (``decode_time``, which ends in
``block_until_ready``) over the decode steps of the window."""


def read(run):
    steps = run.delta("decode_steps")
    return None if steps <= 0 else 1e3 * run.delta("decode_time") / steps
