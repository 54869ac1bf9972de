"""step_mfu (model, models/model.py): model FLOPs of the tokens the
window's steps computed (prompt rows no earlier prompt shares, and output
tokens; every matmul, attention over the live context, the LM head once per
sampled token; ``steps.model_flops``) over the time those ``step_chunk``
calls took (host clock; each ends when the device is done) times the chip's
peak bf16 FLOP/s, summed over the cell's chips. It is the whole step's
share of the peak: below a cell's knee the window also holds idle waits
for arrivals, which no program change shortens, so they are left out."""

import steps


def read(run):
    ws = run.window_steps()
    busy = sum(s.end_s - s.start_s for s in ws)
    if not ws or busy <= 0:
        return None
    chips = run.cell.chips
    return 100.0 * steps.model_flops(run) / (busy * run.peak["bf16_flops_per_s"] * chips)
