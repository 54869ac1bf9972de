"""decode_attn_roofline (kernels, kernels/exaq_paged_attention.py): the
least time the window's decode attention needs on this chip (distinct live
KV read once per step and layer, q and output rows; 4*ctx*H*Dh FLOPs per
slot and layer; ``work.py``), over the device time of the fused paged-decode
kernel in the trace, which names the kernel's custom call after the
jitted wrapper (``exaq_paged_decode_attention.<n>``; read by hand from a
v5e trace)."""

import steps

KERNEL = ("exaq_paged_decode_attention",)


def read(run):
    if run.trace is None or not run.chips:
        return None
    t = sum(run.trace.op_seconds(c, KERNEL) for c in run.chips) / len(run.chips)
    need, n = steps.decode_work(run)
    if t <= 0 or n == 0:
        return None
    least, _bound = need.least_time(run.peak)
    return 100.0 * least / t


def bound(run):
    return steps.decode_work(run)[0].least_time(run.peak)[1]
