"""prefill_attn_roofline (kernels, kernels/exaq_paged_prefill.py): the least
time the window's prefill attention needs on this chip (each chunk's KV
window read once, q and output rows, causal 4*H*Dh*sum(row+1) FLOPs;
``work.py``, rows placed by ``steps.py``), over the device time of the
fused paged-prefill kernel in the trace (``exaq_paged_prefill_attention.<n>``,
read by hand from a v5e trace)."""

import steps

KERNEL = ("exaq_paged_prefill_attention",)


def read(run):
    if run.trace is None or not run.chips:
        return None
    t = sum(run.trace.op_seconds(c, KERNEL) for c in run.chips) / len(run.chips)
    need, n = steps.prefill_work(run)
    if t <= 0 or n == 0:
        return None
    least, _bound = need.least_time(run.peak)
    return 100.0 * least / t


def bound(run):
    return steps.prefill_work(run)[0].least_time(run.peak)[1]
