"""output_tokens_per_s: every output token the window's steps emitted, of
any request, over the whole window (until its last step ended)."""

import latency


def read(run):
    return latency.output_tokens(run) / run.close_s
