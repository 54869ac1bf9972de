"""prefix_hit_share (host scheduler, runtime/engine_core.py): prompt tokens
the prefix cache served, over prompt tokens admitted in the window, from
the engine's counters ``prefix_hit_tokens`` and ``prompt_tokens``."""


def read(run):
    prompt = run.delta("prompt_tokens")
    return None if prompt <= 0 else 100.0 * run.delta("prefix_hit_tokens") / prompt
