"""The work each ``step_chunk`` of the window needed, from what the driver saw.

Decode: a request with ``b`` tokens before a step and ``a`` after it
decoded ``a - b`` tokens in that step (``a - 1`` when its first token came
from this step's prefill). Its k-th decode step attended over its prompt,
the tokens generated before it and the new one: ``P + g + k`` positions,
with ``g = max(b, 1)``. The decode chunk's k-th step is shared by every
request that decoded more than k tokens in it.

Prefill: a request needs the rows of its prompt that no earlier prompt
shares (``RunData.needed_cached``, by content), in chunks of the program's
``prefill_chunk`` rows, one chunk per ``step_chunk`` as the engine runs
them; its last chunk ran in the step that gave its first token, the one
before in the step before, and so on. Where the program's cache holds less
than content allows (it matches whole blocks), its first chunk starts a
few rows earlier than counted here, so the rows counted are the rows
needed, aligned to the step that finished them.
"""

from __future__ import annotations

from collections import defaultdict

import work


def decode_steps(run, steps):
    """Per decode step of the given ``step_chunk`` calls: (prompts, contexts)
    of the requests that decoded in it."""
    recs = {r.index: r for r in run.records}
    for step in steps:
        k_of = {}
        for idx, before, after in step.progress:
            if after == 0:
                continue
            g = max(before, 1)
            k = after - g
            if k > 0:
                k_of[idx] = (g, k)
        depth = max((k for _g, k in k_of.values()), default=0)
        for t in range(depth):
            live = [(recs[i], g) for i, (g, k) in k_of.items() if k > t]
            yield [r.prompt for r, _ in live], [len(r.prompt) + g + t for r, g in live]


def prefill_rows(run) -> dict[int, list[tuple[int, int]]]:
    """Step index -> [(start, n)] prefill chunks needed in that step."""
    out: dict[int, list[tuple[int, int]]] = defaultdict(list)
    C = run.prefill_chunk
    for r in run.records:
        if r.first_step is None or r.index not in run.needed_cached:
            continue
        c, P = run.needed_cached[r.index], len(r.prompt)
        m = -(-(P - c) // C)
        for i in range(m):
            start = c + i * C
            out[r.first_step - (m - 1) + i].append((start, min(C, P - start)))
    return out


def decode_work(run, steps=None) -> tuple[work.Work, int]:
    """Attention work of every decode step in ``steps`` (default: the
    window's), and the number of decode steps."""
    steps = run.window_steps() if steps is None else steps
    total, n = work.Work(), 0
    memo: dict[tuple, int] = {}
    for prompts, ctx in decode_steps(run, steps):
        key = tuple(id(p) for p in prompts)
        if key not in memo:
            memo[key] = work.distinct_prefix_tokens(prompts)
        total = total + work.decode_step_work(run.shape, prompts, ctx, shared=memo[key])
        n += 1
    return total, n


def prefill_work(run, steps=None) -> tuple[work.Work, int]:
    """Attention work of the prefill chunks needed in ``steps``, and their
    number."""
    steps = run.window_steps() if steps is None else steps
    rows = prefill_rows(run)
    total, n = work.Work(), 0
    for step in steps:
        for start, k in rows.get(step.index, ()):
            total = total + work.prefill_chunk_work(run.shape, start, k)
            n += 1
    return total, n


def model_flops(run, steps=None) -> float:
    """Model FLOPs of the window: prefill rows needed and decoded tokens,
    the LM head once per sampled token."""
    steps = run.window_steps() if steps is None else steps
    flops = 0.0
    for _prompts, ctx in decode_steps(run, steps):
        flops += work.model_flops(run.shape, float(sum(ctx)), len(ctx), len(ctx))
    rows = prefill_rows(run)
    last_chunk = {(r.first_step, len(r.prompt)) for r in run.records if r.first_step is not None}
    for step in steps:
        for start, k in rows.get(step.index, ()):
            ctx_sum = k * start + k * (k + 1) / 2
            sampled = int((step.index, start + k) in last_chunk)
            flops += work.model_flops(run.shape, ctx_sum, k, sampled)
    return flops
