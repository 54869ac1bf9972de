"""moe_rows_per_expert (model, models/moe.py): token rows each held expert's
weight read serves in a decode step and layer, from the engine's counters
``moe_rows`` (live rows routed to the held experts) over
``moe_experts_touched`` ((step, layer, expert) triples with at least one
such row) over the window. Silent where the program has no such counters."""


def read(run):
    touched = run.delta("moe_experts_touched")
    return None if touched <= 0 else run.delta("moe_rows") / touched
