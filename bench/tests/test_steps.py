"""The work of a window, from what the driver saw, on hand-worked cases."""

import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import steps  # noqa: E402
import work  # noqa: E402
from driver import Record, Step  # noqa: E402

SHAPE = work.Shape(layers=1, d_model=8, heads=2, kv_heads=1, head_dim=4, d_ff=16, vocab=10)


def fake_run(records, step_list, seconds=10.0):
    r = run.RunData(cell=None, seconds=seconds, setup_s=0.0, records=records, steps=step_list,
                    lateness=[], stats0={}, stats1={}, max_slots=4, prefill_chunk=4,
                    shape=SHAPE, device_kind="TPU v5 lite")
    r.needed_cached = run.needed_cached(records, [])
    return r


def two_requests():
    a = Record(0, 0.0, np.arange(10, dtype=np.int32), 6, sent_s=0.0)
    b = Record(1, 0.5, np.concatenate([np.arange(6), [90, 91, 92]]).astype(np.int32), 3, sent_s=0.5)
    a.first_step, b.first_step = 2, 3
    s = [Step(0, 0.0, 1.0, [(0, 0, 0)]),
         Step(1, 1.0, 2.0, [(0, 0, 0), (1, 0, 0)]),
         Step(2, 2.0, 3.0, [(0, 0, 3), (1, 0, 0)]),     # a: first token, then 2 decoded
         Step(3, 3.0, 4.0, [(0, 3, 6), (1, 0, 2)])]     # a: 3 more; b: first token and 1
    return [a, b], s


def test_needed_cached_is_the_longest_shared_prefix_with_an_earlier_prompt():
    recs, _ = two_requests()
    assert run.needed_cached(recs, []) == {0: 0, 1: 6}
    # a prompt wholly inside an earlier one keeps its last token to prefill
    c = Record(2, 1.0, np.arange(5, dtype=np.int32), 2, sent_s=1.0)
    assert run.needed_cached(recs + [c], [])[2] == 4


def test_decode_contexts_per_step():
    recs, s = two_requests()
    got = list(steps.decode_steps(fake_run(recs, s), s))
    # step 2: a decodes 2 (g=1): contexts 11, 12
    # step 3: a decodes 3 (g=3): 13, 14, 15; b decodes 1 (g=1): 10
    assert [c for _p, c in got] == [[11], [12], [13, 10], [14], [15]]


def test_prefill_rows_end_at_the_first_token_step():
    recs, s = two_requests()
    rows = steps.prefill_rows(fake_run(recs, s))
    # a: 10 rows from 0 in chunks of 4, the last in step 2; b: rows 6..8 in step 3
    assert dict(rows) == {0: [(0, 4)], 1: [(4, 4)], 2: [(8, 2)], 3: [(6, 3)]}


def test_window_work_sums_the_steps_inside_it():
    recs, s = two_requests()
    r = fake_run(recs, s, seconds=2.5)     # steps 0, 1 and 2 start inside
    dec, n = steps.decode_work(r)
    assert n == 2
    assert dec.flops == pytest.approx(4 * 2 * 4 * (11 + 12))
    pre, m = steps.prefill_work(r)
    assert m == 3
    want = sum((work.prefill_chunk_work(SHAPE, a, k) for a, k in [(0, 4), (4, 4), (8, 2)]), work.Work())
    assert pre.flops == want.flops and pre.bytes == want.bytes
    # a's 10 prompt rows (causal contexts 1..10 sum to 55) with one LM-head
    # row, and its 2 decoded tokens at contexts 11 and 12
    assert steps.model_flops(r) == pytest.approx(
        work.model_flops(SHAPE, 55, 10, 1) + work.model_flops(SHAPE, 11 + 12, 2, 2))


def test_shared_prompts_lower_decode_bytes_in_a_step():
    a = Record(0, 0.0, np.arange(8, dtype=np.int32), 4, sent_s=0.0)
    b = Record(1, 0.0, np.concatenate([np.arange(7), [50]]).astype(np.int32), 4, sent_s=0.0)
    a.first_step = b.first_step = 0
    s = [Step(0, 0.0, 1.0, [(0, 0, 2), (1, 0, 2)])]
    dec, n = steps.decode_work(fake_run([a, b], s))
    # one decode step, two slots at context 9 (8 prompt tokens and the
    # token being decoded): 9 distinct prompt positions (7 shared + 1 + 1)
    # and one own position per slot, then a q and an output row per slot
    assert n == 1
    assert dec.bytes == (9 + 2) * 16 + 2 * 2 * 16
