"""The yardstick's arithmetic, on cases worked by hand."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import work  # noqa: E402

# one layer, 2 q heads, 1 kv head, head_dim 4, bf16 pool: a KV token is
# 2 * 1 * 4 * 2 = 16 bytes per layer; a q or output row is 2 * 4 * 2 = 16 bytes
SMALL = work.Shape(layers=1, d_model=8, heads=2, kv_heads=1, head_dim=4, d_ff=16, vocab=10)


def test_distinct_prefix_tokens_counts_shared_positions_once():
    assert work.distinct_prefix_tokens([[1, 2, 3]]) == 3
    assert work.distinct_prefix_tokens([[1, 2, 3], [1, 2, 4]]) == 4          # 1,2 shared
    assert work.distinct_prefix_tokens([[1, 2, 3], [1, 2, 3]]) == 3          # identical
    assert work.distinct_prefix_tokens([[1, 2], [1, 2, 5, 6]]) == 4          # a prefix of another
    assert work.distinct_prefix_tokens([[1, 2, 3], [9, 2, 3]]) == 6          # same tail, no prefix
    assert work.distinct_prefix_tokens([[5, 1], [1, 2, 3], [1, 2, 4], [5, 1, 1]]) == 7


def test_decode_bytes_drop_when_slots_share_a_prefix():
    shared = [[1, 2, 3, 4, 5, 6], [1, 2, 3, 4, 5, 7]]
    apart = [[1, 2, 3, 4, 5, 6], [8, 2, 3, 4, 5, 7]]
    ctx = [8, 8]  # 6 prompt tokens, one generated, the new one
    w_shared = work.decode_step_work(SMALL, shared, ctx)
    w_apart = work.decode_step_work(SMALL, apart, ctx)
    # shared: 7 distinct prompt positions + 2 own tokens per slot = 11 tokens
    assert w_shared.bytes == 11 * 16 + 2 * 2 * 16
    # apart: 12 prompt positions + 4 = 16 tokens
    assert w_apart.bytes == 16 * 16 + 2 * 2 * 16
    assert w_shared.bytes < w_apart.bytes
    # FLOPs do not depend on sharing: 4 * ctx * H * Dh per slot
    assert w_shared.flops == w_apart.flops == 4 * 8 * 2 * 4 * 2


def test_decode_work_scales_with_layers():
    deep = work.Shape(layers=3, d_model=8, heads=2, kv_heads=1, head_dim=4, d_ff=16, vocab=10)
    one = work.decode_step_work(SMALL, [[1, 2]], [3])
    three = work.decode_step_work(deep, [[1, 2]], [3])
    assert three.bytes == 3 * one.bytes and three.flops == 3 * one.flops


def test_prefill_chunk_work_by_hand():
    w = work.prefill_chunk_work(SMALL, start=4, n=2)
    # rows 4 and 5 attend over 5 and 6 keys: 4 * (5 + 6) * H * Dh
    assert w.flops == 4 * 11 * 2 * 4
    # the window of 6 tokens read once, two q rows and two output rows
    assert w.bytes == 6 * 16 + 2 * 2 * 16


def test_model_flops_by_hand():
    # per layer: q 8x8, k and v 8x4 each, o 8x8, mlp 3 x 8x16 = 64+64+64+384 = 576 weights
    assert SMALL.matmul_weights_per_layer == 576
    f = work.model_flops(SMALL, ctx_sum=10, tokens=2, sampled=1)
    assert f == 2 * 576 * 2 + 4 * 2 * 4 * 10 + 2 * 8 * 10


def test_least_time_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.Work(flops=1000, bytes=50).least_time(peak) == (10.0, "compute")
    assert work.Work(flops=100, bytes=50).least_time(peak) == (5.0, "memory")


def test_peaks_table_knows_v5e_and_refuses_unknown_devices():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "source" in p
    with pytest.raises(work.UnknownDevice):
        work.peaks("TPU v9 imaginary")
