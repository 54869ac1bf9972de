"""Host scheduler (runtime.engine_core) unit tests — NO device arrays.

The whole point of the EngineCore split (DESIGN.md §9) is that every paged
scheduling decision — admission, prefix matching, chunked-prefill planning,
CoW adjudication, preempt-and-recompute, the int8 fresh-scale queue — is
plain Python over numpy scalars and can be tested without compiling a
single jitted function. The first test pins the contract structurally:
importing the module must not import jax.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.runtime.engine_core import (  # noqa: E402
    EngineCore,
    HostCore,
    PrefillChunkPlan,
    Request,
    _bucket,
    decode_tile_counts,
)
from repro.runtime.kv_pool import NULL_BLOCK, PoolExhausted, PoolStats  # noqa: E402

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _core(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    return EngineCore(**kw)


# ------------------------------------------------------------ import purity


def test_engine_core_imports_without_jax():
    """engine_core is the host half of the split: importing it must not drag
    in jax (device_step.py owns all device code)."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import repro.runtime.engine_core, sys; "
         "assert 'jax' not in sys.modules, 'engine_core imported jax'; "
         "print('PURE_HOST_OK')"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src")),
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "PURE_HOST_OK" in out.stdout


def test_kv_pool_imports_without_jax():
    out = subprocess.run(
        [sys.executable, "-c",
         "import repro.runtime.kv_pool, sys; "
         "assert 'jax' not in sys.modules; print('OK')"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src")),
    )
    assert out.returncode == 0, out.stderr


# --------------------------------------------------------------- validation


def test_validate_rejects_empty_and_oversized():
    core = _core()
    with pytest.raises(ValueError, match="empty"):
        core.submit([], 4)
    with pytest.raises(ValueError, match="max_seq"):
        core.submit(list(range(64)), 4)
    with pytest.raises(ValueError, match="max_new"):
        core.submit([1, 2], 0)


def test_validate_rejects_request_larger_than_pool():
    core = _core(num_blocks=4)  # 3 usable blocks of 4 tokens
    with pytest.raises(ValueError, match="blocks"):
        core.submit(list(range(10)), 10)  # worst case 20 tok = 5 blocks
    core.submit(list(range(6)), 4)  # 10 tok = 3 blocks: fits


# ---------------------------------------------------------------- admission


def test_admit_allocates_table_and_parks_prefilling():
    core = _core()
    core.submit([1, 2, 3, 4, 5, 6], 4)
    assert core._admit() == 1
    s = core._slots[0]
    assert s.prefilling and not s.free
    assert len(s.table) == 2  # ceil(6/4) blocks
    assert (core._tables[0, :2] == s.table).all()
    assert (core._tables[0, 2:] == NULL_BLOCK).all()
    assert core.stats["prompt_tokens"] == 6 and core.stats["prefix_hit_tokens"] == 0


def test_admission_back_pressure_requeues_on_pool_exhaustion():
    core = _core(num_blocks=4, max_slots=2)  # 3 usable blocks
    core.submit([1] * 8, 1)   # needs 2 blocks prompt (+1 gen fits in last)
    core.submit([2] * 8, 1)
    assert core._admit() == 1  # second request cannot get 3 blocks
    assert core.num_queued == 1  # rolled back, not dropped
    assert core.pool.stats.frees >= 0  # rollback released partial allocs


def test_prefix_reuse_after_commit():
    core = _core()
    prompt = [7, 7, 7, 7, 9, 9]  # first block (4 tok) hashable
    core.submit(prompt, 4)
    core._admit()
    plan = core.plan_prefill_chunk(0)
    assert plan.n == 6 and plan.start == 0
    done = core.commit_prefill_chunk(0, plan.n)
    assert done  # whole prompt in one chunk
    # same prompt again: both blocks (full + partial tail) hit the prefix
    # index; cached clamps to len(prompt)-1 so the last token re-prefills
    core.submit(prompt, 4)
    core._admit()
    s1 = core._slots[1]
    assert s1.cached == 5
    assert s1.table == core._slots[0].table  # both blocks shared
    assert core.stats["prefix_hit_tokens"] == 5
    assert core.prefix_hit_rate == pytest.approx(5 / 12)


def test_fully_cached_prompt_still_prefills_last_token():
    core = _core()
    prompt = [3, 3, 3, 3]  # exactly one block
    core.submit(prompt, 4)
    core._admit()
    core.commit_prefill_chunk(0, core.plan_prefill_chunk(0).n)
    core.submit(prompt, 4)
    core._admit()
    # cached is clamped to len(prompt)-1: sampling needs the last token's logits
    assert core._slots[1].cached == 3
    plan = core.plan_prefill_chunk(1)
    assert plan.start == 3 and plan.n == 1


# ------------------------------------------------------------ prefill plans


def test_plan_shapes_and_scatter_targets():
    core = _core(prefill_chunk=8, block_size=4)
    core.submit(list(range(100, 110)), 4)  # 10 tokens, 2 chunks
    core._admit()
    s = core._slots[0]
    p1 = core.plan_prefill_chunk(0)
    assert isinstance(p1, PrefillChunkPlan)
    assert p1.tokens.shape == (1, 8) and p1.n == 8 and p1.start == 0
    assert (p1.tokens[0, :8] == np.arange(100, 108)).all()
    assert (p1.blk_t[:4] == s.table[0]).all() and (p1.blk_t[4:8] == s.table[1]).all()
    assert (p1.off_t[:8] == [0, 1, 2, 3, 0, 1, 2, 3]).all()
    assert not core.commit_prefill_chunk(0, p1.n)
    p2 = core.plan_prefill_chunk(0)
    assert p2.start == 8 and p2.n == 2
    assert (p2.blk_t[:2] == s.table[2]).all()
    # padded rows scatter into the null block at spread offsets
    assert (p2.blk_t[2:] == NULL_BLOCK).all()
    assert core.commit_prefill_chunk(0, p2.n)


# ------------------------------------------------------------ CoW queueing


def test_cow_fork_queues_copy_instead_of_performing_it():
    core = _core()
    prompt = [5, 5, 5, 5]
    core.submit(prompt, 8)
    core._admit()
    core.commit_prefill_chunk(0, core.plan_prefill_chunk(0).n)
    core.submit(prompt, 8)
    core._admit()  # slot 1 shares block 0's first block (refcount 2)
    shared = core._slots[1].table[0]
    assert core.pool.refcount[shared] == 2
    assert core.pending_copies == []
    core._make_writable(1, 0)
    new = core._slots[1].table[0]
    assert new != shared
    assert core.pending_copies == [(shared, new)]
    assert core._tables[1, 0] == new
    # the queue is handed over exactly once
    assert core.take_pending_copies() == [(shared, new)]
    assert core.take_pending_copies() == []


def test_exclusive_block_appends_in_place():
    core = _core()
    core.submit([1, 2, 3], 4)
    core._admit()
    blk = core._slots[0].table[0]
    core._make_writable(0, 0)
    assert core._slots[0].table[0] == blk  # refcount 1: no fork
    assert core.pending_copies == []


# ------------------------------------------------------- fresh-scale queue


def test_fresh_scale_queue_only_when_quantized():
    fp = _core(quantized=False)
    fp.submit([1, 2, 3, 4, 5], 4)
    fp._admit()
    assert fp.take_fresh_scale_ids() == []

    q = _core(quantized=True)
    q.submit([1, 2, 3, 4, 5], 4)
    q._admit()
    fresh = q.take_fresh_scale_ids()
    assert sorted(fresh) == fresh and len(fresh) == 2
    assert set(fresh) == set(q._slots[0].table)
    assert q.take_fresh_scale_ids() == []  # cleared


def test_fork_destination_escapes_scale_reset():
    """A CoW fork's scales arrive with the copied payload: its id must NOT
    sit in the fresh queue or the flush would zero the copied grid."""
    q = _core(quantized=True)
    prompt = [5, 5, 5, 5]
    q.submit(prompt, 8)
    q._admit()
    q.commit_prefill_chunk(0, q.plan_prefill_chunk(0).n)
    q.submit(prompt, 8)
    q._admit()
    q.take_fresh_scale_ids()  # drain admission allocations
    q._make_writable(1, 0)
    _, dst = q.pending_copies[0]
    assert dst not in q._fresh_blocks


# ---------------------------------------------------------------- preempt


def test_preempt_releases_blocks_and_requeues_continuation():
    core = _core()
    core.submit([1, 2, 3, 4, 5], 10)
    core._admit()
    core.commit_prefill_chunk(0, core.plan_prefill_chunk(0).n)
    req = core._slots[0].req
    core._complete_first(0, req, 42)
    core._slots[0].generated.extend([43, 44])
    core._budget[0] = 7
    blocks = list(core._slots[0].table)
    core._preempt(0)
    assert not core._active[0] and core._slots[0].free
    assert (core._tables[0] == NULL_BLOCK).all()
    for b in blocks:
        assert core.pool.refcount[b] == 0  # released (may live in LRU)
    cont = core._queue[0]
    assert cont.uid == req.uid
    assert cont.prompt == req.prompt + (42, 43, 44)
    assert cont.max_new == 7
    assert core._preempt_carry[req.uid] == [42, 43, 44]
    assert core.stats["preemptions"] == 1


def test_finish_merges_preempt_carry():
    core = _core()
    core.submit([1, 2, 3], 5)
    core._admit()
    core.commit_prefill_chunk(0, core.plan_prefill_chunk(0).n)
    req = core._slots[0].req
    core._complete_first(0, req, 10)
    core._preempt_carry[req.uid] = [8, 9]
    core._slots[0].generated.append(11)
    core._finish(0, "length")
    g = core._results[req.uid]
    assert g.tokens == [8, 9, 10, 11]
    assert not core._preempt_carry  # consumed


def test_reserve_raises_when_sole_request_cannot_grow():
    core = _core(num_blocks=4, max_slots=2, max_seq=12)  # 3 usable blocks
    core.submit([1, 2, 3, 4], 20)  # worst case clamps to max_seq = 3 blocks
    core._admit()
    core.commit_prefill_chunk(0, core.plan_prefill_chunk(0).n)
    core._complete_first(0, core._slots[0].req, 1)
    # pin the remaining blocks (as a concurrent prefill would): the sole
    # active slot can neither grow nor find a victim to preempt
    held = [core.pool.alloc(), core.pool.alloc()]
    with pytest.raises(PoolExhausted, match="only active request"):
        core._reserve_chunk_blocks(8)
    for b in held:
        core.pool.release(b)


# ---------------------------------------------------------------- plumbing


def test_step_chunk_is_device_layer_territory():
    with pytest.raises(NotImplementedError):
        HostCore(max_slots=1, max_seq=8).step_chunk()


def test_bucket_rounds_up_to_power_of_two():
    assert _bucket(1, 16) == 16
    assert _bucket(16, 16) == 16
    assert _bucket(17, 16) == 32
    assert _bucket(3, 8) == 8
    assert _bucket(9, 8) == 16


def test_pool_stats_merged_sums_fieldwise():
    a = PoolStats(allocs=3, frees=1, evictions=2, cow_copies=1, hash_hits=4, hash_misses=5)
    b = PoolStats(allocs=10, frees=20, evictions=0, cow_copies=2, hash_hits=0, hash_misses=1)
    m = PoolStats.merged([a, b])
    assert m == PoolStats(allocs=13, frees=21, evictions=2, cow_copies=3,
                          hash_hits=4, hash_misses=6)
    assert PoolStats.merged([]) == PoolStats()


def test_request_uses_host_greedy_default():
    """engine_core cannot import runtime.sampling (it imports jax); the host
    default must be an independent greedy sentinel with the same fields."""
    r = Request((1,), 1)
    assert r.sampling.temperature == 0.0
    assert r.sampling.top_k == 0
    assert r.sampling.top_p == 1.0


# ------------------------------------------------------ decode tile counters

@pytest.mark.parametrize("steps", [1, 3])
def test_decode_tile_counts_follow_lengths(steps):
    """decode_kv_tiles counts cdiv(kv_len, tile) for every slot of every
    step — idle slots at their stale length plus the step's own token,
    active ones growing one token a step until their mask drops —
    and decode_kv_tiles_full counts a whole-table walk."""
    lens = np.array([0, 3, 4, 7])  # slot 0 idle; tile 4; a 10-token table
    masks = np.array([[0, 1, 1, 1],   # slot 2 finishes after step 0,
                      [0, 1, 0, 1],   # slot 3 after step 1
                      [0, 1, 0, 0]])[:steps]
    walked, full = decode_tile_counts(lens, masks, tile=4, table_tokens=10)
    # step 0 reads [1, 4, 5, 8] tokens: 1+1+2+2 tiles; step 1 [1, 5, 6, 9]:
    # 1+2+2+3; step 2 [1, 6, 6, 10]: 1+2+2+3
    assert walked == {1: 6, 3: 6 + 8 + 8}[steps]
    assert full == steps * 4 * 3
