"""Pure-host engine core: slot table, block allocator, prefix cache, scheduler.

This module is the HOST half of the serving engines (DESIGN.md §9): every
scheduling decision — admission against the rolling-hash prefix cache, block
allocation and copy-on-write adjudication, chunked-prefill planning,
preempt-and-recompute back-pressure, finish transitions, telemetry — lives
here as plain Python + numpy over small integer state. It imports **no jax**
(enforced by tests/test_engine_core.py): the device half is
``runtime/device_step.py``, which holds the jitted functions that consume the
plans produced here and carry the (possibly mesh-sharded) pool pytree.

The split is what lets the system scale past one chip: the same ``EngineCore``
instance schedules a single-device engine, a tensor-parallel engine whose pool
is sharded over the 'model' mesh axis, or one replica of a data-parallel
fleet (``runtime.engine.DataParallelEngine``) — the core never knows, because
block ids, tables, and lengths are device-layout-free.

Two classes:

  * ``HostCore``   — slot-level state shared by the slot and paged engines:
                     per-slot arrays (lens / active / budget / sampling
                     params), the request queue, results, finish transitions,
                     chunk-absorption bookkeeping, occupancy telemetry.
  * ``EngineCore`` — the paged scheduler on top: ``BlockPool`` allocator +
                     per-slot block tables, prefix-hash admission, chunked-
                     prefill planning, CoW planning (device copies are
                     *queued* as (src, dst) pairs for the device step to
                     drain), fresh-block scale-reset queueing for int8 pools,
                     and the preempt-and-recompute policy.
"""

from __future__ import annotations

import bisect
import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from repro.runtime.kv_pool import (
    NULL_BLOCK,
    BlockPool,
    PoolExhausted,
    PoolOccupancy,
    chain_hashes,
)
from repro.runtime.speculative import greedy_accept_length


@dataclass(frozen=True)
class GreedySampling:
    """jax-free stand-in for ``runtime.sampling.SamplingParams`` defaults —
    the engines pass real SamplingParams; the core only reads these fields."""

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0


GREEDY = GreedySampling()


@dataclass(frozen=True)
class Request:
    """One generation request — the single submission surface of every engine
    front (``submit``/``try_submit``/``AsyncFrontend.submit`` all accept one
    in place of the legacy (prompt, max_new, ...) spread). ``uid`` is
    engine-assigned at admission; user-constructed requests leave it at -1.
    """

    prompt: tuple[int, ...]
    max_new: int
    sampling: Any = GREEDY
    # SLA annotations (DESIGN.md §11): lower priority value = more urgent
    # class; deadline is an *absolute* TTFT deadline on the core clock
    # (``HostCore.now()``) — None means no SLA.
    priority: int = 0
    deadline: float | None = None
    uid: int = -1


@dataclass(frozen=True)
class EngineConfig:
    """Single construction surface for every serving engine (jax-free).

    Pool sizing, cache dtype, fusion / speculative knobs, and scheduling
    knobs in one frozen value: ``launch/serve.py`` builds exactly one from
    argparse (``args_to_config``) and hands it to whichever engine class the
    config family selects; benches, tests, the chaos harness and the async
    frontend pass it through unchanged. ``kv_dtype`` is a *string* key
    (fp32 | bf16 | fp16 | int8 | int4) resolved to a device dtype at the
    engine layer — this module stays jax-free. The legacy per-field kwarg
    spreads survive as thin deprecated shims on the engine constructors.
    """

    max_slots: int
    max_seq: int
    block_size: int = 16
    prefill_chunk: int = 32
    num_blocks: int | None = None
    eos_id: int | None = None
    steps_per_sync: int = 8
    kv_dtype: str = "bf16"
    fused: bool | None = None       # None = auto (fused kernels when they apply)
    seed: int = 0
    max_inflight: int | None = None
    admit_watermark: float | None = None
    spec_k: int = 0                 # speculative draft length (0 = vanilla)
    drafter: str | None = None      # "ngram" | "pool" (spec_k > 0 only)
    replicas: int = 1               # DataParallelEngine fan-out

    def core_kwargs(self) -> dict:
        """kwargs for the host half (``EngineCore``) — the paged scheduler
        knows nothing of dtypes, fusion or speculation."""
        return dict(
            max_slots=self.max_slots, max_seq=self.max_seq,
            block_size=self.block_size, prefill_chunk=self.prefill_chunk,
            num_blocks=self.num_blocks, eos_id=self.eos_id,
            steps_per_sync=self.steps_per_sync, max_inflight=self.max_inflight,
            admit_watermark=self.admit_watermark,
            quantized=self.kv_dtype in ("int8", "int4"),
        )


@dataclass(frozen=True)
class Rejected:
    """Structured shed-load response (DESIGN.md §11): the admission layer's
    alternative to silently queueing into an eviction storm. ``retryable``
    distinguishes transient overload (back off ``backoff_hint`` clock units
    and resubmit) from requests that can never be served (malformed, larger
    than the pool). ``occupancy`` is the pool census at decision time when a
    paged pool was consulted; ``uid`` is set only for post-admission sheds
    (a queued request whose TTFT deadline expired), -1 otherwise."""

    reason: str  # "invalid" | "max_inflight" | "pool_pressure" | "deadline"
    detail: str = ""
    retryable: bool = True
    backoff_hint: float = 0.0
    occupancy: PoolOccupancy | None = None
    uid: int = -1


class AdmissionRejected(RuntimeError):
    """Raised by ``submit`` when admission control sheds the request; the
    structured ``Rejected`` rides in ``.rejected`` (``try_submit`` returns
    it instead of raising — the frontend path)."""

    def __init__(self, rejected: Rejected):
        super().__init__(f"request shed: {rejected.reason} {rejected.detail}".strip())
        self.rejected = rejected


@dataclass
class Generation:
    """Finished request: generated ids (EOS included when hit) + why it ended."""

    uid: int
    tokens: list[int]
    finish_reason: str  # "eos" | "length" | "cancelled"


class _ReqQueue:
    """Priority request queue with the deque surface the cores grew up with.

    Entries order by ``(priority, seq)``: equal priorities stay FIFO on the
    admission sequence number, and a preempted continuation — which reuses
    its original uid as ``seq`` — re-enters *ahead* of later arrivals of its
    class, preserving the pre-priority engines' appendleft semantics (and
    their bit-exact admission order when every request is class 0)."""

    def __init__(self):
        self._items: list[tuple[int, int, Request]] = []

    def append(self, req: Request) -> None:
        # uid is monotone per core, so it doubles as the admission seq;
        # ties in (priority, seq) are impossible and Request never compares
        bisect.insort(self._items, (req.priority, req.uid, req))

    appendleft = append  # continuations re-sort by their (old, small) uid

    def popleft(self) -> Request:
        return self._items.pop(0)[2]

    def remove_uid(self, uid: int) -> Request | None:
        for i, (_, _, req) in enumerate(self._items):
            if req.uid == uid:
                return self._items.pop(i)[2]
        return None

    def __bool__(self) -> bool:
        return bool(self._items)

    def __len__(self) -> int:
        return len(self._items)

    def __getitem__(self, i: int) -> Request:
        return self._items[i][2]

    def __iter__(self):
        return (req for _, _, req in self._items)


@dataclass
class _Slot:
    uid: int = -1
    generated: list[int] = field(default_factory=list)

    @property
    def free(self) -> bool:
        return self.uid < 0

    @property
    def prefilling(self) -> bool:
        return False  # slot-engine prefill is synchronous at admission


@dataclass
class _PagedSlot:
    uid: int = -1
    generated: list[int] = field(default_factory=list)
    req: Request | None = None
    table: list[int] = field(default_factory=list)   # host truth; mirrored to _tables
    hashes: list[tuple[int, int]] = field(default_factory=list)
    filled: int = 0        # prompt tokens with KV materialized (hits + chunks)
    cached: int = 0        # tokens satisfied from the prefix cache
    _prefilling: bool = False

    @property
    def free(self) -> bool:
        return self.uid < 0

    @property
    def prefilling(self) -> bool:
        return self._prefilling


@dataclass(frozen=True)
class PrefillChunkPlan:
    """Host-computed launch plan for one chunked-prefill step: everything the
    device step needs, as plain numpy (the device step ships it)."""

    slot: int
    tokens: np.ndarray       # (1, C) int32, right-padded
    start: int               # tokens already materialized (hits + prior chunks)
    n: int                   # live tokens in this chunk
    blk_t: np.ndarray        # (C,) int32 scatter target blocks (pad -> null)
    off_t: np.ndarray        # (C,) int32 scatter target offsets


@dataclass
class SpecBranch:
    """One in-flight speculative draft branch (DESIGN.md §12): the blocks
    backing verify positions ``[start, start + len(drafts)]`` of slot
    ``slot``. Every entry of ``table`` is branch-owned (holds exactly one
    refcount); the slot's shared prefix blocks are covered by the slot's own
    references and are NOT retained again — a branch dies (abort) or is
    spliced into the slot table (commit) without ever touching prefix
    refcounts. When ``forked`` the first entry is a CoW read-fork of the
    slot's partially-filled tail block, delivered by a queued
    ``pending_copies`` entry (drained before the verify launches)."""

    slot: int
    uid: int
    drafts: tuple[int, ...]
    start: int               # kv length at fork time = write position of row 0
    bi0: int                 # first table index the branch owns
    table: list[int]         # branch-owned block ids for indices [bi0, ...]
    forked: bool             # table[0] is a CoW copy of the slot's tail block


@dataclass(frozen=True)
class SpecVerifyPlan:
    """Launch plan for one speculative verify round: one fused paged-prefill
    call over the window [start, start + C) with the branch's blocks spliced
    over the slot's table (device mirror composed here, host-side)."""

    slot: int
    branch: SpecBranch
    tokens: np.ndarray       # (1, C) int32: [pending, draft_1..draft_{C-1}]
    start: int
    table: np.ndarray        # (MB,) int32 slot-prefix + branch window table
    blk_t: np.ndarray        # (C,) int32 scatter target blocks (branch-owned)
    off_t: np.ndarray        # (C,) int32 scatter target offsets


@dataclass(frozen=True)
class SpecCommit:
    """Host outcome of a verify round, pre-absorb: what to emit plus the
    int4 tail-hygiene coordinates (the engine trims sub codes the rejected
    rows seeded past the accepted length — DESIGN.md §12)."""

    slot: int
    emitted: list[int]       # drafts[:accepted] + [correction token]
    accepted: int            # accepted draft prefix length in [0, k]
    tail_block: int          # the committed tail block id
    tail_rows: int           # committed-valid rows of that block: (start+a)%bs+1
    trim_tail: bool          # rejected rows wrote into the kept tail block


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def decode_tile_counts(kv_lens, masks, *, tile: int, table_tokens: int) -> tuple[int, int]:
    """Tiles the fused paged-decode kernel walks over one decode chunk, and
    the tiles a walk over every table entry would take.

    ``kv_lens`` (S,) are the slots' lengths as the chunk was dispatched and
    ``masks`` (steps, S) which slots were active at each step. Step t
    attends over ``kv_lens + 1 + (tokens the slot emitted before t)`` tokens
    of every slot, active or not (the step passes every slot's length), in
    ``tile``-token tiles; a full walk takes ``cdiv(table_tokens, tile)``
    tiles per slot and step. Returns ``(walked, full)``."""
    masks = np.asarray(masks, np.int64)
    before = np.cumsum(masks, axis=0) - masks
    kv = np.asarray(kv_lens, np.int64)[None, :] + 1 + before
    walked = int(np.sum(-(-kv // tile)))
    return walked, masks.size * -(-table_tokens // tile)


class HostCore:
    """Slot-level host scheduler state shared by both engines (no jax)."""

    def __init__(self, *, max_slots: int, max_seq: int, eos_id: int | None = None,
                 steps_per_sync: int = 8, clock=None, max_inflight: int | None = None):
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.steps_per_sync = steps_per_sync
        # SLA clock (DESIGN.md §11): deadlines are absolute values of now().
        # Default clock is the deterministic core tick counter (one tick per
        # decode step or prefill chunk) so scheduler tests and bench traces
        # are machine-portable; an online frontend passes time.monotonic.
        self._clock = clock
        self._ticks = 0
        self.max_inflight = max_inflight

        # host-side slot state (small; shipped to device each chunk)
        self._slots = [self._new_slot() for _ in range(max_slots)]
        self.kv_lens = np.zeros((max_slots,), np.int32)
        self._active = np.zeros((max_slots,), bool)
        self._budget = np.zeros((max_slots,), np.int32)
        self._tokens = np.zeros((max_slots, 1), np.int32)
        self._temperature = np.zeros((max_slots,), np.float32)
        self._top_k = np.zeros((max_slots,), np.int32)
        self._top_p = np.ones((max_slots,), np.float32)

        self._queue = _ReqQueue()
        self._results: dict[int, Generation] = {}
        self.sheds: dict[int, Rejected] = {}  # post-admission deadline sheds
        self._next_uid = 0
        # tokens emitted before a preemption, merged back at finish (paged
        # engines populate it; the slot engine never preempts)
        self._preempt_carry: dict[int, list[int]] = {}
        self._submit_time: dict[int, float] = {}
        self.ttft: dict[int, float] = {}  # uid -> first-token latency in now() units
        # uid -> perf_counter() when the request (or its preempted
        # continuation) entered the queue; admission turns it into queue_wait_s
        self._queued_at: dict[int, float] = {}

        # telemetry for bench_serving
        self.stats = {"decode_steps": 0, "tokens_out": 0, "occupancy_sum": 0.0,
                      "max_active": 0, "prefills": 0, "decode_time": 0.0,
                      "cancelled": 0, "shed": 0, "admitted": 0, "queue_wait_s": 0.0}

    def _new_slot(self):
        return _Slot()

    def now(self) -> float:
        """Current SLA-clock reading: wall clock when one was injected, else
        the deterministic tick counter."""
        return float(self._clock()) if self._clock is not None else float(self._ticks)

    def _validate_request(self, prompt, max_new: int) -> None:
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) >= self.max_seq:
            raise ValueError(f"prompt length {len(prompt)} >= max_seq {self.max_seq}")
        if max_new < 1:
            raise ValueError("max_new must be >= 1")

    # ---------------------------------------------------------------- admission

    def _occupancy(self) -> PoolOccupancy | None:
        return None  # the paged core reports its BlockPool census

    def _in_system(self) -> int:
        """Requests admitted but not finished: queued + occupying a slot."""
        return len(self._queue) + sum(not s.free for s in self._slots)

    def _admission_check(self) -> Rejected | None:
        """Load-shedding gate for new submissions (DESIGN.md §11). Returns a
        structured ``Rejected`` when the request should back off, None when
        it may enter the queue. Never sheds on request *validity* — that is
        ``_validate_request``'s job and is non-retryable."""
        if self.max_inflight is not None and self._in_system() >= self.max_inflight:
            return Rejected(
                "max_inflight",
                detail=f"{self._in_system()} requests in flight >= cap {self.max_inflight}",
                retryable=True, backoff_hint=float(self.steps_per_sync),
                occupancy=self._occupancy(),
            )
        return None

    def _as_request(self, prompt, max_new, sampling, priority,
                    deadline) -> Request:
        """Normalize the two submission forms — a ``Request`` value, or the
        legacy ``(prompt, max_new, ...)`` spread — into one canonical
        ``Request`` with an int-tuple prompt. The uid stays -1 here;
        ``_enqueue`` assigns it at admission."""
        if isinstance(prompt, Request):
            if max_new is not None:
                raise ValueError("pass either a Request or (prompt, max_new), not both")
            r = prompt
        else:
            if max_new is None:
                raise ValueError("max_new is required when submitting a raw prompt")
            r = Request(prompt, max_new, sampling, int(priority), deadline)
        toks = tuple(int(t) for t in np.asarray(r.prompt).reshape(-1))
        return dataclasses.replace(r, prompt=toks)

    def _enqueue(self, req: Request) -> int:
        uid = self._next_uid
        self._next_uid += 1
        req = dataclasses.replace(req, uid=uid)
        self._queue.append(req)
        self._submit_time[uid] = self.now()
        self._queued_at[uid] = time.perf_counter()
        return uid

    def _note_admitted(self, uid: int) -> None:
        """A queued request moved into a slot: count it and the host-clock
        seconds it waited in the queue since it last entered it."""
        now = time.perf_counter()
        self.stats["queue_wait_s"] += now - self._queued_at.pop(uid, now)
        self.stats["admitted"] += 1

    def submit(self, prompt, max_new: int | None = None, sampling=GREEDY, *,
               priority: int = 0, deadline: float | None = None) -> int:
        """Admit or die: malformed requests raise ValueError, shed load raises
        ``AdmissionRejected`` (offline callers treat both as fatal); returns
        the uid. Frontends wanting structured outcomes use ``try_submit``.

        Accepts either a ``Request`` value (the canonical submission path) or
        the legacy ``(prompt, max_new, ...)`` spread."""
        req = self._as_request(prompt, max_new, sampling, priority, deadline)
        self._validate_request(req.prompt, req.max_new)
        rej = self._admission_check()
        if rej is not None:
            raise AdmissionRejected(rej)
        return self._enqueue(req)

    def try_submit(self, prompt, max_new: int | None = None, sampling=GREEDY, *,
                   priority: int = 0, deadline: float | None = None) -> int | Rejected:
        """Non-raising admission for the serving front: returns a uid, or a
        ``Rejected`` — non-retryable for malformed requests, retryable with a
        backoff hint for shed load. Accepts a ``Request`` or the legacy
        kwarg spread, like ``submit``."""
        try:
            req = self._as_request(prompt, max_new, sampling, priority, deadline)
            self._validate_request(req.prompt, req.max_new)
        except (ValueError, TypeError) as e:
            return Rejected("invalid", detail=str(e), retryable=False,
                            occupancy=self._occupancy())
        rej = self._admission_check()
        if rej is not None:
            return rej
        return self._enqueue(req)

    @property
    def num_active(self) -> int:
        return int(self._active.sum())

    @property
    def num_queued(self) -> int:
        return len(self._queue)

    def has_work(self) -> bool:
        return (bool(self._queue) or self.num_active > 0
                or any(not s.free and s.prefilling for s in self._slots))

    def _free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self._slots) if s.free]

    # ------------------------------------------------- cancellation / streaming

    def cancel(self, uid: int) -> bool:
        """Abort a request wherever it lives — queued, prefilling, or decoding.
        Every block it holds is released back to the pool (the paged
        ``_finish`` path), tokens generated so far land in results with
        finish_reason "cancelled", and a queued preempt-continuation resolves
        to its carried tokens. Returns False for unknown/finished uids (a
        disconnect racing a finish is not an error)."""
        req = self._queue.remove_uid(uid)
        if req is not None:
            carry = self._preempt_carry.pop(uid, [])
            self._results[uid] = Generation(uid, carry, "cancelled")
            self._submit_time.pop(uid, None)
            self._queued_at.pop(uid, None)
            self.stats["cancelled"] += 1
            return True
        for slot, s in enumerate(self._slots):
            if not s.free and s.uid == uid:
                self._cancel_slot(slot)
                self._submit_time.pop(uid, None)
                self.stats["cancelled"] += 1
                return True
        return False

    def _cancel_slot(self, slot: int) -> None:
        self._finish(slot, "cancelled")

    def tokens_so_far(self, uid: int) -> list[int]:
        """Every token generated for ``uid`` so far (preempt carry included) —
        the frontend's streaming source between chunks. Finished requests
        report their final tokens; unknown uids report []."""
        carry = self._preempt_carry.get(uid, [])
        for i, s in enumerate(self._slots):
            if not s.free and s.uid == uid:
                return list(carry) + list(s.generated)
        if uid in self._results:
            return list(self._results[uid].tokens)
        return list(carry)  # queued (possibly a preempted continuation)

    def take_finished(self) -> dict[int, Generation]:
        """Drain completed results (finish/EOS/cancel) since the last call."""
        out, self._results = self._results, {}
        return out

    def take_shed(self) -> dict[int, Rejected]:
        """Drain post-admission deadline sheds since the last call."""
        out, self.sheds = self.sheds, {}
        return out

    def _shed_expired(self) -> int:
        """Shed queued requests whose TTFT deadline already passed — running
        their prefill can only waste pool blocks the punctual requests need.
        Continuations of preempted requests are exempt: their admission
        decision was already made (and a decoding request's TTFT was met).
        Shed uids land in ``sheds`` as retryable ``Rejected`` responses."""
        if not self._queue:
            return 0
        now = self.now()
        expired = [r.uid for r in self._queue
                   if r.deadline is not None and r.deadline <= now
                   and r.uid not in self._preempt_carry]
        for uid in expired:
            req = self._queue.remove_uid(uid)
            self.sheds[uid] = Rejected(
                "deadline",
                detail=f"TTFT deadline {req.deadline:g} expired at clock {now:g}",
                retryable=True, backoff_hint=float(self.steps_per_sync),
                occupancy=self._occupancy(), uid=uid,
            )
            self._submit_time.pop(uid, None)
            self._queued_at.pop(uid, None)
            self.stats["shed"] += 1
        return len(expired)

    def _complete_first(self, slot: int, req: Request, first: int) -> None:
        """Record the first generated token and flip the slot into decode
        state (or finish immediately on EOS / budget 1). The *sampling* of
        that token from prefill logits is device work (the engine's
        ``_sample_first``); this is the host transition it feeds."""
        sp = req.sampling
        self.stats["tokens_out"] += 1
        t0 = self._submit_time.pop(req.uid, None)
        if t0 is not None and req.uid not in self.ttft:  # continuations keep the original TTFT
            self.ttft[req.uid] = self.now() - t0
        s = self._slots[slot]
        s.uid, s.generated = req.uid, [first]
        self.kv_lens[slot] = len(req.prompt)
        self._tokens[slot, 0] = first
        self._temperature[slot] = sp.temperature
        self._top_k[slot] = sp.top_k
        self._top_p[slot] = sp.top_p
        self._budget[slot] = req.max_new - 1
        hit_eos = self.eos_id is not None and first == self.eos_id
        if hit_eos or req.max_new == 1:
            self._finish(slot, "eos" if hit_eos else "length")
        else:
            self._active[slot] = True

    def _finish(self, slot: int, reason: str):
        s = self._slots[slot]
        self._results[s.uid] = Generation(s.uid, list(s.generated), reason)
        self._slots[slot] = self._new_slot()
        self._active[slot] = False

    def _pick_sampler(self) -> str:
        """Cheapest chunk sampler covering every active slot's params."""
        act = self._active
        if (self._temperature[act] <= 0.0).all():
            return "greedy"
        if (self._top_k[act] == 0).all() and (self._top_p[act] >= 1.0).all():
            return "temperature"
        return "full"

    def _clamp_steps(self, steps: int | None) -> int:
        # clamp to the largest remaining budget among active slots: a tail
        # chunk never runs whole-model decode steps nobody can consume (at
        # most steps_per_sync distinct scan lengths ever compile)
        max_budget = int(self._budget[self._active].max())
        return min(steps or self.steps_per_sync, max(max_budget, 1))

    def _absorb_chunk(self, tokens, lens, active, budget, emitted, masks, was_active) -> int:
        """Pull a finished decode chunk's state back to host: emissions per
        slot, occupancy telemetry, and finish transitions for slots that
        went inactive inside the chunk."""
        self._tokens = np.array(tokens)
        self.kv_lens = np.array(lens)
        self._active = np.array(active)
        self._budget = np.array(budget)
        emitted = np.asarray(emitted)  # (steps, S)
        masks = np.asarray(masks)
        n_out = 0
        self._ticks += emitted.shape[0]  # SLA clock: one tick per decode step
        for t in range(emitted.shape[0]):
            self.stats["decode_steps"] += 1
            self.stats["occupancy_sum"] += float(masks[t].sum())
            self.stats["max_active"] = max(self.stats["max_active"], int(masks[t].sum()))
            for slot in np.nonzero(masks[t])[0]:
                self._slots[slot].generated.append(int(emitted[t, slot]))
                n_out += 1
        self.stats["tokens_out"] += n_out
        for slot in range(self.max_slots):
            if was_active[slot] and not self._active[slot]:
                last = self._slots[slot].generated[-1]
                hit_eos = self.eos_id is not None and last == self.eos_id
                self._finish(slot, "eos" if hit_eos else "length")
        return n_out

    def step_chunk(self, steps: int | None = None) -> int:  # pragma: no cover
        raise NotImplementedError("the engine layer (runtime/engine.py) drives device chunks")

    def run(self) -> dict[int, Generation]:
        """Drain the queue and all active slots; returns {uid: Generation}."""
        while self.has_work():
            self.step_chunk()
        out, self._results = self._results, {}
        return out

    @property
    def mean_occupancy(self) -> float:
        steps = max(self.stats["decode_steps"], 1)
        return self.stats["occupancy_sum"] / steps


# ===================================================================== paged


class EngineCore(HostCore):
    """Host scheduler for the block-paged engine (DESIGN.md §3/§9).

    Owns every paged scheduling decision with zero device state: the
    ``BlockPool`` allocator + refcounted prefix index, the per-slot block
    tables (host truth in ``_slots[i].table``, device mirror in ``_tables``),
    prefix-hash admission, chunked-prefill planning, the preempt-and-
    recompute policy, and the int8 fresh-block scale-reset queue.

    Device effects are *queued, never performed*: copy-on-write forks append
    ``(src, dst)`` to ``pending_copies`` and fresh allocations accumulate in
    ``_fresh_blocks`` — ``runtime.engine.PagedEngine`` drains both through
    the jitted functions in ``runtime/device_step.py`` before any launch
    that reads or writes the pool. Draining order matters and is part of the
    contract: copies first (in queue order — a queued copy's source may be
    released and recycled afterwards, and a later fork may target it), then
    scale resets (so a stale queued copy can never resurrect a recycled
    block's old quantization grid).
    """

    def __init__(self, *, max_slots: int, max_seq: int, block_size: int = 16,
                 prefill_chunk: int = 32, num_blocks: int | None = None,
                 eos_id: int | None = None, steps_per_sync: int = 8,
                 quantized: bool = False, clock=None, max_inflight: int | None = None,
                 admit_watermark: float | None = None, state_blocks: bool = False):
        # explicit base call: PagedEngine linearizes as (EngineCore, Engine,
        # HostCore) and Engine.__init__ must not run on this path
        HostCore.__init__(self, max_slots=max_slots, max_seq=max_seq, eos_id=eos_id,
                          steps_per_sync=steps_per_sync, clock=clock,
                          max_inflight=max_inflight)
        # shed new work once this fraction of pool blocks is live (None = off):
        # admission control before the allocator thrashes into eviction storms
        self.admit_watermark = admit_watermark
        self.block_size = block_size
        self.prefill_chunk = prefill_chunk
        self.blocks_per_table = -(-max_seq // block_size)
        if num_blocks is None:
            num_blocks = 1 + max_slots * self.blocks_per_table  # +1: reserved null block
        self.num_blocks = num_blocks
        self.pool = BlockPool(num_blocks, block_size)
        self._tables = np.full((max_slots, self.blocks_per_table), NULL_BLOCK, np.int32)
        self._quantized = quantized
        # SSM/hybrid state pools checkpoint recurrent state at *block*
        # granularity and decode overwrites the partial tail block in place,
        # so only full blocks may enter the prefix index and cache hits must
        # be block-aligned (DESIGN.md §13)
        self.state_blocks = state_blocks

        self.stats.update(prompt_tokens=0, prefix_hit_tokens=0,
                          prefill_tokens=0, prefill_chunks=0, preemptions=0,
                          decode_kv_tiles=0, decode_kv_tiles_full=0,
                          moe_rows=0, moe_experts_touched=0, moe_expert_slots=0)
        # CoW device copies planned but not yet performed: (src, dst) pairs in
        # the order they must execute (see class docstring)
        self.pending_copies: list[tuple[int, int]] = []
        # blocks handed out by the pool since the last device launch whose
        # scale planes must be reset to "unset" before anything writes them
        # (recycled/evicted blocks carry a stale grid otherwise) — int8 only.
        # A set: an id can be released (admission rollback, preemption) and
        # re-allocated before the flush, and a CoW fork destination must be
        # *removed* (its valid scales arrive with the copied payload)
        self._fresh_blocks: set[int] = set()
        # speculative-decoding branches in flight, slot -> [SpecBranch]
        # (DESIGN.md §12). Branch blocks are invisible to the slot table
        # until commit; every fault path (_preempt, _finish, cancel) must
        # abort them first so their refcounts and queued fork copies die
        # with the slot.
        self._branches: dict[int, list[SpecBranch]] = {}
        self.stats.update(spec_rounds=0, spec_drafted=0, spec_accepted=0,
                          spec_emitted=0)

    def _new_slot(self):
        return _PagedSlot()

    def _validate_request(self, prompt, max_new: int) -> None:
        super()._validate_request(prompt, max_new)
        worst = min(len(prompt) + max_new, self.max_seq)
        need = -(-worst // self.block_size)
        if need > self.pool.num_blocks - 1:
            raise ValueError(
                f"request needs up to {need} blocks of {self.block_size} but the pool "
                f"has {self.pool.num_blocks - 1} usable blocks"
            )

    def _occupancy(self) -> PoolOccupancy:
        return self.pool.occupancy()

    def _admission_check(self) -> Rejected | None:
        rej = super()._admission_check()
        if rej is not None:
            return rej
        if self.admit_watermark is not None:
            occ = self.pool.occupancy()
            if occ.live_fraction >= self.admit_watermark:
                return Rejected(
                    "pool_pressure",
                    detail=(f"{occ.num_live}/{occ.num_blocks} blocks live "
                            f">= watermark {self.admit_watermark:g}"),
                    retryable=True, backoff_hint=float(self.steps_per_sync),
                    occupancy=occ,
                )
        return None

    def _cancel_slot(self, slot: int) -> None:
        # queued CoW copies into blocks this cancel releases must never run:
        # the dst can be recycled to another slot before the next drain, and
        # a stale copy would overwrite its payload (the live engine drains
        # copies eagerly so this is a host-only-core concern, but the chaos
        # harness runs exactly that configuration)
        doomed = set(self._slots[slot].table)
        if doomed and self.pending_copies:
            self.pending_copies = [(s, d) for (s, d) in self.pending_copies
                                   if d not in doomed]
        super()._cancel_slot(slot)

    # -------------------------------------------------------------- block ops

    def _make_writable(self, slot: int, bi: int) -> None:
        """CoW: before appending into table entry ``bi``, fork a shared block
        (refcount > 1) and queue its payload copy; exclusive blocks append in
        place (appends land beyond the hashed token count — DESIGN.md §3)."""
        s = self._slots[slot]
        blk = s.table[bi]
        if self.pool.writable(blk):
            return
        new = self.pool.fork(blk)
        # the fork gets payload AND scales copied, so it must NOT be pending
        # a scale reset: fork() allocates internally and can hand back an id
        # that was _alloc_fresh'd and then released (rollback/preemption)
        # while still queued — flushing that id after this copy would zero
        # the fork's grid and corrupt its dequant
        self._fresh_blocks.discard(new)
        self.pending_copies.append((blk, new))
        s.table[bi] = new
        self._tables[slot, bi] = new

    def take_pending_copies(self) -> list[tuple[int, int]]:
        """Hand the queued CoW copies to the device layer (clears the queue)."""
        out, self.pending_copies = self.pending_copies, []
        return out

    def _ensure_decode_blocks(self, slot: int, steps: int) -> None:
        """Pre-chunk allocation: positions [lens, lens+writes) must have
        writable blocks before the jitted chunk launches (tables are fixed
        for the whole chunk). ``writes`` is bounded by the slot's own budget
        so a nearly-finished slot never allocates blocks it cannot write;
        blocks over-allocated for an EOS mid-chunk are reclaimed at finish."""
        s = self._slots[slot]
        lens = int(self.kv_lens[slot])
        writes = min(steps, int(self._budget[slot]) + 1)  # +1: the finishing write
        last_pos = min(lens + writes, self.max_seq) - 1
        bi0 = lens // self.block_size
        if bi0 < len(s.table):
            self._make_writable(slot, bi0)
        need = last_pos // self.block_size + 1
        while len(s.table) < need:
            blk = self._alloc_fresh()
            self._tables[slot, len(s.table)] = blk
            s.table.append(blk)

    def _alloc_fresh(self) -> int:
        """Pool alloc that queues the block for a scale reset (int8 pools):
        a block off the free list or evicted from the LRU carries a stale
        quantization grid that must not seed the next write."""
        blk = self.pool.alloc()
        if self._quantized:
            self._fresh_blocks.add(blk)
        return blk

    def take_fresh_scale_ids(self) -> list[int]:
        """Blocks allocated since the last device launch whose scale planes
        the device layer must reset before any jitted write (clears the
        queue; sorted for a deterministic device call)."""
        fresh = sorted(self._fresh_blocks)
        self._fresh_blocks = set()
        return fresh

    def _preempt(self, slot: int) -> None:
        """Release a live slot's blocks under pool pressure and requeue the
        request for recompute: the continuation prompt is the original prompt
        plus everything generated so far, so prefilling it reproduces the
        decode state exactly (greedy continuation is bit-identical — chunked
        prefill is exact, DESIGN.md §3), and its prompt blocks usually hit
        the prefix cache the preempted slot just parked. Works on decoding
        *and* mid-prefill slots (priority admission evicts either); the
        continuation keeps the request's priority class and deadline."""
        self.abort_spec_branches(slot)  # branch blocks + queued fork copies die first
        s = self._slots[slot]
        req = s.req
        done = list(s.generated)
        # mid-prefill: nothing sampled yet, the continuation is the original
        # request verbatim (its _budget is stale — never set for this slot)
        remaining = req.max_new if s.prefilling else int(self._budget[slot])
        carry = self._preempt_carry.pop(req.uid, []) + done
        if carry:  # no empty entries: _shed_expired treats presence as TTFT-met
            self._preempt_carry[req.uid] = carry
        cont = Request(req.prompt + tuple(done), remaining, req.sampling,
                       req.priority, req.deadline, uid=req.uid)
        doomed = set(s.table)
        if doomed and self.pending_copies:  # same staleness hazard as _cancel_slot
            self.pending_copies = [(a, b) for (a, b) in self.pending_copies
                                   if b not in doomed]
        for blk in s.table:
            self.pool.release(blk)
        self._tables[slot, :] = NULL_BLOCK
        self._slots[slot] = self._new_slot()
        self._active[slot] = False
        self.stats["preemptions"] += 1
        self._queued_at[req.uid] = time.perf_counter()
        self._queue.appendleft(cont)  # continuation bypasses _validate_request:
        # its prompt may legitimately reach max_seq (finishes right after prefill)

    def _victim_rank(self, j: int):
        """Sort key for preemption policy (DESIGN.md §11): under max(), the
        victim is the least-urgent occupied slot — highest priority value
        first, then the most deadline slack (no deadline = infinite slack),
        then the newest uid. With every request at the defaults this reduces
        exactly to the pre-SLA policy (preempt the newest)."""
        s = self._slots[j]
        req = s.req
        prio = req.priority if req is not None else 0
        slack = req.deadline if (req is not None and req.deadline is not None) else float("inf")
        return (prio, slack, s.uid)

    def _reserve_chunk_blocks(self, steps: int) -> None:
        """Ensure every active slot can write its share of the coming chunk,
        most-urgent slots reserving first. Exhaustion preempts the least
        urgent active slot per ``_victim_rank`` (its blocks free up, its
        request recomputes later) instead of crashing the engine — honest
        back-pressure on undersized pools."""
        order = sorted((i for i in range(self.max_slots) if self._active[i]),
                       key=self._victim_rank)
        for i in order:
            # a slot later in the order may have been preempted for an earlier one
            while self._active[i]:
                try:
                    self._ensure_decode_blocks(i, steps)
                    break
                except PoolExhausted:
                    victims = [j for j in range(self.max_slots) if self._active[j]]
                    victim = max(victims, key=self._victim_rank)
                    if victim == i and len(victims) == 1:
                        raise PoolExhausted(
                            f"cannot grow KV for the only active request (uid "
                            f"{self._slots[i].uid}): pool of {self.pool.num_blocks - 1} "
                            f"usable blocks is too small for max_seq {self.max_seq}",
                            retryable=False, occupancy=self.pool.occupancy(),
                        ) from None
                    self._preempt(victim)

    # ------------------------------------------------------------- scheduling

    def _preempt_for(self, req: Request) -> int | None:
        """Occupied slot to evict so the strictly-more-urgent ``req`` can run:
        the least-urgent per ``_victim_rank``, and only when its priority
        class is strictly less urgent than ``req``'s — equal-class arrivals
        wait their turn (no churn within a class). None when nothing
        qualifies. Mid-prefill slots are eligible victims: they hold blocks
        and haven't produced a token yet, so they are the cheapest to redo."""
        occupied = [j for j in range(self.max_slots) if not self._slots[j].free]
        if not occupied:
            return None
        victim = max(occupied, key=self._victim_rank)
        vreq = self._slots[victim].req
        if vreq is None or vreq.priority <= req.priority:
            return None
        return victim

    def _admit(self) -> int:
        """Shed expired deadlines, then match prefix hashes, retain hits,
        allocate the rest of the prompt's blocks, and park the slot in
        chunked-prefill state. The queue is priority-ordered, so the head is
        always the most urgent waiter; when it is blocked on a slot or on
        pool blocks held by a strictly-less-urgent class, that victim is
        preempted (deadline-aware ``_victim_rank``). Otherwise pool
        exhaustion rolls the request back into the queue (back-pressure)."""
        self._shed_expired()
        admitted = 0
        free = self._free_slots()
        while self._queue:
            req = self._queue[0]
            if not free:
                victim = self._preempt_for(req)
                if victim is None:
                    break
                self._preempt(victim)
                free = self._free_slots()
            hashes = chain_hashes(req.prompt, self.block_size)
            table, cached = [], 0
            for h, n in hashes:
                blk = self.pool.lookup(h)
                if blk is None:
                    break
                table.append(blk)
                cached += n
            # always re-prefill at least the last prompt token: sampling needs
            # its logits (a fully-cached prompt has KV but no logits)
            if self.state_blocks:
                # state planes checkpoint at block boundaries only, and decode
                # mutates partial tail blocks in place — a prefix hit is only
                # usable up to the last *full* block strictly inside the
                # prompt. Release over-matched blocks (lookup retained them).
                limit = ((len(req.prompt) - 1) // self.block_size) * self.block_size
                keep = min(cached, limit) // self.block_size
                for b in table[keep:]:
                    self.pool.release(b)
                del table[keep:]
                cached = keep * self.block_size
            else:
                cached = min(cached, len(req.prompt) - 1)
            try:
                while len(table) < len(hashes):
                    table.append(self._alloc_fresh())
            except PoolExhausted:
                for b in table:
                    self.pool.release(b)
                victim = self._preempt_for(req)
                if victim is None:
                    break
                self._preempt(victim)  # frees its blocks; retry the same head
                free = self._free_slots()
                continue
            self._queue.popleft()
            self._note_admitted(req.uid)
            slot = free.pop(0)
            s = self._slots[slot]
            s.uid, s.req, s.table, s.hashes = req.uid, req, table, hashes
            s.filled = s.cached = cached
            s._prefilling = True
            self._tables[slot, :] = NULL_BLOCK
            self._tables[slot, : len(table)] = table
            self.stats["prompt_tokens"] += len(req.prompt)
            self.stats["prefix_hit_tokens"] += cached
            admitted += 1
        return admitted

    def plan_prefill_chunk(self, slot: int) -> PrefillChunkPlan:
        """Plan the next ``prefill_chunk``-token chunk for a prefilling slot:
        CoW-protect the chunk's target blocks (copies are queued) and compute
        the padded token window plus per-row scatter targets. Does not
        advance ``filled`` — ``commit_prefill_chunk`` does, after the device
        step ran the plan."""
        s = self._slots[slot]
        req = s.req
        bs = self.block_size
        n = min(self.prefill_chunk, len(req.prompt) - s.filled)
        start = s.filled
        for bi in range(start // bs, (start + n - 1) // bs + 1):
            self._make_writable(slot, bi)
        C = self.prefill_chunk
        toks = np.zeros((1, C), np.int32)
        toks[0, :n] = req.prompt[start : start + n]
        blk_t = np.full((C,), NULL_BLOCK, np.int32)
        off_t = np.arange(C, dtype=np.int32) % bs  # spread padded-row writes in the null block
        for i in range(n):
            pos = start + i
            blk_t[i] = s.table[pos // bs]
            off_t[i] = pos % bs
        return PrefillChunkPlan(slot, toks, start, n, blk_t, off_t)

    def commit_prefill_chunk(self, slot: int, n: int) -> bool:
        """Host transitions after a prefill chunk ran on device: advance
        ``filled``, publish fully-materialized hashed blocks to the prefix
        index, and report whether the prompt just completed (the engine then
        samples the first token from the chunk's logits)."""
        s = self._slots[slot]
        s.filled += n
        self._ticks += 1  # SLA clock: one tick per prefill chunk
        self.stats["prefill_chunks"] += 1
        self.stats["prefill_tokens"] += n
        bs = self.block_size
        for bi, (h, ntok) in enumerate(s.hashes):
            # state pools: partial tail blocks are decode-mutable in place, so
            # only full blocks may ever enter the prefix index (DESIGN.md §13)
            if bi * bs + ntok <= s.filled and (not self.state_blocks or ntok == bs):
                self.pool.register(h, s.table[bi])
        if s.filled == len(s.req.prompt):
            s._prefilling = False
            self.stats["prefills"] += 1
            return True
        return False

    # ------------------------------------------------- speculative decoding

    def plan_spec_round(self, slot: int, drafts) -> SpecVerifyPlan:
        """Fork a draft branch and plan its verify window (DESIGN.md §12).

        The branch owns fresh blocks for every table index the window
        ``[start, start + k]`` touches. When the slot's tail block is
        partially filled, the branch's first block is a CoW *read-fork* of
        it — payload copy queued on ``pending_copies``, the slot's own
        reference left untouched — so a rejected round releases the copy and
        the slot is exactly as it was. Raises ``PoolExhausted`` with full
        rollback (no branch registered, no blocks leaked) when the pool
        cannot cover the window; the engine retries with k=0 or preempts.
        """
        s = self._slots[slot]
        bs = self.block_size
        drafts = tuple(int(d) for d in drafts)
        k = len(drafts)
        L = int(self.kv_lens[slot])
        assert self._active[slot] and not s.prefilling, "spec round needs a decoding slot"
        assert L + k < self.max_seq, "k_eff clamp must keep the window inside max_seq"
        bi0 = L // bs
        forked = (L % bs) != 0
        # active decode slots always satisfy len(table) == ceil(L / bs): spec
        # rounds grow the table themselves and decode chunks never run on a
        # spec engine, so over-allocation (an EOS mid-chunk) cannot occur
        assert len(s.table) == bi0 + (1 if forked else 0), (
            f"slot {slot} table length {len(s.table)} inconsistent with kv_len {L}"
        )
        bik = (L + k) // bs
        table: list[int] = []
        try:
            for bi in range(bi0, bik + 1):
                blk = self._alloc_fresh()
                if bi == bi0 and forked:
                    # read-fork of the partially-filled tail: the copied
                    # payload carries valid scales, so the block must not sit
                    # in the fresh-reset queue (same hazard as _make_writable)
                    self._fresh_blocks.discard(blk)
                    self.pending_copies.append((s.table[bi0], blk))
                table.append(blk)
        except PoolExhausted:
            self._release_branch_blocks(table)
            raise
        br = SpecBranch(slot, s.uid, drafts, L, bi0, table, forked)
        self._branches.setdefault(slot, []).append(br)
        # window rows: the pending token (sampled last round, KV not yet
        # written) then the k drafts — C = k + 1 rows at positions [L, L + k]
        C = k + 1
        toks = np.zeros((1, C), np.int32)
        toks[0, 0] = self._tokens[slot, 0]
        toks[0, 1:] = drafts
        win = np.full((self.blocks_per_table,), NULL_BLOCK, np.int32)
        win[:bi0] = s.table[:bi0]
        win[bi0 : bik + 1] = table
        blk_t = np.zeros((C,), np.int32)
        off_t = np.zeros((C,), np.int32)
        for i in range(C):
            pos = L + i
            blk_t[i] = table[pos // bs - bi0]
            off_t[i] = pos % bs
        return SpecVerifyPlan(slot, br, toks, L, win, blk_t, off_t)

    def commit_spec_round(self, plan: SpecVerifyPlan, verified) -> SpecCommit:
        """Adjudicate a verify round: greedy accept rule, splice the winning
        branch prefix into the slot table, release the losing tail. The
        committed tail block is always a branch block (the branch covers
        position ``start`` onward), so ``keep >= 1`` and the slot's old tail
        — if the branch forked it — is released here: safe, because the
        engine drained the fork copy before the verify launched."""
        br = plan.branch
        slot = plan.slot
        s = self._slots[slot]
        bs = self.block_size
        verified = [int(v) for v in np.asarray(verified).reshape(-1)]
        k = len(br.drafts)
        assert len(verified) == k + 1, "verify must return one token per window row"
        a = greedy_accept_length(br.drafts, verified)
        L = br.start
        bi0 = br.bi0
        tail_bi = (L + a) // bs
        keep = tail_bi - bi0 + 1
        self._release_branch_blocks(br.table[keep:])
        kept = br.table[:keep]
        if br.forked:
            old = s.table[bi0]
            s.table[bi0] = kept[0]
            self.pool.release(old)
            s.table.extend(kept[1:])
        else:
            s.table.extend(kept)
        self._tables[slot, bi0 : bi0 + keep] = kept
        self._branches[slot].remove(br)
        if not self._branches[slot]:
            del self._branches[slot]
        self.stats["spec_rounds"] += 1
        self.stats["spec_drafted"] += k
        self.stats["spec_accepted"] += a
        emitted = list(br.drafts[:a]) + [verified[a]]
        # int4 tail hygiene: rejected rows at positions [L+a+1, L+k] seeded
        # immutable sub-block codes; when the first of them shares the kept
        # tail block, the engine must zero codes past the committed rows
        trim_tail = a < k and (L + a + 1) // bs == tail_bi
        return SpecCommit(slot, emitted, a, kept[-1], (L + a) % bs + 1, trim_tail)

    def absorb_spec_round(self, slot: int, emitted: list[int]) -> int:
        """Pull one committed spec round into host state: each emitted token
        replays the decode-scan transition (append, kv_len++, budget--,
        pending-token update, finish checks in scan order) so greedy spec is
        bit-identical to vanilla including where generation stops — later
        emissions past a finish are truncated, exactly the tokens vanilla
        would never have produced. One round = one device step = one SLA
        tick, which is what makes steps-per-token the speedup metric."""
        s = self._slots[slot]
        self._ticks += 1
        self.stats["decode_steps"] += 1
        self.stats["occupancy_sum"] += 1.0  # spec rounds run one slot per launch
        self.stats["max_active"] = max(self.stats["max_active"], self.num_active)
        n_out = 0
        finished = None
        for t in emitted:
            t = int(t)
            s.generated.append(t)
            self.kv_lens[slot] += 1
            self._budget[slot] -= 1
            self._tokens[slot, 0] = t
            n_out += 1
            if self.eos_id is not None and t == self.eos_id:
                finished = "eos"
                break
            if self._budget[slot] <= 0 or self.kv_lens[slot] >= self.max_seq:
                finished = "length"
                break
        self.stats["tokens_out"] += n_out
        self.stats["spec_emitted"] += n_out
        if finished is not None:
            self._finish(slot, finished)
        return n_out

    def _release_branch_blocks(self, blocks) -> None:
        """Release branch-owned blocks, first purging any queued CoW copy
        whose destination is one of them: the id can be recycled before the
        next drain, and a stale fork copy landing in it would corrupt the
        new owner (the drain-ordering hazard of DESIGN.md §9). Released ids
        stay in ``_fresh_blocks`` — a queued reset on a freed block is
        harmless, and the id re-entering via alloc needs the reset anyway."""
        doomed = set(blocks)
        if doomed and self.pending_copies:
            self.pending_copies = [(a, b) for (a, b) in self.pending_copies
                                   if b not in doomed]
        for blk in blocks:
            self.pool.release(blk)

    def abort_spec_branches(self, slot: int) -> int:
        """Kill every in-flight branch of ``slot`` (losing sibling, cancel,
        preemption, mid-verify PoolExhausted): all branch blocks release and
        their queued fork copies are purged. The slot's own table is
        untouched — a read-fork never dropped the slot's references."""
        branches = self._branches.pop(slot, [])
        for br in branches:
            self._release_branch_blocks(br.table)
        return len(branches)

    def _finish(self, slot: int, reason: str):
        # in-flight spec branches die with the slot (cancel mid-verify, EOS
        # truncation): their blocks and queued fork copies must go before the
        # slot's own references drop, or a recycled dst could eat a stale copy
        self.abort_spec_branches(slot)
        s = self._slots[slot]
        for blk in s.table:
            self.pool.release(blk)
        self._tables[slot, :] = NULL_BLOCK
        carry = self._preempt_carry.pop(s.uid, None)
        super()._finish(slot, reason)
        if carry:  # tokens generated before a preemption lead the final answer
            g = self._results[s.uid]
            self._results[s.uid] = Generation(g.uid, carry + g.tokens, g.finish_reason)

    # -------------------------------------------------------------- telemetry

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of submitted prompt tokens served from the prefix cache."""
        return self.stats["prefix_hit_tokens"] / max(self.stats["prompt_tokens"], 1)

    @property
    def live_kv_tokens(self) -> int:
        """Tokens of KV currently materialized for unfinished requests."""
        total = 0
        for i, s in enumerate(self._slots):
            if s.free:
                continue
            total += s.filled if s.prefilling else int(self.kv_lens[i])
        return total
