"""Continuous-batching decode engines: slot-ragged and block-paged KV caches.

The serving problem EXAQ targets (paper §4: attention-heavy decode) is only
won at the *runtime* level: many concurrent requests of different lengths
must share one jitted step, or the kernel savings drown in per-request
dispatch and padding waste (cf. QUIK/SoftmAP — low-bit inference pays off
when the surrounding runtime is batched and fused).

This module is the glue layer of the host/device split (DESIGN.md §9):

  * ``runtime/engine_core.py`` — every scheduling decision (slot table,
    BlockPool allocator, prefix cache, admission, preempt-and-recompute) as
    plain Python + numpy; imports no jax.
  * ``runtime/device_step.py`` — every jitted function, operating on an
    explicitly mesh-sharded cache/pool pytree.
  * here — ``Engine`` and ``PagedEngine`` wire core plans into device calls
    and absorb device results back into core state, and
    ``DataParallelEngine`` runs independent paged replicas over disjoint
    device subsets behind one shared admission queue.

``Engine`` — slot cache (PR 1 baseline, kept as the parity oracle):

  * Slot cache   — fixed (L, max_slots, KV, max_seq, Dh) K/V buffers plus a
                   per-slot ``kv_lens`` vector. Shapes never change, so the
                   decode step compiles exactly once; raggedness lives in the
                   lengths, and ``attention_decode_ragged`` masks/writes per
                   slot (DESIGN.md §Serving).
  * Scheduler    — requests queue up host-side; free slots are filled by a
                   bucketed single-request prefill (padded to a power-of-two
                   length; the true length picks the logits row), finished
                   slots (EOS / token budget / max_seq) are evicted and
                   immediately refilled.
  * Decode chunk — ``steps_per_sync`` decode steps run inside one jitted
                   ``lax.scan``; every step batches ALL active slots through
                   one ragged attention dispatch per layer and one batched
                   sampling dispatch (greedy / temperature / top-k / top-p
                   with per-slot params — runtime/sampling.py).

``PagedEngine`` — block-paged cache (DESIGN.md §3): the slot engine's memory
model scales as ``max_slots x max_seq`` regardless of live lengths and
re-prefills identical prefixes per request. The paged engine replaces the
rectangular buffers with a global block pool (``runtime/kv_pool.py``) plus
per-request block tables:

  * Block pool    — K/V live in (L, num_blocks, KV, block_size, Dh); a slot's
                    cache is the blocks its table names, so memory tracks the
                    sum of live lengths, not slots x max_seq.
  * Prefix reuse  — prompt blocks are published under a rolling chain hash;
                    later requests sharing a prefix retain the cached blocks
                    (refcounted, copy-on-write on append) and skip their
                    prefill entirely.
  * Chunked prefill — prompts prefill in fixed-size chunks interleaved with
                    decode chunks, so a long prompt never stalls the running
                    batch; chunking is bit-exact vs one-shot prefill because
                    the EXAQ histogram combine composes across partitions
                    (DESIGN.md §2/§3).

Families: dense / moe (token-only attention decoders) on both engines, and —
paged only — ssm / hybrid through the architecture-agnostic StatePool
(DESIGN.md §13): the pool pytree carries whatever per-layer plane groups the
model config declares (attention K/V blocks, Mamba2 conv-tail + SSM-state
planes checkpointed per block), the host scheduler treats blocks as blocks,
and MoE routing batches across live slots inside the jitted decode scan.
Audio caches aren't slot-ragged or block-paged; vlm decode would work (its
KV cache is regular) but the engines' prefill builds token-only batches —
admitting vlm needs per-request ``vision_embeds`` plumbing first.
``runtime.serve.generate`` keeps the rectangular loop for those.
"""

from __future__ import annotations

import dataclasses
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span

from repro.kernels.exaq_paged_attention import decode_tile_tokens
from repro.kernels.kv_codec import kv_cache_is_quantized
from repro.runtime import sampling as smp
from repro.runtime.device_step import PagedDeviceStep, SlotDeviceStep
from repro.runtime.engine_core import (
    EngineConfig,
    EngineCore,
    Generation,
    HostCore,
    Request,
    _bucket,
    decode_tile_counts,
    _PagedSlot,
    _Slot,
)
from repro.runtime.kv_pool import (
    NULL_BLOCK,
    BlockPool,
    PoolExhausted,
    PoolStats,
    chain_hashes,
)
from repro.runtime.speculative import NgramDrafter, make_drafter

__all__ = [
    "DataParallelEngine",
    "Engine",
    "EngineConfig",
    "Generation",
    "PagedEngine",
    "Request",
    "resolve_kv_dtype",
]

# re-exported for existing importers; the host halves live in engine_core
_ = (BlockPool, PoolExhausted, chain_hashes, NULL_BLOCK, _Slot, _PagedSlot)

# families whose paged pool carries recurrent-state planes (conv tails + SSM
# heads) checkpointed at block granularity instead of / alongside KV blocks
# (DESIGN.md §13)
STATE_FAMILIES = ("ssm", "hybrid")

# "int4" has no jnp dtype: the string sentinel travels down to the pool
# builder as-is (payload dtype uint8 — DESIGN.md §10). ``runtime.serve``
# re-exports this map for its flag parsing.
KV_DTYPES = {
    "fp32": jnp.float32,
    "fp16": jnp.float16,
    "bf16": jnp.bfloat16,
    "int8": jnp.int8,
    "int4": "int4",
}


def resolve_kv_dtype(name: str):
    """EngineConfig's string ``kv_dtype`` -> device cache dtype (or the
    "int4" string sentinel)."""
    try:
        return KV_DTYPES[name]
    except KeyError:
        raise ValueError(
            f"kv_dtype must be one of {sorted(KV_DTYPES)}, got {name!r}"
        ) from None


def kv_dtype_name(cache_dtype) -> str:
    """Reverse of ``resolve_kv_dtype`` for the legacy ``cache_dtype=`` shims:
    a device dtype (or the "int4" sentinel) -> the EngineConfig string key."""
    if isinstance(cache_dtype, str):
        if cache_dtype in KV_DTYPES:
            return cache_dtype
        raise ValueError(f"unknown cache dtype sentinel {cache_dtype!r}")
    d = jnp.dtype(cache_dtype)
    for name, dt in KV_DTYPES.items():
        if not isinstance(dt, str) and jnp.dtype(dt) == d:
            return name
    raise ValueError(f"unsupported KV cache dtype {cache_dtype!r}")


def _validate_engine_cfg(cfg, cache_dtype, *, paged: bool) -> None:
    if cfg.frontend is not None:
        raise ValueError(
            f"Engine supports token-only decoders, got frontend={cfg.frontend!r} "
            "(frontend models need per-request embeds at prefill)"
        )
    quantized = kv_cache_is_quantized(cache_dtype)
    if cfg.family in STATE_FAMILIES:
        if not paged:
            raise ValueError(
                f"family={cfg.family!r} serves through the paged StatePool "
                "(recurrent state checkpointed per block — DESIGN.md §13); the slot "
                "engine's rectangular cache has no state planes — use PagedEngine"
            )
        if quantized:
            raise ValueError(
                "int8/int4 pools are attention-only (per-block scales — DESIGN.md "
                f"§6/§10); family={cfg.family!r} state planes must stay full-precision"
            )
        if cfg.ssm_chunk != 1:
            raise ValueError(
                f"paged state serving needs ssm_chunk=1 (got {cfg.ssm_chunk}): the "
                "chunked SSD scan reassociates the recurrence, so block-granular "
                "checkpoints would not reproduce rectangular prefill bit-exactly "
                "(DESIGN.md §13) — rebuild the config with ssm_chunk=1"
            )
    elif cfg.family not in ("dense", "moe"):
        raise ValueError(
            f"Engine supports dense/moe attention decoders and paged ssm/hybrid "
            f"state decoders, got family={cfg.family!r} (audio caches aren't "
            "slot-ragged or block-paged)"
        )
    if quantized and not paged:
        raise ValueError(
            "int8/int4 KV are paged-pool storage formats (per-block scales — DESIGN.md "
            "§6/§10); the slot engine's rectangular cache supports fp dtypes only"
        )


_LEGACY_ENGINE_KEYS = frozenset({
    "max_slots", "max_seq", "block_size", "prefill_chunk", "num_blocks",
    "eos_id", "steps_per_sync", "cache_dtype", "seed", "fused",
    "max_inflight", "admit_watermark", "spec_k", "drafter",
})


def _resolve_config(config: EngineConfig | None, legacy_kw: dict, *, cls: str) -> EngineConfig:
    """One construction surface, two spellings: either ``config=EngineConfig``
    (canonical) or the legacy per-field kwargs (deprecated shim). Mixing them
    is an error — a config is a complete statement of the engine shape, and
    silently overriding fields would make two call sites disagree about what
    was served."""
    if config is not None:
        if legacy_kw:
            raise TypeError(
                f"{cls}: pass either config=EngineConfig(...) or the legacy "
                f"per-field kwargs, not both (got {sorted(legacy_kw)})"
            )
        if not isinstance(config, EngineConfig):
            raise TypeError(f"{cls}: config must be an EngineConfig, got {type(config).__name__}")
        return config
    unknown = set(legacy_kw) - _LEGACY_ENGINE_KEYS
    if unknown:
        raise TypeError(f"{cls}: unexpected keyword arguments {sorted(unknown)}")
    if "max_slots" not in legacy_kw or "max_seq" not in legacy_kw:
        raise TypeError(f"{cls}: pass config=EngineConfig(...) (or legacy max_slots=/max_seq=)")
    warnings.warn(
        f"{cls}(max_slots=..., max_seq=..., ...) per-field construction is "
        "deprecated; build an EngineConfig and pass it as the config argument",
        DeprecationWarning, stacklevel=3,
    )
    kw = dict(legacy_kw)
    if "cache_dtype" in kw:
        kw["kv_dtype"] = kv_dtype_name(kw.pop("cache_dtype"))
    return EngineConfig(**kw)


class Engine(HostCore):
    """Continuous-batching serving engine for one model + qstate.

    Typical use::

        eng = Engine(cfg, params, EngineConfig(max_slots=8, max_seq=512, eos_id=2))
        eng.submit(Request([1, 5, 7], max_new=32))
        eng.submit([9, 9], max_new=16, sampling=SamplingParams(temperature=0.8))
        results = eng.run()          # {uid: Generation}

    or incrementally (arrival-driven traces): ``submit`` whenever requests
    arrive, ``step_chunk()`` to advance ``steps_per_sync`` decode steps.
    ``EngineConfig`` is the canonical construction surface; the legacy
    per-field kwargs (``max_slots=..., cache_dtype=...``) survive as a
    deprecated shim.
    """

    def __init__(
        self,
        cfg,
        params,
        config: EngineConfig | None = None,
        *,
        qstate=None,
        mesh=None,
        clock=None,
        **legacy_kw,
    ):
        config = _resolve_config(config, legacy_kw, cls=type(self).__name__)
        cache_dtype = resolve_kv_dtype(config.kv_dtype)
        _validate_engine_cfg(cfg, cache_dtype, paged=isinstance(self, PagedEngine))
        HostCore.__init__(self, max_slots=config.max_slots, max_seq=config.max_seq,
                          eos_id=config.eos_id, steps_per_sync=config.steps_per_sync,
                          clock=clock, max_inflight=config.max_inflight)
        self.config = config
        self._dev = SlotDeviceStep(
            cfg, params, qstate=qstate, max_slots=config.max_slots,
            max_seq=config.max_seq, eos_id=config.eos_id,
            cache_dtype=cache_dtype, mesh=mesh,
        )
        self._bind_device_step()
        self._key = jax.random.PRNGKey(config.seed)
        self._cache_k, self._cache_v = self._dev.init_cache()

    def _bind_device_step(self):
        """Expose the device step's resolved objects under the engine's
        long-standing attribute names (params is the *placed* copy)."""
        self.cfg = self._dev.cfg
        self.params = self._dev.params
        self.model = self._dev.model
        self.qstate = self._dev.qstate
        self.cache_dtype = self._dev.cache_dtype

    def submit(self, prompt, max_new: int | None = None,
               sampling: smp.SamplingParams = smp.GREEDY, *,
               priority: int = 0, deadline: float | None = None) -> int:
        """Submit a ``Request`` (canonical) or the legacy kwarg spread."""
        with span("engine.submit"):
            return super().submit(prompt, max_new, sampling, priority=priority,
                                  deadline=deadline)

    def _sample_first(self, slot: int, req: Request, logits) -> None:
        """Sample the first generated token from prefill logits (device) and
        hand the host transition to the core. The host blocks here until the
        device has produced the token."""
        with span("engine.sample_first", uid=req.uid):
            self._key, sub = jax.random.split(self._key)
            first = self._dev.sample_first(logits, req.sampling, sub)
        self._complete_first(slot, req, first)

    # ------------------------------------------------------------- scheduling

    def _admit(self) -> int:
        """Prefill queued requests into free slots; returns #admitted."""
        admitted = 0
        free = self._free_slots()
        while free and self._queue:
            req = self._queue.popleft()
            self._note_admitted(req.uid)
            slot = free.pop(0)
            with span("engine.prefill", uid=req.uid):
                P = min(_bucket(len(req.prompt)), self.max_seq)
                padded = np.zeros((1, P), np.int32)
                padded[0, : len(req.prompt)] = req.prompt
                logits, ks, vs = self._dev.prefill(padded, [len(req.prompt)])
                self._cache_k, self._cache_v = self._dev.insert(
                    self._cache_k, self._cache_v, ks, vs, slot
                )
                self.stats["prefills"] += 1
                self._sample_first(slot, req, logits)
            admitted += 1
        return admitted

    def step_chunk(self, steps: int | None = None) -> int:
        """Admit + run one jitted decode chunk; returns #tokens emitted.

        Every phase runs under an ``engine.*`` profiler span (nested inside
        ``engine.step``), so a trace can put each device idle gap down to
        what the host was doing; with no profiler running a span costs one
        object."""
        with span("engine.step"):
            return self._step(steps)

    def _step(self, steps: int | None) -> int:
        with span("engine.admit"):
            self._admit()
        if self.num_active == 0:
            return 0
        steps = self._clamp_steps(steps)
        t0 = time.perf_counter()
        with span("engine.decode"):
            out = self._dev.decode_chunk(
                {"k": self._cache_k, "v": self._cache_v},
                self._tokens, self.kv_lens, self._active, self._budget,
                self._temperature, self._top_k, self._top_p, self._key,
                steps=steps, sampler=self._pick_sampler(),
            )
        kv, tokens, lens, active, budget, self._key, emitted, masks = out
        with span("engine.device_wait"):
            jax.block_until_ready(emitted)
        self.stats["decode_time"] += time.perf_counter() - t0
        self._cache_k, self._cache_v = kv["k"], kv["v"]
        was_active = self._active
        with span("engine.absorb"):
            return self._absorb_chunk(tokens, lens, active, budget, emitted, masks,
                                      was_active)


# ===================================================================== paged


class PagedEngine(EngineCore, Engine):
    """Continuous batching over a block-paged KV cache (DESIGN.md §3).

    Same public surface as ``Engine`` (submit / step_chunk / run), same
    sampling and finish semantics, different memory model:

      * KV lives in a global pool of ``num_blocks`` blocks of ``block_size``
        tokens; each slot's cache is the blocks its table names
        (``runtime/kv_pool.BlockPool`` owns ids, refcounts, the prefix index
        and CoW adjudication — the core queues the device copies, the device
        step performs them).
      * Admission matches the prompt's rolling block hashes against the
        prefix index; hits retain cached blocks and skip their prefill. At
        least the prompt's last token is always re-prefilled so sampling has
        logits.
      * Remaining prompt tokens prefill in ``prefill_chunk``-token chunks —
        one chunk per prefilling slot per ``step_chunk``, *interleaved* with
        decode chunks for the already-active slots, so a long prompt never
        stalls the running batch.
      * Greedy outputs are bit-exact vs the slot engine on the same trace:
        chunking and paging both compose under the EXAQ histogram combine
        (§2), and the benchmark asserts it (benchmarks/bench_serving.py).

    ``num_blocks`` defaults to full provisioning (every slot can reach
    ``max_seq``), which makes pool exhaustion impossible; smaller pools are
    allowed (prefix sharing usually covers the gap) and exhaustion becomes
    back-pressure, never KV corruption: admission leaves requests queued,
    and decode growth preempts the newest active request — its blocks free
    up (prompt blocks stay parked in the prefix cache) and it is requeued
    for recompute with prompt+generated-so-far, which reproduces greedy
    output bit-exactly (chunked prefill is exact, DESIGN.md §3).

    ``cache_dtype=jnp.int8`` stores the pool quantized (DESIGN.md §6):
    int8 payloads plus per-(layer, block, kv-head) fp32 scale planes.
    Scatters quantize, reads dequantize (the fused kernel in VMEM, the
    gather during assembly), CoW copies carry the scales with the payload,
    and blocks freshly allocated off the free list / evicted have their
    scales host-reset to the "unset" sentinel before the next device write
    so recycled blocks can't inherit a stale quantization grid.

    ``cache_dtype="int4"`` (string sentinel — int4 has no jnp dtype) packs
    the pool two values per uint8 byte (DESIGN.md §10): the int8 machinery
    above plus per-(layer, block, kv-head, sub-block) 4-bit scale codes in
    "k_sub"/"v_sub" planes, reset alongside the block scales on recycle.
    Both fused kernels unpack the nibbles in VMEM after the block-table DMA
    — no dense dequantized copy in HBM.

    ``fused`` selects the paged attention path for BOTH halves of the
    serving loop (DESIGN.md §3 fused paged decode, §7 fused paged prefill):
    ``True`` dispatches the fused Pallas kernels — block-table-indexed K/V
    loads straight from the pool, no HBM gather copy on decode steps and no
    dense window copy per prefill chunk — requires ``softmax_impl="exaq"``;
    ``False`` forces the gather-then-dispatch references; ``None``
    (default) keeps whatever ``cfg.quant.use_fused_kernel`` says. All
    paths share the global-grid EXAQ combine, so greedy outputs agree
    under the default qstate (asserted by the tier-1 suite). Caveat: the
    fused kernels fold the default-sigma clip as a compile-time constant —
    a *calibrated* per-layer ``qstate`` only takes effect on the gather
    paths, so keep ``fused=False`` when serving calibrated clips.

    ``mesh`` shards the pool tensor-parallel (DESIGN.md §9): the kv-head dim
    of payloads and scale planes partitions over the mesh's 'model' axis
    when divisible (``block_pool_spec``/``block_scale_spec``; non-divisible
    head counts fall back to a replicated pool), block tables stay
    replicated, and the fused kernels run under shard_map with each shard
    DMAing only its local heads (kernels/ops.py). Params stay replicated so
    greedy decode is bit-exact against a single-shard run.
    """

    def __init__(
        self,
        cfg,
        params,
        config: EngineConfig | None = None,
        *,
        qstate=None,
        mesh=None,
        clock=None,
        **legacy_kw,
    ):
        config = _resolve_config(config, legacy_kw, cls="PagedEngine")
        cache_dtype = resolve_kv_dtype(config.kv_dtype)
        if config.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {config.spec_k}")
        if config.fused is not None:
            if config.fused and cfg.quant.softmax_impl != "exaq":
                raise ValueError(
                    f"fused=True needs softmax_impl='exaq' (static clip/LUT folded into the "
                    f"kernel), got {cfg.quant.softmax_impl!r}"
                )
            cfg = cfg.with_quant(use_fused_kernel=config.fused)
        _validate_engine_cfg(cfg, cache_dtype, paged=True)
        state_blocks = cfg.family in STATE_FAMILIES
        if state_blocks:
            if config.spec_k > 0:
                raise ValueError(
                    "speculative decoding needs CoW read-forks of the KV tail; state "
                    "planes checkpoint only at block boundaries, so spec_k must be 0 "
                    f"for family={cfg.family!r} (DESIGN.md §13)"
                )
            if config.prefill_chunk % config.block_size != 0:
                raise ValueError(
                    "state-pool prefill checkpoints at block boundaries inside each "
                    f"chunk: prefill_chunk ({config.prefill_chunk}) must be a multiple "
                    f"of block_size ({config.block_size}) (DESIGN.md §13)"
                )
        EngineCore.__init__(self, clock=clock, state_blocks=state_blocks,
                            **config.core_kwargs())
        self.config = config
        self._dev = PagedDeviceStep(
            cfg, params, qstate=qstate, num_blocks=self.num_blocks,
            block_size=config.block_size, max_seq=config.max_seq,
            eos_id=config.eos_id, cache_dtype=cache_dtype, mesh=mesh,
        )
        self._bind_device_step()
        # tokens per tile of the fused decode kernel, for the decode_kv_tiles
        # counters; None where this engine's decode never runs that kernel
        self._decode_tile = None
        if (cfg.quant.use_fused_kernel and cfg.quant.softmax_impl == "exaq"
                and not cfg.attention_free):
            kv = cfg.num_kv_heads
            tp = 1 if mesh is None else mesh.shape.get("model", 1)
            self._decode_tile = decode_tile_tokens(
                block_size=config.block_size, max_blocks=self.blocks_per_table,
                kv_heads=kv // tp if kv % tp == 0 else kv,
                head_dim=cfg.resolved_head_dim, kv_dtype=cache_dtype,
            )
        # (layer, held expert) pairs of a MoE decode step, for moe_expert_slots
        self._moe_slots_per_step = 0
        if cfg.moe is not None:
            lo, hi = cfg.held_experts
            self._moe_slots_per_step = (cfg.num_layers - cfg.moe.first_dense) * (hi - lo)
        self._key = jax.random.PRNGKey(config.seed)
        self._pool = self._dev.init_pool()
        # raw jitted (pool, src, dst) -> pool CoW copy; tests drive it directly
        self._jit_copy_block = self._dev.copy_block
        # speculative decoding (DESIGN.md §12): spec_k > 0 replaces decode
        # chunks with per-slot draft/verify rounds; drafter may be a name
        # from the registry ("ngram"), a Drafter instance, or None (ngram)
        self.spec_k = config.spec_k
        drafter = config.drafter
        if isinstance(drafter, str):
            drafter = make_drafter(drafter)
        if self.spec_k > 0 and drafter is None:
            drafter = NgramDrafter()
        self.drafter = drafter

    def submit(self, prompt, max_new: int | None = None,
               sampling: smp.SamplingParams = smp.GREEDY, *,
               priority: int = 0, deadline: float | None = None) -> int:
        samp = prompt.sampling if isinstance(prompt, Request) else sampling
        if self.spec_k > 0 and samp.temperature > 0:
            raise ValueError(
                "speculative decoding (spec_k > 0) is greedy-only: the accept rule "
                "compares exact argmaxes (DESIGN.md §12); submit with temperature=0"
            )
        return super().submit(prompt, max_new, sampling, priority=priority,
                              deadline=deadline)

    # -------------------------------------------------------------- block ops

    def _make_writable(self, slot: int, bi: int) -> None:
        """Core CoW adjudication + immediate device copy: the engine drains
        the copy queue as soon as it is planned, so the pool state callers
        observe (tests, telemetry) is never behind the host tables."""
        EngineCore._make_writable(self, slot, bi)
        self._drain_copies()

    def _drain_copies(self) -> None:
        copies = self.take_pending_copies()
        if copies:
            with span("engine.copy_blocks"):
                self._pool = self._dev.copy_blocks(self._pool, copies)

    def _flush_fresh_scales(self) -> None:
        """Reset the scale planes of blocks allocated since the last launch.
        Runs (bucketed, null-block padded — idempotent) before any jitted
        write so the first scatter into a recycled block seeds a fresh scale.
        Released-but-still-queued ids are harmless: a free block's scales
        may be zeroed; only fork destinations must escape (see
        ``EngineCore._make_writable``)."""
        fresh = self.take_fresh_scale_ids()
        if not fresh:
            return
        n = _bucket(len(fresh), 8)
        ids = np.full((n,), NULL_BLOCK, np.int32)
        ids[: len(fresh)] = fresh
        with span("engine.reset_scales"):
            self._pool = self._dev.reset_fresh_scales(self._pool, ids)

    # ------------------------------------------------------------- scheduling

    def _prefill_step(self, slot: int) -> None:
        """Advance one ``prefill_chunk``-token chunk for a prefilling slot;
        on prompt completion, sample the first token and activate."""
        req = self._slots[slot].req
        plan = self.plan_prefill_chunk(slot)
        self._flush_fresh_scales()
        logits, self._pool = self._dev.prefill_chunk(
            self._pool, plan.tokens, self._tables[slot], plan.start, plan.n,
            plan.blk_t, plan.off_t,
        )
        if self.commit_prefill_chunk(slot, plan.n):
            self._sample_first(slot, req, logits)
            # a preempted-at-the-brink continuation can legally have
            # len(prompt) == max_seq: its first sampled token is also its
            # last (no cache room to decode further)
            if self._active[slot] and int(self.kv_lens[slot]) >= self.max_seq:
                self._finish(slot, "length")

    def _step(self, steps: int | None) -> int:
        """Admit; advance one prefill chunk per prefilling slot; run one
        jitted decode chunk over the active slots. Returns #tokens emitted."""
        with span("engine.admit"):
            self._admit()
        for i, s in enumerate(self._slots):
            if not s.free and s.prefilling:
                with span("engine.prefill", uid=s.uid):
                    self._prefill_step(i)
        if self.num_active == 0:
            return 0
        if self.spec_k > 0:
            return self._spec_chunk()
        steps = self._clamp_steps(steps)
        with span("engine.reserve"):
            self._reserve_chunk_blocks(steps)  # may preempt slots under pool pressure
        if self.num_active == 0:
            return 0
        self._flush_fresh_scales()
        t0 = time.perf_counter()
        with span("engine.decode"):
            out = self._dev.decode_chunk(
                self._pool, self._tables, self._tokens, self.kv_lens, self._active,
                self._budget, self._temperature, self._top_k, self._top_p, self._key,
                steps=steps, sampler=self._pick_sampler(),
            )
        pool, tokens, lens, active, budget, self._key, emitted, masks, routing = out
        with span("engine.device_wait"):
            jax.block_until_ready(emitted)
        self.stats["decode_time"] += time.perf_counter() - t0
        self._pool = pool
        was_active = self._active
        lens_in = self.kv_lens
        with span("engine.absorb"):
            n_out = self._absorb_chunk(tokens, lens, active, budget, emitted, masks,
                                       was_active)
            if self._decode_tile is not None:
                walked, full = decode_tile_counts(
                    lens_in, masks, tile=self._decode_tile,
                    table_tokens=self.blocks_per_table * self.block_size)
                self.stats["decode_kv_tiles"] += walked
                self.stats["decode_kv_tiles_full"] += full
            if routing is not None:
                rows, touched = (int(n) for n in np.asarray(routing))
                self.stats["moe_rows"] += rows
                self.stats["moe_experts_touched"] += touched
                self.stats["moe_expert_slots"] += len(masks) * self._moe_slots_per_step
        return n_out

    # ------------------------------------------------- speculative decoding

    def _spec_chunk(self) -> int:
        """One draft/verify round per active slot (DESIGN.md §12); replaces
        the decode chunk entirely when ``spec_k > 0``. Returns #tokens
        emitted. A slot deactivated mid-chunk (finished, or preempted by a
        sibling's pool-pressure retry) is skipped."""
        n_out = 0
        for slot in range(self.max_slots):
            if self._active[slot]:
                with span("engine.spec_round", uid=self._slots[slot].uid):
                    n_out += self._spec_round(slot)
        return n_out

    def _spec_round(self, slot: int) -> int:
        """Draft k tokens, fork a branch, verify the whole window in one
        fused paged-prefill call, accept/reject, absorb. Pool exhaustion
        first retries draft-free (k=0 needs at most one block — the round
        degrades to vanilla single-token decode), then preempts the least
        urgent *other* slot; a sole slot that cannot even grow by one block
        raises non-retryable, mirroring ``_reserve_chunk_blocks``."""
        s = self._slots[slot]
        L = int(self.kv_lens[slot])
        k_eff = max(0, min(self.spec_k, int(self._budget[slot]) - 1,
                           self.max_seq - 1 - L))
        drafts: list[int] = []
        if k_eff > 0:
            drafts = list(self.drafter.propose(list(s.req.prompt) + list(s.generated),
                                               k_eff))[:k_eff]
        while True:
            try:
                plan = self.plan_spec_round(slot, drafts)
                break
            except PoolExhausted:
                if drafts:
                    drafts = []  # degrade to k=0 before evicting anyone
                    continue
                victims = [j for j in range(self.max_slots)
                           if self._active[j] and j != slot]
                if not victims:
                    raise PoolExhausted(
                        f"cannot grow KV for the only active request (uid "
                        f"{s.uid}): pool of {self.pool.num_blocks - 1} usable "
                        f"blocks is too small for max_seq {self.max_seq}",
                        retryable=False, occupancy=self.pool.occupancy(),
                    ) from None
                self._preempt(max(victims, key=self._victim_rank))
        # branch fork copies must land before verify reads the window, and
        # fresh-block scale resets before verify's first quantized scatter
        self._drain_copies()
        self._flush_fresh_scales()
        t0 = time.perf_counter()
        verified, self._pool = self._dev.verify_chunk(
            self._pool, plan.tokens, plan.table, plan.start, plan.blk_t, plan.off_t,
        )
        verified = np.asarray(jax.device_get(verified))
        self.stats["decode_time"] += time.perf_counter() - t0
        res = self.commit_spec_round(plan, verified)
        if self._dev.int4 and res.trim_tail:
            # rejected rows seeded immutable sub-block codes in the kept tail
            # block; zero every sub-block wholly past the committed rows so
            # the next (vanilla-equivalent) write re-seeds it (DESIGN.md §12)
            n_sub = self._pool["k_sub"].shape[-1]
            sub_bs = self.block_size // n_sub
            keep_subs = (res.tail_rows - 1) // sub_bs + 1
            if keep_subs < n_sub:
                self._pool = self._dev.trim_sub_scales(self._pool, res.tail_block,
                                                       keep_subs)
        return self.absorb_spec_round(slot, res.emitted)

    # -------------------------------------------------------------- telemetry

    @property
    def kv_pool_bytes(self) -> int:
        """Device bytes of the whole pool (int8: payloads + scale planes)."""
        return self._dev.pool_bytes(self._pool)

    @property
    def pool_stats(self) -> PoolStats:
        """Allocator counters; same accessor shape as ``DataParallelEngine``."""
        return self.pool.stats


# ================================================================ data parallel


class DataParallelEngine:
    """Independent ``PagedEngine`` replicas over the 'data' axis behind one
    shared admission queue (DESIGN.md §9).

    The block pool is deliberately *not* sharded over 'data' — prefix sharing
    only pays within one pool, so each replica owns a full engine (scheduler
    + pool + tables) on its own device subset (``launch.mesh.
    make_replica_meshes``), and data parallelism is pure request-level
    scaling: submissions land in a shared host queue and are dispatched to
    the least-loaded replica at each ``step_chunk``. Dispatch is
    deterministic (load, then replica index), and greedy decode is
    batch-composition-independent (per-slot attention is masked to the slot;
    sampling is argmax), so a DP fleet reproduces a single engine's greedy
    tokens bit-exactly — the parity suite asserts it.

    Public surface mirrors the single engine: ``submit`` / ``step_chunk`` /
    ``run`` / ``has_work`` plus aggregated telemetry (``stats``,
    ``prefix_hit_rate``, ``mean_occupancy``) and ``per_replica_stats`` for
    bench_serving's per-replica reporting.
    """

    def __init__(self, cfg, params, config: EngineConfig | None = None, *,
                 replicas: int | None = None, meshes=None, **engine_kw):
        if replicas is None:
            replicas = config.replicas if config is not None else 2
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        if meshes is not None and len(meshes) != replicas:
            raise ValueError(f"got {len(meshes)} meshes for {replicas} replicas")
        meshes = meshes if meshes is not None else [None] * replicas
        self.engines = [PagedEngine(cfg, params, config, mesh=m, **engine_kw)
                        for m in meshes]
        self.config = self.engines[0].config
        self._pending: list[Request] = []
        self._route: dict[int, tuple[int, int]] = {}  # global uid -> (replica, local uid)
        self._next_uid = 0
        self._results: dict[int, Generation] = {}

    def submit(self, prompt, max_new: int | None = None,
               sampling: smp.SamplingParams = smp.GREEDY, *,
               priority: int = 0, deadline: float | None = None) -> int:
        """Submit a ``Request`` (canonical) or the legacy kwarg spread."""
        if isinstance(prompt, Request):
            if max_new is not None:
                raise ValueError("pass either a Request or (prompt, max_new), not both")
            req = prompt
        else:
            if max_new is None:
                raise ValueError("max_new is required when submitting a raw prompt")
            req = Request(prompt, max_new, sampling, int(priority), deadline)
        toks = tuple(int(t) for t in np.asarray(req.prompt).reshape(-1))
        # validate against replica 0 (all replicas are configured identically)
        self.engines[0]._validate_request(toks, req.max_new)
        uid = self._next_uid
        self._next_uid += 1
        self._pending.append(dataclasses.replace(req, prompt=toks, uid=uid))
        return uid

    def _dispatch(self) -> None:
        """Hand queued requests to replicas with admission capacity, least
        loaded first (live slots + local backlog; ties break on index)."""
        while self._pending:
            loads = [
                (len([s for s in e._slots if not s.free]) + e.num_queued, i)
                for i, e in enumerate(self.engines)
            ]
            load, i = min(loads)
            if load >= self.engines[i].max_slots:
                break  # every replica is saturated; keep the shared backlog
            req = self._pending.pop(0)
            local = self.engines[i].submit(dataclasses.replace(req, uid=-1))
            self._route[req.uid] = (i, local)

    def has_work(self) -> bool:
        return bool(self._pending) or any(e.has_work() for e in self.engines)

    def step_chunk(self, steps: int | None = None) -> int:
        self._dispatch()
        return sum(e.step_chunk(steps) for e in self.engines if e.has_work())

    def run(self) -> dict[int, Generation]:
        while self.has_work():
            self.step_chunk()
        out = {}
        for uid, (i, local) in self._route.items():
            g = self.engines[i]._results.pop(local, None)
            if g is None:
                g = self._results.pop(uid, None)
            if g is not None:
                out[uid] = Generation(uid, g.tokens, g.finish_reason)
        self._route = {uid: r for uid, r in self._route.items() if uid not in out}
        return out

    # -------------------------------------------------------------- telemetry

    @property
    def num_active(self) -> int:
        return sum(e.num_active for e in self.engines)

    @property
    def num_queued(self) -> int:
        return len(self._pending) + sum(e.num_queued for e in self.engines)

    @property
    def per_replica_stats(self) -> list[dict]:
        out = []
        for e in self.engines:
            s = dict(e.stats)
            s["prefix_hit_rate"] = e.prefix_hit_rate
            s["mean_occupancy"] = e.mean_occupancy
            out.append(s)
        return out

    @property
    def stats(self) -> dict:
        """Replica stats summed (max_active is a max across replicas)."""
        agg: dict = {}
        for s in (e.stats for e in self.engines):
            for k, v in s.items():
                if k == "max_active":
                    agg[k] = max(agg.get(k, 0), v)
                else:
                    agg[k] = agg.get(k, 0) + v
        return agg

    @property
    def prefix_hit_rate(self) -> float:
        s = self.stats
        return s["prefix_hit_tokens"] / max(s["prompt_tokens"], 1)

    @property
    def mean_occupancy(self) -> float:
        """Mean live slots per decode step, summed over replicas (occupancy
        sums add; steps are the max so overlapping replicas don't divide
        each other's occupancy away)."""
        steps = max(max(e.stats["decode_steps"] for e in self.engines), 1)
        return sum(e.stats["occupancy_sum"] for e in self.engines) / steps

    @property
    def kv_pool_bytes(self) -> int:
        return sum(e.kv_pool_bytes for e in self.engines)

    @property
    def pool_stats(self) -> PoolStats:
        """Field-wise sum of every replica pool's allocator counters."""
        return PoolStats.merged([e.pool.stats for e in self.engines])
