"""Jitted device steps for the serving engines: the other half of the
host/device split (DESIGN.md §9).

``runtime/engine_core.py`` makes every scheduling decision with plain Python
ints; this module owns everything that touches a jax array: cache/pool
construction (placed onto a mesh with the specs from ``runtime/sharding.py``),
the jitted prefill/decode/scatter functions, the CoW block copy, the int8
scale resets, and sampling. The engines in ``runtime/engine.py`` glue the two
layers together. Every jitted program compiles under a stable name that says
what it does (``jit_paged_decode_chunk``, ``jit_paged_prefill_chunk``,
``jit_copy_block``, ...; see ``_jit``), which is how a profiler trace lists
each execution.

Sharding contract (the reason this layer exists):

  * The paged pool pytree is *explicitly sharded* at construction:
    ``block_pool_spec`` puts the kv-head dim over the 'model' mesh axis when
    divisible (scale planes follow via ``block_scale_spec``); block tables
    and the small per-slot vectors are replicated. Every jitted entry point
    takes and returns that same sharded pytree, so placement is decided once
    here and never re-negotiated inside the engines.
  * Params are placed **replicated** (``P()``), deliberately: sharding the
    matmuls would split their contractions and psum the partials, which
    reassociates fp addition — greedy decode would no longer be bit-exact
    against a single-shard run. Replicated params + head-sharded attention
    (each head's math is computed whole on exactly one shard) keeps the
    tensor-parallel engine bit-identical, which the parity suite asserts.
  * Calls run under ``sharding.use_mesh``, so the trace-time shard_map
    dispatch around the fused paged kernels (kernels/ops.py) sees the mesh.
  * Small host inputs (tokens, tables, lens, rng key) are placed replicated
    on the step's mesh per call — data-parallel replicas own disjoint device
    subsets, and uncommitted default-device arrays must not pin a replica's
    computation to device 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels.kv_codec import kv_cache_is_int4
from repro.models import build_model, default_qstate
from repro.runtime import sampling as smp
from repro.runtime import sharding as shd


def _jit(fn, name: str, **jit_kw):
    """``jax.jit(fn, **jit_kw)`` under a stable program name: the compiled
    module is ``jit_<name>``, which is what a profiler trace lists each
    executed program by, whatever the Python function is called."""

    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program, **jit_kw)


def decode_scan(step_kv, kv, tokens, lens, active, budget, temperature, top_k,
                top_p, key, *, steps, sampler, eos_id, max_seq):
    """``steps`` decode iterations under one jit: per step, one attention
    dispatch over all slots + one batched sampling dispatch. EOS/budget/
    max_seq transitions update the active mask *inside* the scan, so a slot
    that finishes mid-chunk stops consuming budget and its later emissions
    are masked. ``sampler`` (static, known host-side from the active slots'
    params) picks the cheapest variant: "greedy" is pure argmax,
    "temperature" is sort-free Gumbel-max, "full" is the general top-k/top-p
    sampler. ``step_kv(tokens, kv, lens, active)`` is the engine-specific
    model call (slot-ragged or paged); ``kv`` is the engine's cache pytree —
    {"k","v"} for the slot cache, plus "k_scale"/"v_scale" planes for an
    int8 paged pool. Where ``step_kv`` returns a third value (a MoE model's
    per-layer routing counts), the scan stacks it over steps and returns it
    last."""
    eos = -1 if eos_id is None else eos_id

    def step(carry, _):
        kv, tokens, lens, active, budget, key = carry
        logits, kv, *extra = step_kv(tokens, kv, lens, active)
        key, sub = jax.random.split(key)
        if sampler == "greedy":
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        elif sampler == "temperature":
            nxt = smp.sample_temperature(logits, temperature, sub)
        else:
            nxt = smp.sample_tokens(logits, temperature, top_k, top_p, sub)
        emit_mask = active
        new_lens = jnp.where(active, lens + 1, lens)
        new_budget = jnp.where(active, budget - 1, budget)
        finished = (nxt == eos) | (new_budget <= 0) | (new_lens >= max_seq)
        new_active = active & ~finished
        new_tokens = jnp.where(active, nxt, tokens[:, 0])[:, None]
        emitted = jnp.where(emit_mask, nxt, -1)
        return (kv, new_tokens, new_lens, new_active, new_budget, key), (
            emitted,
            emit_mask,
            *extra,
        )

    init = (kv, tokens, lens, active, budget, key)
    (kv, tokens, lens, active, budget, key), (emitted, masks, *extra) = jax.lax.scan(
        step, init, None, length=steps
    )
    return (kv, tokens, lens, active, budget, key, emitted, masks, *extra)


class _DeviceStep:
    """Shared device-side scaffold: model/qstate/params placement + sampling."""

    def __init__(self, cfg, params, *, qstate, max_seq, eos_id, cache_dtype, mesh):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.qstate = qstate if qstate is not None else default_qstate(cfg)
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.cache_dtype = cache_dtype
        self.mesh = mesh
        if mesh is not None:
            # replicated on purpose — see the module docstring's contract
            params = jax.device_put(params, NamedSharding(mesh, P()))
        self.params = params
        self._jit_sample = _jit(smp.sample_tokens, "sample_first")

    def _put(self, x, dtype=None):
        """Host array -> device, replicated on this step's mesh (if any)."""
        a = jnp.asarray(x, dtype)
        if self.mesh is not None:
            a = jax.device_put(a, NamedSharding(self.mesh, P()))
        return a

    def sample_first(self, logits, sampling, key) -> int:
        """Sample one token from a (1, V) prefill logits row."""
        with shd.use_mesh(self.mesh):
            out = self._jit_sample(
                logits,
                self._put([sampling.temperature], jnp.float32),
                self._put([sampling.top_k], jnp.int32),
                self._put([sampling.top_p], jnp.float32),
                self._put(key),
            )
        return int(out[0])


class SlotDeviceStep(_DeviceStep):
    """Device half of the slot engine: rectangular (L, S, KV, max_seq, Dh)
    cache, bucketed single-request prefill + insert, scanned decode chunks."""

    def __init__(self, cfg, params, *, qstate=None, max_slots, max_seq,
                 eos_id=None, cache_dtype=jnp.bfloat16, mesh=None):
        super().__init__(cfg, params, qstate=qstate, max_seq=max_seq,
                         eos_id=eos_id, cache_dtype=cache_dtype, mesh=mesh)
        self.max_slots = max_slots
        # donate the K/V buffers on the hot paths: the engine rebinds them from
        # the outputs immediately, so XLA may update the cache in place instead
        # of copying the full (L, slots, KV, max_seq, Dh) arrays per chunk /
        # admission (CPU ignores donation; TPU/GPU halve peak cache memory)
        self._jit_prefill = _jit(self._prefill_fn, "slot_prefill")
        self._jit_insert = _jit(self._insert_fn, "slot_insert", donate_argnums=(0, 1))
        self._jit_chunk = _jit(self._chunk_fn, "slot_decode_chunk",
                               static_argnames=("steps", "sampler"), donate_argnums=(1,))

    def init_cache(self):
        """Build the slot cache, sharded per ``slot_cache_spec`` on a mesh."""
        cache = self.model.init_cache(self.max_slots, self.max_seq, self.cache_dtype)
        if self.mesh is not None:
            spec = shd.slot_cache_spec(self.cfg, self.mesh)
            sh = NamedSharding(self.mesh, spec)
            cache["k"] = jax.device_put(cache["k"], sh)
            cache["v"] = jax.device_put(cache["v"], sh)
        return cache["k"], cache["v"]

    # ------------------------------------------------------------- jitted fns

    def _prefill_fn(self, params, tokens, length):
        """tokens (1, P) right-padded; length (1,) true prompt length."""
        cache = self.model.init_cache(1, tokens.shape[1], self.cache_dtype)
        logits, cache = self.model.prefill(
            params, {"tokens": tokens}, cache, self.qstate, lens=length
        )
        return logits, cache["k"], cache["v"]

    def _insert_fn(self, big_k, big_v, ks, vs, slot):
        """Write a (L, 1, KV, P, Dh) prefill cache into slot ``slot``."""
        start = (0, slot, 0, 0, 0)
        return (
            jax.lax.dynamic_update_slice(big_k, ks.astype(big_k.dtype), start),
            jax.lax.dynamic_update_slice(big_v, vs.astype(big_v.dtype), start),
        )

    def _chunk_fn(self, params, kv, tokens, lens, active, budget, temperature,
                  top_k, top_p, key, *, steps, sampler):
        def step_kv(tokens, kv, lens, active):
            logits, cache = self.model.decode_step_ragged(
                params, tokens, kv, lens, self.qstate
            )
            return logits, {"k": cache["k"], "v": cache["v"]}

        return decode_scan(step_kv, kv, tokens, lens, active, budget,
                           temperature, top_k, top_p, key, steps=steps,
                           sampler=sampler, eos_id=self.eos_id, max_seq=self.max_seq)

    # ---------------------------------------------------------------- wrappers

    def prefill(self, padded, length):
        with shd.use_mesh(self.mesh):
            return self._jit_prefill(self.params, self._put(padded),
                                     self._put(length, jnp.int32))

    def insert(self, big_k, big_v, ks, vs, slot):
        with shd.use_mesh(self.mesh):
            return self._jit_insert(big_k, big_v, ks, vs, slot)

    def decode_chunk(self, kv, tokens, lens, active, budget, temperature,
                     top_k, top_p, key, *, steps, sampler):
        with shd.use_mesh(self.mesh):
            return self._jit_chunk(
                self.params, kv, self._put(tokens), self._put(lens),
                self._put(active), self._put(budget), self._put(temperature),
                self._put(top_k), self._put(top_p), self._put(key),
                steps=steps, sampler=sampler,
            )


class PagedDeviceStep(_DeviceStep):
    """Device half of the paged engine: the sharded block-pool pytree and the
    jitted chunked-prefill / decode-chunk / CoW-copy / scale-reset functions
    that carry it (DESIGN.md §3/§6/§9)."""

    def __init__(self, cfg, params, *, qstate=None, num_blocks, block_size,
                 max_seq, eos_id=None, cache_dtype=jnp.bfloat16, mesh=None):
        super().__init__(cfg, params, qstate=qstate, max_seq=max_seq,
                         eos_id=eos_id, cache_dtype=cache_dtype, mesh=mesh)
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.int4 = kv_cache_is_int4(cache_dtype)
        self.quantized = self.int4 or jnp.dtype(cache_dtype) == jnp.int8
        self._jit_prefill_chunk = _jit(self._prefill_chunk_fn, "paged_prefill_chunk",
                                       donate_argnums=(1,))
        self._jit_verify_chunk = _jit(self._verify_chunk_fn, "paged_verify_chunk",
                                      donate_argnums=(1,))
        self._jit_trim_sub = _jit(self._trim_sub_fn, "trim_sub_scales", donate_argnums=(0,))
        # raw jitted (pool, src, dst) -> pool; the engine exposes this as
        # ``_jit_copy_block`` (tests drive it directly on a loose pool dict)
        self.copy_block = _jit(self._copy_block_fn, "copy_block", donate_argnums=(0,))
        self.reset_scales = _jit(self._reset_scales_fn, "reset_scales", donate_argnums=(0,))
        self._jit_chunk = _jit(self._chunk_fn, "paged_decode_chunk",
                               static_argnames=("steps", "sampler"), donate_argnums=(1,))

    def init_pool(self) -> dict:
        """Build the block pool, sharded over the mesh: payloads per
        ``block_pool_spec`` (kv-heads over 'model' when divisible), scale
        planes per ``block_scale_spec``, int4 sub-code planes per
        ``block_sub_scale_spec``."""
        return self.model.init_block_pool(self.num_blocks, self.block_size,
                                          self.cache_dtype, mesh=self.mesh)

    # ------------------------------------------------------------- jitted fns

    def _prefill_chunk_fn(self, params, pool, tokens, table, start, chunk_len, blk_t, off_t):
        return self.model.prefill_paged_chunk(
            params, tokens, pool, table, start, chunk_len, blk_t, off_t, self.qstate,
            block_size=self.block_size,
        )

    def _verify_chunk_fn(self, params, pool, tokens, table, start, blk_t, off_t):
        """Speculative verify (DESIGN.md §12): one fused paged-prefill call
        over the [start, start+C) window returns the target model's greedy
        token after every row — pending token + each draft position. Argmax
        runs in-jit so only (C,) int32 crosses back to the host."""
        logits, pool = self.model.verify_paged_chunk(
            params, tokens, pool, table, start, blk_t, off_t, self.qstate
        )
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), pool

    def _trim_sub_fn(self, pool, blk, keep_subs):
        """Zero block ``blk``'s int4 sub-scale codes at sub indices >=
        ``keep_subs`` (all layers, K and V). Rejected verify rows may have
        seeded sub codes past the accepted tail inside the kept block; sub
        codes are immutable once set (first-write-wins), so without this the
        next real token to reach that sub-block would quantize at a scale
        vanilla decode never saw (DESIGN.md §12)."""
        pool = dict(pool)
        n_sub = pool["k_sub"].shape[-1]
        drop = jnp.arange(n_sub) >= keep_subs  # (n_sub,)
        for key in ("k_sub", "v_sub"):
            plane = pool[key]  # (L, N, KV, n_sub)
            pool[key] = plane.at[:, blk].set(
                jnp.where(drop, jnp.zeros((), plane.dtype), plane[:, blk])
            )
        return pool

    def _copy_block_fn(self, pool, src, dst):
        """Copy-on-write device half: duplicate block ``src`` into ``dst``
        across all layers (the pool already moved the refcounts). For an int8
        pool the per-block scale planes travel with the payload — the fork
        must dequantize identically to the shared original (DESIGN.md §6)."""
        return {k: a.at[:, dst].set(a[:, src]) for k, a in pool.items()}

    def _reset_scales_fn(self, pool, ids):
        """Zero the scale planes of freshly allocated blocks: 0 is the
        "unset" sentinel the next scatter seeds from (DESIGN.md §6). Packed
        int4 pools also zero the sub-block scale-code planes (DESIGN.md §10)
        — a stale nonzero sub code would be immutable under first-write-wins
        and dequantize the new tenant's rows at the old tenant's scale."""
        pool = dict(pool)
        pool["k_scale"] = pool["k_scale"].at[:, ids].set(0.0)
        pool["v_scale"] = pool["v_scale"].at[:, ids].set(0.0)
        if "k_sub" in pool:
            pool["k_sub"] = pool["k_sub"].at[:, ids].set(0)
            pool["v_sub"] = pool["v_sub"].at[:, ids].set(0)
        return pool

    def _chunk_fn(self, params, pool, tables, tokens, lens, active, budget,
                  temperature, top_k, top_p, key, *, steps, sampler):
        """The decode chunk, and for a MoE model its routing: (2,) int32,
        live rows routed to the held experts and the (step, layer, expert)
        triples with at least one, summed over the chunk (None otherwise)."""
        def step_kv(tokens, pool, lens, active):
            return self.model.decode_step_paged(
                params, tokens, pool, tables, lens, active, self.qstate,
                block_size=self.block_size,
            )

        out = decode_scan(step_kv, pool, tokens, lens, active, budget,
                          temperature, top_k, top_p, key, steps=steps,
                          sampler=sampler, eos_id=self.eos_id, max_seq=self.max_seq)
        if self.cfg.moe is None:
            return out + (None,)
        counts = out[8]  # (steps, layers, held experts)
        return out[:8] + (jnp.stack([counts.sum(), (counts > 0).sum()]).astype(jnp.int32),)

    # ---------------------------------------------------------------- wrappers

    def prefill_chunk(self, pool, tokens, table, start, n, blk_t, off_t):
        with shd.use_mesh(self.mesh):
            return self._jit_prefill_chunk(
                self.params, pool, self._put(tokens), self._put(table),
                self._put(np.int32(start)), self._put(np.int32(n)),
                self._put(blk_t), self._put(off_t),
            )

    def verify_chunk(self, pool, tokens, table, start, blk_t, off_t):
        """-> (verified (C,) int32 greedy tokens, new_pool). Compiles once
        per distinct window length C (k is fixed per engine, so in practice
        two shapes: k+1 and the k=0 fallback row)."""
        with shd.use_mesh(self.mesh):
            return self._jit_verify_chunk(
                self.params, pool, self._put(tokens), self._put(table),
                self._put(np.int32(start)), self._put(blk_t), self._put(off_t),
            )

    def trim_sub_scales(self, pool, blk, keep_subs) -> dict:
        """Drop rejected-row sub-scale codes past ``keep_subs`` in ``blk``."""
        with shd.use_mesh(self.mesh):
            return self._jit_trim_sub(pool, self._put(np.int32(blk)),
                                      self._put(np.int32(keep_subs)))

    def copy_blocks(self, pool, copies) -> dict:
        """Drain queued CoW copies (in order — sources may be recycled and
        re-targeted later in the same queue)."""
        with shd.use_mesh(self.mesh):
            for src, dst in copies:
                pool = self.copy_block(pool, self._put(np.int32(src)),
                                       self._put(np.int32(dst)))
        return pool

    def reset_fresh_scales(self, pool, ids) -> dict:
        """Zero the scale planes of blocks ``ids`` ((n,) int32, null-padded)."""
        with shd.use_mesh(self.mesh):
            return self.reset_scales(pool, self._put(ids))

    def decode_chunk(self, pool, tables, tokens, lens, active, budget,
                     temperature, top_k, top_p, key, *, steps, sampler):
        with shd.use_mesh(self.mesh):
            return self._jit_chunk(
                self.params, pool, self._put(tables), self._put(tokens),
                self._put(lens), self._put(active), self._put(budget),
                self._put(temperature), self._put(top_k), self._put(top_p),
                self._put(key), steps=steps, sampler=sampler,
            )

    def pool_bytes(self, pool) -> int:
        """Device bytes of the whole pool (int8: payloads + scale planes)."""
        return sum(a.nbytes for a in pool.values())
