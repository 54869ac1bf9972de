#!/usr/bin/env python3
"""Serve full-width yi-6b on a TPU through the paged engine, and check it.

    python chip_smoke.py              # one chip: fused kernels vs the gather reference
    python chip_smoke.py --chips 4    # four chips: dp=2 x tp=2 fleet vs a one-chip engine

One chip. yi-6b at its published widths (32 layers, d_model 4096, 32 heads,
4 kv-heads, d_ff 11008, vocab 64000), bf16 weights drawn from ``--seed``,
EXAQ INT2 softmax, a bf16 block pool, and EngineConfig's default block size,
prefill chunk and decode chunk. Four greedy requests share a 128-token
prefix and carry ragged 64-1024-token suffixes; the first is submitted
alone until the prefix is cached, so chunked prefill, prefix-cache hits and
decode all run. The requests are served through the fused Pallas paged
decode/prefill kernels and through the gather reference on the same
parameters, first as served (bf16 activations), then for the first
token only with the embedding table in fp32 (every activation fp32), an
fp32 pool and exact fp32 matmuls. The run
fails unless every request ends with its whole token budget, all
first-token logits are finite, every token is in the vocabulary, the
compiled fused prefill and decode steps hold the Pallas kernels
(``tpu_custom_call``: compiled, not interpreted, not the reference), and
the two paths' first-token logits agree within LOGIT_RTOL with fp32
activations and within BF16_LOGIT_RTOL as served. Greedy token agreement
is printed only: argmax margins on random weights are noise.

Four chips (``--chips 4``). The same requests through a one-chip PagedEngine
on device 0, which is then freed, and through a DataParallelEngine of two
replicas that each shard their pool over a tensor-parallel pair of chips.
Greedy tokens must be identical (DESIGN.md §9), and each replica's pool must
be sharded over its own two chips.

Lines before the last report set-up figures (parameter bytes, peak device
memory, cold wall time including compiles), not speed. The last line is one
JSON object naming the device. Without a TPU the script exits non-zero and
prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "yi-6b"
PREFIX = 128
SUFFIXES = (64, 384, 704, 1024)
GEN = 16
# Limits on the largest first-token logit difference between the fused and
# gather paths, relative to the largest logit. With fp32 activations the
# paths differ only in how attention is computed. With bf16 activations one
# differently rounded value is amplified by every random layer after it
# (3.65e-2 at 32 layers on a v5e), so the bf16 limit catches gross faults
# only, such as a wrong block or head.
LOGIT_RTOL = 1e-2
BF16_LOGIT_RTOL = 1e-1


def require(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: {what}")


def make_prompts(vocab: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, PREFIX)
    return [np.concatenate([shared, rng.integers(0, vocab, n)]) for n in SUFFIXES]


def serve(engine, prompts, vocab: int, gen: int = GEN):
    """Serve ``prompts`` greedily, ``gen`` tokens each; the first alone until
    the shared prefix is cached. Returns (tokens per request, cold wall
    seconds)."""
    from repro.runtime.engine_core import Request

    t0 = time.perf_counter()
    uids = [engine.submit(Request(prompts[0], gen))]
    for _ in range(-(-PREFIX // engine.config.prefill_chunk)):
        engine.step_chunk()
    uids += [engine.submit(Request(p, gen)) for p in prompts[1:]]
    results = engine.run()
    wall = time.perf_counter() - t0
    tokens = []
    for u in uids:
        g = results[u]
        require(g.finish_reason == "length" and len(g.tokens) == gen,
                f"request {u} ended {g.finish_reason!r} after {len(g.tokens)}/{gen} tokens")
        require(all(0 <= t < vocab for t in g.tokens), f"request {u} emitted {g.tokens}")
        tokens.append(list(g.tokens))
    return tokens, wall


def fused_steps_hold_kernels(eng) -> None:
    """Lower and compile the fused engine's prefill and decode steps with the
    arguments its own calls pass, and find the Pallas kernel in both texts."""
    import numpy as np

    from repro.runtime import sharding as shd

    d, C = eng._dev, eng.config.prefill_chunk
    with shd.use_mesh(d.mesh):
        prefill = d._jit_prefill_chunk.lower(
            d.params, eng._pool, d._put(np.zeros((1, C), np.int32)), d._put(eng._tables[0]),
            d._put(np.int32(0)), d._put(np.int32(C)), d._put(np.zeros((C,), np.int32)),
            d._put(np.arange(C, dtype=np.int32) % eng.block_size),
        )
        decode = d._jit_chunk.lower(
            d.params, eng._pool, d._put(eng._tables), d._put(eng._tokens), d._put(eng.kv_lens),
            d._put(eng._active), d._put(eng._budget), d._put(eng._temperature),
            d._put(eng._top_k), d._put(eng._top_p), d._put(eng._key),
            steps=eng.steps_per_sync, sampler="greedy",
        )
    for name, lowered in (("prefill", prefill), ("decode", decode)):
        require("tpu_custom_call" in lowered.as_text(), f"lowered fused {name} step has no Pallas kernel")
        require("tpu_custom_call" in lowered.compile().as_text(),
                f"compiled fused {name} step has no Pallas kernel")
        print(f"fused {name} step: tpu_custom_call in lowered and compiled text")


def serve_both_paths(cfg, params, config, prompts, label: str, *, gen: int, check_kernels: bool):
    """Serve ``prompts`` through the fused kernels, then the gather reference,
    on the same parameters; return each path's (tokens, first-token logits)."""
    import numpy as np

    from repro.runtime.engine import PagedEngine

    class FirstLogits(PagedEngine):
        """Keeps each request's first-token logits (the last prefill row)."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.first_logits = {}

        def _sample_first(self, slot, req, logits):
            self.first_logits[req.uid] = np.asarray(logits, np.float32)[0]
            super()._sample_first(slot, req, logits)

    runs = []
    for fused in (True, False):
        name = f"{label}, {'fused' if fused else 'gather'}"
        eng = FirstLogits(cfg, params, dataclasses.replace(config, fused=fused))
        tokens, wall = serve(eng, prompts, cfg.vocab_size, gen)
        logits = np.stack([eng.first_logits[u] for u in range(len(prompts))])
        require(bool(np.isfinite(logits).all()), f"{name}: non-finite first-token logits")
        print(f"{name}: cold wall {wall:.3f} s incl. compiles (set-up, not speed); "
              f"prefix-hit share {eng.prefix_hit_rate:.4f} "
              f"({eng.stats['prefix_hit_tokens']}/{eng.stats['prompt_tokens']} prompt tokens); "
              f"{eng.stats['prefill_chunks']} prefill chunks")
        require(eng.stats["prefix_hit_tokens"] > 0, f"{name}: no prefix-cache hit")
        if fused and check_kernels:
            fused_steps_hold_kernels(eng)
        runs.append((tokens, logits))
        del eng
    return runs


def compare_logits(label: str, fused, gather, limit: float) -> None:
    import numpy as np

    (tok_f, lf), (tok_g, lg) = fused, gather
    diff = np.abs(lf - lg)
    rel = float(diff.max() / np.abs(lg).max())
    print(f"{label}: first-token logits fused vs gather: max abs diff {float(diff.max())!r}, "
          f"max rel diff {rel!r} (limit {limit}), relative L2 "
          f"{float(np.linalg.norm(lf - lg) / np.linalg.norm(lg))!r}")
    agree = sum(a == b for ta, tb in zip(tok_f, tok_g) for a, b in zip(ta, tb))
    print(f"{label}: greedy tokens fused vs gather: {agree}/{sum(map(len, tok_f))} agree "
          f"(printed only)")
    require(rel < limit, f"{label}: fused vs gather logits differ by {rel} relative")


def one_chip(cfg, params, config, prompts) -> None:
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    fused, gather = serve_both_paths(cfg, params, config, prompts, "bf16", gen=GEN,
                                     check_kernels=True)
    print(f"peak device memory, bf16 serving: {dev.memory_stats()['peak_bytes_in_use']} bytes")
    compare_logits("bf16", fused, gather, BF16_LOGIT_RTOL)
    # The same weights with an fp32 embedding table make every activation
    # fp32; an fp32 pool and "highest" matmul precision make the reference
    # exact fp32 (it rounds attention weights to the pool dtype). What is
    # left between the two paths is the attention implementation. Only the
    # first token is compared, so only prefill runs.
    params["embed"]["tokens"] = params["embed"]["tokens"].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        fused, gather = serve_both_paths(cfg, params, dataclasses.replace(config, kv_dtype="fp32"),
                                         prompts, "fp32", gen=1, check_kernels=False)
    compare_logits("fp32", fused, gather, LOGIT_RTOL)


def four_chips(cfg, config, prompts, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_replica_meshes
    from repro.models import init_params
    from repro.runtime.engine import DataParallelEngine, PagedEngine

    key = jax.random.PRNGKey(seed)
    ref = PagedEngine(cfg, init_params(cfg, key, jnp.bfloat16), config)
    ref_tokens, wall = serve(ref, prompts, cfg.vocab_size)
    print(f"one-chip reference: cold wall {wall:.3f} s incl. compiles (set-up, not speed)")
    del ref
    gc.collect()  # device 0 must hold replica 0's copy of the weights next

    meshes = make_replica_meshes(2, 2)
    params = init_params(cfg, key, jnp.bfloat16, sharding=NamedSharding(meshes[0], P()))
    fleet = DataParallelEngine(cfg, params, dataclasses.replace(config, replicas=2), meshes=meshes)
    for i, (eng, mesh) in enumerate(zip(fleet.engines, meshes)):
        sh = eng._pool["k"].sharding
        require(sh.device_set == set(mesh.devices.flat) and tuple(sh.spec)[2] == "model",
                f"replica {i} pool placed {sh}")
        print(f"replica {i}: pool k sharded {sh.spec} over devices "
              f"{sorted(d.id for d in sh.device_set)}")
    tokens, wall = serve(fleet, prompts, cfg.vocab_size)
    print(f"dp=2 x tp=2 fleet: cold wall {wall:.3f} s incl. compiles (set-up, not speed); "
          f"prefix-hit share {fleet.prefix_hit_rate:.4f}")
    require(tokens == ref_tokens, f"fleet tokens {tokens} != one-chip tokens {ref_tokens}")
    print(f"greedy tokens dp=2 x tp=2 vs one chip: bit-identical ({len(prompts) * GEN} tokens)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the dp=2 x tp=2 fleet and its one-chip reference")
    ap.add_argument("--seed", type=int, default=0, help="weights and prompts")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, found {len(devices)}")

    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import init_params
    from repro.runtime.engine_core import EngineConfig

    print(f"compile cache: {enable_compile_cache()}")
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    cfg = get_config(ARCH).with_quant(softmax_impl="exaq", bits=2)
    prompts = make_prompts(cfg.vocab_size, args.seed)
    config = EngineConfig(max_slots=len(prompts), max_seq=PREFIX + max(SUFFIXES) + GEN,
                          kv_dtype="bf16", fused=True, seed=args.seed)
    if args.chips == 4:
        four_chips(cfg, config, prompts, args.seed)
    else:
        t0 = time.perf_counter()
        params = jax.block_until_ready(init_params(cfg, jax.random.PRNGKey(args.seed), jnp.bfloat16))
        print(f"params: {sum(x.nbytes for x in jax.tree.leaves(params))} bytes in "
              f"{time.perf_counter() - t0:.3f} s "
              f"(jitted init, set-up)")
        one_chip(cfg, params, config, prompts)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                             "count": len(devices)}}))


if __name__ == "__main__":
    main()
