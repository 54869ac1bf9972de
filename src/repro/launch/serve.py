"""Serving driver: continuous-batching engine with EXAQ softmax.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
        --requests 8 --slots 4 --prompt-len 64 --gen 32 --impl exaq --bits 2 \
        --temperature 0.8 --top-k 40

Attention token decoders (dense/moe) run through ``runtime.engine`` — ragged
prompt lengths, slot refill, per-request sampling, one jitted decode step for
all active slots. ``--paged`` swaps in the block-paged engine (DESIGN.md §3):
a global KV block pool with shared-prefix reuse and chunked prefill
(``--block-size`` / ``--prefill-chunk`` / ``--num-blocks`` tune it;
``--fused`` / ``--no-fused`` pick the fused Pallas paged-decode +
paged-prefill kernels vs the gather-then-dispatch references for paged
attention — DESIGN.md §3/§7; ``--kv-dtype int8`` stores the pool as int8
codes with per-block scales, dequantized inside the fused kernels —
DESIGN.md §6 — and ``--kv-dtype int4`` packs two values per byte with
4-bit per-sub-block scale codes on top, nibble-unpacked in VMEM —
DESIGN.md §10); with ``--shared-prefix N``
every request opens with the same N-token system prompt, so the printed
prefix-cache hit rate shows the reuse win. ``--tp N`` shards each block
pool's kv-head axis over an N-way 'model' mesh axis and ``--dp M`` runs M
independent engine replicas behind one shared admission queue
(DESIGN.md §9) — both paged-only; greedy tokens stay bit-exact across any
dp/tp layout. Other families fall back to the rectangular greedy loop in
``runtime.serve.generate``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.runtime import serve as serve_rt
from repro.runtime.engine_core import EngineConfig, Request
from repro.runtime.sampling import SamplingParams


def args_to_config(args) -> EngineConfig:
    """The parsed CLI namespace -> one ``EngineConfig`` — THE construction
    path for every engine this driver builds (slot, paged, data-parallel,
    online). Pure over the namespace, so unit tests exercise the mapping
    without devices. ``max_seq`` covers the worst prompt (shared prefix +
    ragged prompt cap) plus the generation budget."""
    online = getattr(args, "online", False)
    return EngineConfig(
        max_slots=args.slots,
        max_seq=args.prompt_len + args.shared_prefix + args.gen,
        block_size=args.block_size,
        prefill_chunk=args.prefill_chunk,
        num_blocks=args.num_blocks or None,
        eos_id=None if args.eos_id < 0 else args.eos_id,
        kv_dtype=args.kv_dtype,
        fused=args.fused,
        seed=args.seed,
        max_inflight=(args.max_inflight or None) if online else None,
        spec_k=args.spec_k,
        drafter=args.drafter if args.spec_k else None,
        replicas=args.dp,
    )


def validate_serve_args(args, device_count: int | None = None):
    """Reject inconsistent flag combinations with actionable messages.

    Pure function over the parsed namespace so unit tests can exercise it
    without devices; pass ``device_count`` to also check that ``--dp x --tp``
    fits the visible device set. Raises SystemExit (argparse idiom) on the
    first problem found.
    """
    if args.fused is not None and not args.paged:
        raise SystemExit("--fused/--no-fused select the paged decode path; add --paged")
    if args.fused and args.impl != "exaq":
        raise SystemExit(
            f"--fused folds the EXAQ clip/LUT into the kernel and needs --impl exaq, "
            f"got --impl {args.impl}; drop --fused or switch --impl"
        )
    if args.kv_dtype in ("int8", "int4") and not args.paged:
        raise SystemExit(
            f"--kv-dtype {args.kv_dtype} needs the block pool's per-block scales; add --paged"
        )
    if args.dp < 1 or args.tp < 1:
        raise SystemExit(f"--dp and --tp must be >= 1, got --dp {args.dp} --tp {args.tp}")
    if (args.dp > 1 or args.tp > 1) and not args.paged:
        raise SystemExit(
            "--dp/--tp shard the block pool and replicate the paged engine "
            "(DESIGN.md §9); add --paged"
        )
    if device_count is not None and args.dp * args.tp > device_count:
        raise SystemExit(
            f"--dp {args.dp} x --tp {args.tp} needs {args.dp * args.tp} devices, "
            f"only {device_count} visible (try XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N on CPU)"
        )
    if args.spec_k < 0:
        raise SystemExit(f"--spec-k must be >= 0, got {args.spec_k}")
    if args.spec_k and not args.paged:
        raise SystemExit(
            "--spec-k drafts against the paged pool's branch forks "
            "(DESIGN.md §12); add --paged"
        )
    if args.spec_k and args.temperature > 0:
        raise SystemExit(
            "--spec-k is greedy-only (the accept rule compares exact argmaxes, "
            "DESIGN.md §12); drop --temperature"
        )
    if args.online and not args.paged:
        raise SystemExit(
            "--online drives the paged engine's streaming/cancellation surface "
            "(DESIGN.md §11); add --paged"
        )
    if args.online and args.dp > 1:
        raise SystemExit("--online serves a single engine; drop --dp or --online")
    if args.priority_classes < 1:
        raise SystemExit(f"--priority-classes must be >= 1, got {args.priority_classes}")
    if args.deadline_ms < 0 or args.max_inflight < 0:
        raise SystemExit(
            f"--deadline-ms and --max-inflight must be >= 0 (0 = off), got "
            f"--deadline-ms {args.deadline_ms} --max-inflight {args.max_inflight}"
        )
    if not args.online and (args.priority_classes != 1 or args.deadline_ms
                            or args.max_inflight):
        raise SystemExit(
            "--priority-classes/--deadline-ms/--max-inflight shape online "
            "admission; add --online"
        )


def _serve_online(eng, prompts, args, sp):
    """Drive the asyncio serving front (runtime/frontend.py) over the built
    paged engine: submissions cycle through the priority classes, every
    stream is collected concurrently, and shed load is reported with its
    structured rejection instead of failing the run."""
    import asyncio

    from repro.runtime.engine_core import Rejected
    from repro.runtime.frontend import AsyncFrontend

    deadline = args.deadline_ms / 1e3 if args.deadline_ms else None

    async def go():
        handles, shed = [], []
        async with AsyncFrontend(eng) as fe:
            for i, p in enumerate(prompts):
                h = await fe.submit(Request(p, args.gen, sp,
                                            priority=i % args.priority_classes,
                                            deadline=deadline))
                (shed if isinstance(h, Rejected) else handles).append(h)
            for h in handles:
                await h.collect()
        return handles, shed

    t0 = time.time()
    handles, shed = asyncio.run(go())
    wall = time.time() - t0
    # post-admission deadline sheds resolve as closed "shed" streams
    shed += [h.rejected for h in handles if h.finish_reason == "shed"]
    done = [h for h in handles if h.finish_reason != "shed"]
    n_out = sum(len(h.tokens) for h in done)
    unit = "s" if args.deadline_ms else " ticks"  # engine-clock units (see above)
    print(f"online front: {len(done)} served / {len(shed)} shed of "
          f"{len(prompts)} requests ({args.priority_classes} priority classes, "
          f"deadline {args.deadline_ms or 'off'} ms, "
          f"max-inflight {args.max_inflight or 'off'})")
    print(f"streamed {n_out} tokens in {wall*1e3:.1f} ms "
          f"({n_out/max(wall, 1e-9):.0f} tok/s incl. compile)")
    for h in done[:2]:
        ttft = eng.ttft.get(h.uid)
        ttft_s = "?" if ttft is None else f"{ttft:.3f}{unit}"
        print(f"  req {h.uid} [{h.finish_reason}] ttft={ttft_s}:", h.tokens[:16])
    for r in shed[:2]:
        print(f"  shed [{r.reason}] retryable={r.retryable} "
              f"backoff_hint={r.backoff_hint:.2f}")


def _profiled(log_dir: str | None):
    """``jax.profiler.trace(log_dir)`` around the serving loop, or nothing
    when no directory is given."""
    return jax.profiler.trace(log_dir) if log_dir else contextlib.nullcontext()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--impl", default="exaq", choices=["exact", "exaq", "naive"])
    ap.add_argument("--bits", type=int, default=2)
    ap.add_argument("--clip-rule", default="paper", choices=["paper", "analytic"])
    ap.add_argument("--temperature", type=float, default=0.0, help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--eos-id", type=int, default=-1, help="-1 disables EOS stopping")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="block-paged KV cache with shared-prefix reuse (DESIGN.md §3)")
    ap.add_argument("--block-size", type=int, default=16, help="tokens per KV block (paged)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prompt tokens prefilled per interleaved chunk (paged)")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="pool size in blocks; 0 = full provisioning (paged)")
    ap.add_argument("--fused", dest="fused", action="store_true", default=None,
                    help="paged serving: fused Pallas paged-decode AND paged-prefill "
                         "kernels (no HBM KV gather on decode, no dense window copy "
                         "per prefill chunk; needs --impl exaq)")
    ap.add_argument("--no-fused", dest="fused", action="store_false",
                    help="paged serving: force the gather-then-dispatch references")
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=["fp32", "fp16", "bf16", "int8", "int4"],
                    help="KV cache storage dtype; int8 (paged only) stores the pool "
                         "quantized with per-block scales (DESIGN.md §6); int4 (paged "
                         "only) packs two values per byte with 4-bit sub-block scale "
                         "codes (DESIGN.md §10)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend the same N-token system prompt to every request")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel engine replicas, each with its own block "
                         "pool over a disjoint device slice (paged; DESIGN.md §9)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shards per replica: block pool split on "
                         "the kv-head axis over the 'model' mesh axis (paged; "
                         "DESIGN.md §9)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft up to K tokens per slot per "
                         "round and verify them in one fused paged-prefill call "
                         "(paged + greedy only; 0 = off; DESIGN.md §12)")
    ap.add_argument("--drafter", default="ngram", choices=["ngram"],
                    help="draft proposer for --spec-k: 'ngram' reuses the longest "
                         "matching suffix of the request's own context")
    ap.add_argument("--online", action="store_true",
                    help="asyncio serving front: streaming admission with "
                         "per-request cancellation, priority classes, and TTFT "
                         "deadlines over the paged engine (DESIGN.md §11)")
    ap.add_argument("--priority-classes", type=int, default=1,
                    help="online: cycle submissions through N priority classes "
                         "(0 = most urgent; the scheduler preempts across classes)")
    ap.add_argument("--deadline-ms", type=int, default=0,
                    help="online: per-request TTFT deadline in wall-clock ms "
                         "(0 = off); expired queued requests shed with a "
                         "structured retryable rejection + backoff hint")
    ap.add_argument("--max-inflight", type=int, default=0,
                    help="online: admission cap on requests in the system "
                         "(0 = off); overflow is shed at submit with a backoff "
                         "hint instead of growing the queue")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="record a profiler trace of the serving loop into DIR: "
                         "the engine's engine.* host spans beside its named "
                         "device programs (jit_paged_decode_chunk, ...)")
    args = ap.parse_args()
    validate_serve_args(args, device_count=jax.device_count())
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = cfg.with_quant(softmax_impl=args.impl, bits=args.bits, clip_rule=args.clip_rule)
    if args.paged and cfg.family in ("ssm", "hybrid"):
        # paged state pools checkpoint recurrent state per block; the SSD
        # recurrence must run per token so the checkpoints reproduce the
        # rectangular scan bit-exactly (DESIGN.md §13)
        cfg = dataclasses.replace(cfg, ssm_chunk=1)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    rng = np.random.default_rng(args.seed)
    sp = SamplingParams(temperature=args.temperature, top_k=args.top_k, top_p=args.top_p)
    eos = None if args.eos_id < 0 else args.eos_id

    print(f"arch={cfg.name} impl={args.impl} int{args.bits} kv={args.kv_dtype} "
          f"sampling=(T={sp.temperature}, k={sp.top_k}, p={sp.top_p})")

    if cfg.family in ("dense", "moe") or (args.paged and cfg.family in ("ssm", "hybrid")):
        from repro.runtime.engine import Engine, PagedEngine

        # ragged prompts: uniform in [prompt_len/2, prompt_len]
        lens = rng.integers(max(args.prompt_len // 2, 1), args.prompt_len + 1, args.requests)
        shared = rng.integers(0, cfg.vocab_size, args.shared_prefix)
        prompts = [np.concatenate([shared, rng.integers(0, cfg.vocab_size, int(n))])
                   for n in lens]
        config = args_to_config(args)

        if args.paged:
            # deadlines compare against the engine clock: wall seconds when
            # deadlines are live, deterministic scheduler ticks otherwise
            clock = time.monotonic if (args.online and args.deadline_ms) else None
            if args.dp > 1 or args.tp > 1:
                from repro.launch.mesh import make_replica_meshes

                meshes = make_replica_meshes(args.dp, args.tp)
                if args.dp > 1:
                    from repro.runtime.engine import DataParallelEngine

                    eng = DataParallelEngine(cfg, params, config, meshes=meshes,
                                             clock=clock)
                else:
                    eng = PagedEngine(cfg, params, config, mesh=meshes[0], clock=clock)
            else:
                eng = PagedEngine(cfg, params, config, clock=clock)
        else:
            eng = Engine(cfg, params, config)
        if args.online:
            with _profiled(args.profile_dir):
                _serve_online(eng, prompts, args, sp)
            return
        t0 = time.time()
        with _profiled(args.profile_dir):
            uids = [eng.submit(Request(p, args.gen, sp)) for p in prompts]
            results = eng.run()
        wall = time.time() - t0
        n_out = sum(len(g.tokens) for g in results.values())
        kind = "paged engine" if args.paged else "engine"
        if args.dp > 1 or args.tp > 1:
            kind += f" (dp={args.dp}, tp={args.tp})"
        print(f"{kind}: {args.requests} requests (prompts "
              f"{min(map(len, prompts))}-{max(map(len, prompts))} tok) "
              f"through {args.slots} slots")
        print(f"decoded {n_out} tokens in {wall*1e3:.1f} ms "
              f"({n_out/max(wall, 1e-9):.0f} tok/s incl. compile); "
              f"mean slot occupancy {eng.mean_occupancy:.2f}/{args.slots}")
        if args.paged:
            st = eng.pool_stats
            print(f"prefix-cache hit rate {100*eng.prefix_hit_rate:.1f}% "
                  f"({eng.stats['prefix_hit_tokens']}/{eng.stats['prompt_tokens']} prompt tokens); "
                  f"{eng.stats['prefill_chunks']} prefill chunks of {args.prefill_chunk}; "
                  f"queue wait {1e3*eng.stats['queue_wait_s']/max(eng.stats['admitted'], 1):.1f} "
                  f"ms per admission; "
                  f"pool {eng.kv_pool_bytes/2**20:.1f} MiB, "
                  f"{st.cow_copies} CoW copies, {st.evictions} evictions")
            if args.spec_k:
                es = eng.stats
                print(f"speculative: k={args.spec_k} drafter={args.drafter}; "
                      f"{es['spec_rounds']} verify rounds, accepted "
                      f"{es['spec_accepted']}/{es['spec_drafted']} drafts "
                      f"({es['spec_accepted']/max(es['spec_rounds'],1):.2f} per verify)")
        if args.dp > 1:
            for i, s in enumerate(eng.per_replica_stats):
                print(f"  replica {i}: {s['prefills']} requests, "
                      f"occupancy {s['mean_occupancy']:.2f}/{args.slots}, "
                      f"hit rate {100*s['prefix_hit_rate']:.1f}%")
        for uid in uids[: min(2, len(uids))]:
            print(f"  req {uid} [{results[uid].finish_reason}]:",
                  results[uid].tokens[:16])
    else:
        if sp != SamplingParams() or eos is not None:
            raise SystemExit(
                f"--temperature/--top-k/--top-p/--eos-id are engine-only; "
                f"family {cfg.family!r} uses the greedy rectangular loop"
            )
        B, S = args.slots, args.prompt_len
        prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
        t0 = time.time()
        with _profiled(args.profile_dir):
            gen = serve_rt.generate(params, cfg, prompts, args.gen)
            jax.block_until_ready(gen)
        wall = time.time() - t0
        print(f"rectangular loop ({cfg.family}): {B}x{args.gen} tokens in "
              f"{wall*1e3:.1f} ms ({B*args.gen/max(wall,1e-9):.0f} tok/s incl. compile)")
        for b in range(min(B, 2)):
            print(" ", np.asarray(gen[b])[:16].tolist())


if __name__ == "__main__":
    main()
