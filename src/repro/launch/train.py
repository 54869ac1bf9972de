"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --steps 200 --batch 8 --seq 256 --reduced --ckpt-dir /tmp/ckpt

Production behaviours demonstrated at any scale:
  * mesh-aware pjit (in/out shardings from runtime/sharding.py)
  * checkpoint/restart: atomic keep-k checkpoints, auto-resume from latest,
    deterministic data replay (pipeline state in checkpoint meta)
  * failure handling: on any step exception the driver re-loads the last
    checkpoint and continues (crash-equivalent restart); a heartbeat file lets
    an external watchdog re-exec the process on hangs
  * optional EXAQ-STE quantized-softmax training (paper §7.2 extension)
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro.checkpointing.manager import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import SyntheticLMData
from repro.launch.mesh import make_mesh
from repro.optim.adamw import AdamW
from repro.optim.schedule import cosine_with_warmup
from repro.runtime import sharding as shd
from repro.runtime import train as train_rt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--reduced", action="store_true", help="tiny same-family config (CPU scale)")
    ap.add_argument("--d-model", type=int, default=0, help="override width (with --reduced)")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data-mesh", type=int, default=0, help="data axis size (0 = all local devices)")
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--exaq-train", action="store_true", help="EXAQ-STE softmax during training")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        over = {}
        if args.d_model:
            over.update(d_model=args.d_model, d_ff=args.d_model * 3)
        if args.layers:
            over["num_layers"] = args.layers
        cfg = cfg.reduced(**over)
    cfg = cfg.with_quant(softmax_impl="exaq" if args.exaq_train else "exact")

    n_dev = len(jax.devices())
    dsize = args.data_mesh or max(n_dev // args.model_mesh, 1)
    mesh = make_mesh((dsize, args.model_mesh), ("data", "model")) if dsize * args.model_mesh > 1 else None

    opt = AdamW(lr=cosine_with_warmup(args.lr, 20, args.steps))
    data = SyntheticLMData(cfg.vocab_size, args.seq, args.batch, seed=0)
    state = train_rt.init_train_state(cfg, opt, jax.random.PRNGKey(0))
    step_fn = train_rt.make_train_step(cfg, opt, microbatches=args.microbatches)

    mgr = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    start = 0
    if mgr is not None and mgr.latest_step() is not None:
        state, meta = mgr.restore(jax.eval_shape(lambda: state))
        data.load_state_dict(meta["data"])
        start = int(meta["step"])
        print(f"resumed from step {start}")

    if mesh:
        st_sh = train_rt.state_shardings(cfg, mesh, jax.eval_shape(lambda: state))
        with mesh:
            state = jax.device_put(state, st_sh)
            jit_step = jax.jit(step_fn, in_shardings=(st_sh, None), out_shardings=(st_sh, None))
    else:
        jit_step = jax.jit(step_fn)

    hb = os.path.join(args.ckpt_dir or "/tmp", "heartbeat")
    t0 = time.time()
    i = start
    while i < args.steps:
        batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
        try:
            if mesh:
                with mesh, shd.activation_rules(mesh, shd.make_activation_rules(cfg, mesh)):
                    state, metrics = jit_step(state, batch)
            else:
                state, metrics = jit_step(state, batch)
        except Exception as e:  # crash-equivalent restart from last checkpoint
            if mgr is None or mgr.latest_step() is None:
                raise
            print(f"step {i} failed ({e}); restoring last checkpoint")
            state, meta = mgr.restore(jax.eval_shape(lambda: state))
            data.load_state_dict(meta["data"])
            i = int(meta["step"])
            continue
        i += 1
        with open(hb, "w") as f:
            f.write(str(time.time()))
        if i % 10 == 0 or i == args.steps:
            print(f"step {i}: loss={float(metrics['loss']):.4f} lr={float(metrics['lr']):.2e} "
                  f"({(time.time()-t0)/max(i-start,1):.2f}s/step)")
        if mgr is not None and (i % args.ckpt_every == 0 or i == args.steps):
            mgr.save(i, state, extra_meta={"step": i, "data": data.state_dict(), "arch": cfg.name})
    if mgr is not None:
        mgr.wait()
    print("done")


if __name__ == "__main__":
    main()
