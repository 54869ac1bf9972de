"""Distributed correctness on a small virtual mesh (subprocess: device count
must be set before jax initializes).

  * sharded train step == single-device train step (bitwise-ish)
  * sharded EXAQ serve decode == single-device decode
  * compressed_psum (shard_map) == plain mean within EF error bounds
  * tiny-config dry-run (lower+compile+cost extraction) end-to-end
"""

import os
import subprocess
import sys
import textwrap


ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(body: str) -> str:
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.launch.mesh import make_mesh
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sharded_train_step_matches_single_device():
    print(_run("""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.optim.adamw import AdamW
        from repro.runtime import sharding as shd, train as train_rt
        from repro.data.pipeline import SyntheticLMData

        cfg = get_config("internlm2-1.8b").reduced(num_layers=2, d_model=64, d_ff=128, vocab_size=128)
        opt = AdamW(lr=1e-3)
        state = train_rt.init_train_state(cfg, opt, jax.random.PRNGKey(0))
        data = SyntheticLMData(cfg.vocab_size, 32, 8, seed=0)
        batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
        # fp32 compute isolates the sharding mechanism from bf16 Adam
        # sign-flips on near-zero gradients (2*lr excursions)
        step = train_rt.make_train_step(cfg, opt, compute_dtype=jnp.float32)

        s1, m1 = jax.jit(step)(state, batch)

        mesh = make_mesh((4, 2), ("data", "model"))
        rules = shd.make_activation_rules(cfg, mesh)
        with mesh, shd.activation_rules(mesh, rules):
            st_sh = train_rt.state_shardings(cfg, mesh, jax.eval_shape(lambda: state))
            b_sh = train_rt.batch_shardings(mesh, jax.eval_shape(lambda: batch))
            state_p = jax.device_put(state, st_sh)
            batch_p = jax.device_put(batch, b_sh)
            s2, m2 = jax.jit(step, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None))(state_p, batch_p)

        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4, (m1["loss"], m2["loss"])
        d = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), s1["params"], jax.device_get(s2["params"]))
        md = max(jax.tree.leaves(d))
        assert md < 1e-4, md
        print("SHARDED_TRAIN_OK", float(m1["loss"]))
    """))


def test_sharded_exaq_decode_matches_single_device():
    print(_run("""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import build_model
        from repro.runtime import serve as serve_rt, sharding as shd

        cfg = get_config("yi-6b").reduced(num_layers=2, d_model=64, d_ff=128, vocab_size=128)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0), jnp.bfloat16)
        B, S = 8, 16
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
        cache = serve_rt.init_cache(cfg, B, S + 4)
        pre, dec = serve_rt.make_serve_fns(cfg)
        lg1, cache1 = jax.jit(pre)(params, {"tokens": toks}, cache)
        nxt1, cache1, logits1 = jax.jit(dec)(params, jnp.zeros((B, 1), jnp.int32), cache1)

        mesh = make_mesh((4, 2), ("data", "model"))
        rules = shd.make_activation_rules(cfg, mesh)
        with mesh, shd.activation_rules(mesh, rules):
            p_sh = shd.tree_shardings(jax.eval_shape(lambda: params), cfg, mesh, mode="serve")
            c_sh = serve_rt.cache_shardings(cfg, mesh, jax.eval_shape(lambda: cache))
            tok_sh = NamedSharding(mesh, P(("data",), None))
            params_p = jax.device_put(params, p_sh)
            cache_p = jax.device_put(cache, c_sh)
            lg2, cache2 = jax.jit(pre, in_shardings=(p_sh, {"tokens": tok_sh}, c_sh), out_shardings=(None, c_sh))(
                params_p, {"tokens": jax.device_put(toks, tok_sh)}, cache_p)
            nxt2, cache2, logits2 = jax.jit(dec, in_shardings=(p_sh, tok_sh, c_sh), out_shardings=(tok_sh, c_sh, None))(
                params_p, jax.device_put(jnp.zeros((B, 1), jnp.int32), tok_sh), cache2)

        a, b = np.asarray(logits1, np.float32), np.asarray(jax.device_get(logits2), np.float32)
        assert np.abs(a - b).max() < 0.15, np.abs(a - b).max()   # bf16 + collective reassoc
        agree = (np.asarray(nxt1) == np.asarray(jax.device_get(nxt2))).mean()
        assert agree >= 0.8, agree
        print("SHARDED_DECODE_OK")
    """))


def test_compressed_psum_shard_map():
    print(_run("""
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import compressed_psum

        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(0, 1, (8, 256)), jnp.float32)   # one row per device
        err = jnp.zeros_like(g)

        @partial(jax.shard_map, mesh=mesh, in_specs=(P("data", None), P("data", None)),
                 out_specs=(P("data", None), P("data", None)))
        def sync(gi, ei):
            m, e2 = compressed_psum(gi[0], ei[0], "data")
            return m[None], e2[None]

        mean, err2 = sync(g, err)
        true_mean = np.asarray(g).mean(0)
        got = np.asarray(mean)[0]
        rel = np.abs(got - true_mean).max() / (np.abs(true_mean).max() + 1e-9)
        assert rel < 0.15, rel
        print("COMPRESSED_PSUM_OK", rel)
    """))


def test_tiny_dryrun_end_to_end():
    """dryrun machinery on a reduced config + 8-device mesh: lower, compile,
    trip-counted costs, collective extraction."""
    print(_run("""
        import repro.launch.dryrun as dr
        from repro.configs import get_config
        from repro.optim.adamw import AdamW
        from repro.runtime import sharding as shd, train as train_rt
        from repro.utils import hlo_cost

        cfg = get_config("yi-6b").reduced(num_layers=2, d_model=64, d_ff=128, vocab_size=256)
        mesh = make_mesh((4, 2), ("data", "model"))
        opt = AdamW(lr=1e-3)
        rules = shd.make_activation_rules(cfg, mesh)
        with mesh, shd.activation_rules(mesh, rules):
            state_struct = jax.eval_shape(lambda k: train_rt.init_train_state(cfg, opt, k),
                                          jax.ShapeDtypeStruct((2,), jnp.uint32))
            st_sh = train_rt.state_shardings(cfg, mesh, state_struct)
            specs = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
                     "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
            b_sh = train_rt.batch_shardings(mesh, specs)
            step = train_rt.make_train_step(cfg, opt)
            lowered = jax.jit(step, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None)).lower(state_struct, specs)
            compiled = lowered.compile()
        txt = compiled.as_text()
        cs = hlo_cost.analyze(txt, 8)
        assert cs.flops > 0 and cs.bytes > 0
        assert cs.collective_total > 0  # grad sync must appear
        print("TINY_DRYRUN_OK flops=%.3g coll=%.3g" % (cs.flops, cs.collective_total))
    """))
