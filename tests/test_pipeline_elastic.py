"""Pipeline parallelism (GPipe over 'pod') and elastic re-mesh restore —
subprocess tests (virtual device count must precede jax init)."""

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
    out = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                         env=env, timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_gpipe_pipeline_matches_sequential():
    print(_run("""
        from repro.runtime.pipeline_parallel import pipeline_forward, stack_stages
        mesh = make_mesh((4,), ("pod",))
        rng = np.random.default_rng(0)
        L, D = 8, 16
        W = jnp.asarray(rng.normal(0, 0.3, (L, D, D)), jnp.float32)

        def layer(w, h):
            return jnp.tanh(h @ w)

        def stage(ws, h):   # ws: (L/P, D, D)
            def body(hh, w):
                return layer(w, hh), None
            return jax.lax.scan(body, h, ws)[0]

        x = jnp.asarray(rng.normal(0, 1, (6, 4, D)), jnp.float32)  # 6 microbatches
        # sequential reference
        ref = x
        def seq_body(hh, w):
            return layer(w, hh), None
        ref = jax.lax.scan(seq_body, x.reshape(-1, D), W)[0].reshape(x.shape)

        got = pipeline_forward(stage, stack_stages(W, 4), x, mesh, axis="pod")
        err = float(jnp.abs(got - ref).max())
        assert err < 1e-5, err
        print("GPIPE_OK", err)
    """))


def test_elastic_restore_on_different_mesh(tmp_path):
    """Checkpoint saved from a (4,2) mesh restores onto a (2,4) mesh and
    training continues with identical losses (mesh-agnostic checkpoints)."""
    print(_run(f"""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpointing.manager import CheckpointManager
        from repro.configs import get_config
        from repro.optim.adamw import AdamW
        from repro.runtime import sharding as shd, train as train_rt
        from repro.data.pipeline import SyntheticLMData

        cfg = get_config("internlm2-1.8b").reduced(num_layers=2, d_model=64, d_ff=128, vocab_size=128)
        opt = AdamW(lr=1e-3)
        data = SyntheticLMData(cfg.vocab_size, 32, 8, seed=0)
        state = train_rt.init_train_state(cfg, opt, jax.random.PRNGKey(0))
        step = train_rt.make_train_step(cfg, opt, compute_dtype=jnp.float32)

        def run_on(mesh_shape, state, batches):
            mesh = make_mesh(mesh_shape, ("data", "model"))
            rules = shd.make_activation_rules(cfg, mesh)
            losses = []
            with mesh, shd.activation_rules(mesh, rules):
                st_sh = train_rt.state_shardings(cfg, mesh, jax.eval_shape(lambda: state))
                state = jax.device_put(jax.device_get(state), st_sh)
                f = jax.jit(step, in_shardings=(st_sh, None), out_shardings=(st_sh, None))
                for b in batches:
                    state, m = f(state, b)
                    losses.append(float(m["loss"]))
            return jax.device_get(state), losses

        batches = [{{k: jnp.asarray(v) for k, v in data.next_batch().items()}} for _ in range(4)]
        # reference: all 4 steps on (4,2)
        s_ref, l_ref = run_on((4, 2), state, batches)
        # elastic: 2 steps on (4,2), checkpoint, restore onto (2,4), 2 more
        s_a, l_a = run_on((4, 2), state, batches[:2])
        mgr = CheckpointManager(r"{tmp_path}", async_write=False)
        mgr.save(2, s_a, extra_meta={{"step": 2}})
        s_b, meta = mgr.restore(jax.eval_shape(lambda: s_a))
        s_b, l_b = run_on((2, 4), s_b, batches[2:])
        diff = [abs(x - y) for x, y in zip(l_ref, l_a + l_b)]
        assert max(diff) < 2e-4, (l_ref, l_a + l_b)
        print("ELASTIC_OK", max(diff))
    """))
