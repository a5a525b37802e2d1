"""Multi-device behaviours (pipeline parallelism, compressed psum, sharded
train step).  These need >1 device, so each test runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 — keeping the main test
process single-device per the dry-run contract.

Marked ``multidev``: excluded from the tier-1 run (pytest.ini), executed by
the CI multidev job / `pytest -m multidev`."""

import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.multidev


def _run(src: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # children must never reach for an accelerator the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(src)],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         timeout=420)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_pipeline_matches_sequential():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.pipeline import make_stage_mesh, pipeline_apply

        S, M, D = 4, 6, 16
        mesh = make_stage_mesh(S)
        key = jax.random.PRNGKey(0)
        params = {"w": jax.random.normal(key, (S, D, D)) / np.sqrt(D)}

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"])

        x = jax.random.normal(jax.random.fold_in(key, 1), (M, 3, D))
        out = pipeline_apply(stage_fn, params, x, mesh=mesh)

        ref = x
        for s in range(S):
            ref = jnp.tanh(ref @ params["w"][s])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
        print("pipeline OK")
    """)


def test_compressed_psum_error_feedback():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import compressed_psum

        from repro.compat import make_mesh

        mesh = make_mesh((8,), ("pod",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 37))

        def sync(g_local, err):
            return compressed_psum(g_local[0], err[0], "pod")

        fn = jax.shard_map(sync, mesh=mesh, in_specs=(P("pod"), P("pod")),
                           out_specs=(P(), P("pod")), check_vma=False)
        err0 = jnp.zeros((8, 64, 37))
        g_hat, err = fn(g, err0)
        err = err.reshape(8, 64, 37)                # out_specs stacks shards
        exact = jnp.mean(g, 0)
        rel = float(jnp.linalg.norm(g_hat - exact) / jnp.linalg.norm(exact))
        assert rel < 0.02, rel                      # int8 quantisation error
        # error feedback: same grads + fed-back residual -> two-step average
        # is closer than a single compressed step (EF compensates)
        g_hat2, _ = fn(g, err)
        avg = (np.asarray(g_hat) + np.asarray(g_hat2)) / 2
        rel_avg = float(np.linalg.norm(avg - np.asarray(exact)) / np.linalg.norm(np.asarray(exact)))
        assert rel_avg <= rel + 1e-6, (rel_avg, rel)
        print("compression OK", rel, rel_avg)
    """)


def test_sharded_train_step_runs_on_mesh():
    """The launch-time jit (in/out shardings, donation) on a real 2x4 mesh."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import ModelConfig
        from repro.optim import AdamWConfig
        from repro.parallel.sharding import batch_pspec, param_pspecs
        from repro.runtime.steps import init_train_state, train_step

        cfg = ModelConfig(name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                          d_ff=128, vocab_size=128, dtype="float32", remat="none",
                          microbatches=2)
        from repro.compat import make_mesh, shardings_for, use_mesh

        mesh = make_mesh((2, 4), ("data", "model"))
        with use_mesh(mesh):
            pspecs = param_pspecs(cfg, mesh)
            sspecs = shardings_for(mesh, {
                "params": pspecs, "opt": {"m": pspecs, "v": pspecs},
                "step": jax.sharding.PartitionSpec()})
            bspecs = shardings_for(mesh, {"tokens": batch_pspec(mesh),
                                          "labels": batch_pspec(mesh)})
            fn = jax.jit(lambda s, b: train_step(cfg, AdamWConfig(lr=1e-3), s, b),
                         in_shardings=(sspecs, bspecs), out_shardings=(sspecs, None),
                         donate_argnums=(0,))
            state = jax.jit(lambda k: init_train_state(cfg, k),
                            out_shardings=sspecs)(jax.random.PRNGKey(0))
            toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 128))
            batch = {"tokens": toks, "labels": toks}  # host arrays: jit places them
            losses = []
            for _ in range(4):
                state, metrics = fn(state, batch)
                losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0], losses
        print("sharded train OK", losses)
    """)


def test_pipeline_experiment_shards_over_mesh():
    """The jit-end-to-end Experiment sweep under an active mesh: the task
    instance axis shards over the data axis via parallel/sharding.maybe_shard
    (default SVD readout; the streaming Gram path has its own parity tests),
    and results match the single-device run."""
    _run("""
        import numpy as np
        from repro.compat import make_mesh, use_mesh
        from repro.core import SiliconMR, tasks
        from repro.pipeline import Experiment, ExperimentConfig

        dss = [tasks.narma10(360, seed=s) for s in range(8)]
        batch = (np.stack([d.inputs_train for d in dss]),
                 np.stack([d.targets_train for d in dss]),
                 np.stack([d.inputs_test for d in dss]),
                 np.stack([d.targets_test for d in dss]))
        cfg = ExperimentConfig(model=SiliconMR(), n_nodes=32, washout=40,
                               ridge_l2=(1e-6, 1e-4))
        res_single = Experiment(cfg).run(*batch)

        mesh = make_mesh((8,), ("data",))
        with use_mesh(mesh):
            res_mesh = Experiment(cfg).run(*batch)
        np.testing.assert_allclose(res_mesh.nrmse, res_single.nrmse, atol=1e-4)
        assert np.all(res_mesh.nrmse < 1.0)
        print("sharded experiment OK", np.round(res_mesh.nrmse, 3))
    """)


def test_session_slab_shards_over_mesh():
    """The online serving slab (pipeline/session) under a real 8-device mesh:
    SessionState leaves and the per-tick chunks shard over the batch axis via
    explicit NamedShardings, the jitted step runs distributed, and the solved
    readout / λ choice / reservoir carry match the single-device run."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.compat import make_mesh
        from repro.core import SiliconMR
        from repro.core.masking import make_mask
        from repro.pipeline.session import (SessionConfig, _session_step,
                                            session_init, session_solve)

        b, n, k, chunk = 8, 16, 96, 24
        cfg = SessionConfig(model=SiliconMR(), n_nodes=n, washout=24,
                            ridge_l2=(1e-6, 1e-4), chunk_k=chunk,
                            forgetting=0.99, state_method="fast",
                            use_kernel=False)
        mask = make_mask(n, seed=3)
        rng = np.random.default_rng(0)
        j = jnp.asarray(rng.uniform(0, 1, (b, k)), jnp.float32)
        y = jnp.asarray(rng.standard_normal((b, k)), jnp.float32)
        step = jax.jit(_session_step, static_argnames=("cfg", "refresh"))

        def drive(place):
            state = jax.tree_util.tree_map(place, session_init(cfg, b))
            preds = []
            for lo in range(0, k, chunk):
                y_hat, state = step(cfg, mask, state,
                                    place(j[:, lo:lo + chunk]),
                                    place(y[:, lo:lo + chunk]), refresh=True)
                preds.append(np.asarray(y_hat))
            return session_solve(cfg, state), np.concatenate(preds, axis=1)

        ref, preds_ref = drive(lambda x: x)
        mesh = make_mesh((8,), ("data",))
        shard = NamedSharding(mesh, P("data"))
        out, preds_mesh = drive(lambda x: jax.device_put(x, shard))
        assert len(out.g.sharding.device_set) == 8, out.g.sharding
        # the distributed vmapped eigh differs from single-device at the
        # last f32 digits -> readout within 1e-4
        np.testing.assert_allclose(np.asarray(out.w), np.asarray(ref.w),
                                   atol=1e-4)
        np.testing.assert_array_equal(np.asarray(out.lam_idx),
                                      np.asarray(ref.lam_idx))
        np.testing.assert_allclose(np.asarray(out.s), np.asarray(ref.s),
                                   atol=1e-6)
        np.testing.assert_allclose(preds_mesh, preds_ref, atol=1e-4)
        print("sharded session OK")
    """)
