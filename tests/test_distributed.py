"""Distributed-correctness tests.

These run in subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8
(the flag must not leak into this test process, per the assignment).  Each
script asserts that the sharded/shard_map implementation matches the
single-device reference numerically.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices_script(body: str, timeout=420):
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(2, 4)   # ('data' 2, 'model' 4)
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


def test_flash_decode_matches_single_device():
    run_devices_script("""
        from repro.models.flash_decode import flash_decode, _partial_attend
        from repro.models.common import ParallelCtx
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        B, KV, G, S, hd = 4, 2, 3, 64, 16
        q = jax.random.normal(ks[0], (B, KV, G, hd))
        k = jax.random.normal(ks[1], (B, S, KV, hd))
        v = jax.random.normal(ks[2], (B, S, KV, hd))
        slot_pos = jnp.broadcast_to(jnp.arange(S), (B, S))
        cur = jnp.array([10, 30, 50, 63])
        ref = flash_decode(q, k, v, slot_pos, cur, window=None,
                           softmax_scale=hd**-0.5, ctx=None)
        ctx = ParallelCtx(mesh=mesh, dp=("data",), tp="model",
                          seq_axes=("model",))
        with jax.set_mesh(mesh):
            out = jax.jit(lambda *a: flash_decode(
                *a, window=None, softmax_scale=hd**-0.5, ctx=ctx))(
                q, k, v, slot_pos, cur)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        # windowed variant too
        ref_w = flash_decode(q, k, v, slot_pos, cur, window=16,
                             softmax_scale=hd**-0.5, ctx=None)
        with jax.set_mesh(mesh):
            out_w = jax.jit(lambda *a: flash_decode(
                *a, window=16, softmax_scale=hd**-0.5, ctx=ctx))(
                q, k, v, slot_pos, cur)
        np.testing.assert_allclose(np.asarray(out_w), np.asarray(ref_w),
                                   rtol=2e-5, atol=2e-5)
        print("flash_decode distributed OK")
    """)


def test_moe_alltoall_matches_gather():
    run_devices_script("""
        import dataclasses
        from repro.configs import get_config
        from repro.configs.base import MoEConfig
        from repro.models import moe as moe_mod
        from repro.models.common import ParallelCtx
        cfg = get_config("qwen3-4b").reduced()
        cfg = dataclasses.replace(cfg, moe=MoEConfig(
            num_experts=4, top_k=2, d_ff_expert=64, capacity_factor=64.0))
        params = moe_mod.init_moe(cfg, jax.random.PRNGKey(0))
        T, d = 32, cfg.d_model
        h = jax.random.normal(jax.random.PRNGKey(1), (T, d))
        y_ref, aux_ref = moe_mod.moe_gather(cfg, params, h, None)
        ctx = ParallelCtx(mesh=mesh, dp=("data",), tp="model",
                          seq_axes=("model",), moe_impl="alltoall")
        with jax.set_mesh(mesh):
            y, aux = jax.jit(lambda p, x: moe_mod.moe_alltoall(
                cfg, p, x, ctx))(params, h)
        # identical routing + huge capacity => identical outputs
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-3, atol=2e-3)
        print("moe alltoall == gather OK")
    """)


def test_sharded_train_step_matches_single_device():
    """One training step of the reduced qwen3 on the debug mesh must equal
    the unsharded step (same loss, same updated params)."""
    run_devices_script("""
        from repro.configs import get_config
        from repro.launch import steps as S
        from repro.launch.shardings import (batch_shardings, opt_shardings,
                                            param_shardings)
        from repro.models import init_params
        from repro.configs.shapes import InputShape
        import dataclasses
        cfg = dataclasses.replace(get_config("qwen3-4b").reduced(),
                                  dtype="float32")
        shape = InputShape("t", 32, 8, "train")
        ctx = S.make_ctx(mesh, shape, multi_pod=False)
        step, opt = S.make_train_step_fn(cfg, ctx, q_chunk=32)
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                  cfg.vocab_size)
        batch = {"inputs": {"tokens": toks}, "labels": jnp.roll(toks, -1, 1)}
        # reference: no ctx, no mesh
        step_ref, _ = S.make_train_step_fn(cfg, dataclasses.replace(
            ctx, mesh=None) if False else ctx, q_chunk=32)
        from repro.training.loop import make_loss_fn
        loss_ref = make_loss_fn(cfg, ctx=None, q_chunk=32)(params, batch)
        with jax.set_mesh(mesh):
            p_sh = param_shardings(mesh, params)
            o_sh = opt_shardings(mesh, opt_state)
            b_sh = {"inputs": batch_shardings(mesh, batch["inputs"], ctx.dp),
                    "labels": batch_shardings(mesh, {"l": batch["labels"]},
                                              ctx.dp)["l"]}
            fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh))
            p2, o2, loss = fn(params, opt_state, batch)
        np.testing.assert_allclose(float(loss), float(loss_ref),
                                   rtol=2e-4, atol=2e-4)
        print("sharded train step OK, loss", float(loss))
    """)


def test_prefill_step_lowers_on_debug_mesh():
    run_devices_script("""
        from repro.configs import get_config
        from repro.configs.shapes import InputShape
        from repro.launch import steps as S
        from repro.launch.shardings import batch_shardings, param_shardings
        cfg = get_config("gemma3-4b").reduced()
        shape = InputShape("p", 128, 8, "prefill")
        ctx = S.make_ctx(mesh, shape, multi_pod=False)
        step = S.make_prefill_step_fn(cfg, ctx, q_chunk=64)
        params = S.abstract_params(cfg)
        specs = S.input_specs(cfg, shape)
        with jax.set_mesh(mesh):
            p_sh = param_shardings(mesh, params)
            b_sh = batch_shardings(mesh, specs["inputs"], ctx.dp)
            lowered = jax.jit(step, in_shardings=(p_sh, b_sh)).lower(
                params, specs["inputs"])
            compiled = lowered.compile()
        assert compiled.cost_analysis() is not None
        print("prefill lowering OK")
    """)


@pytest.mark.slow
def test_dryrun_subprocess_one_combo():
    """The real 512-device dry-run entrypoint on one cheap combo."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen3-4b",
         "--shape", "decode_32k", "--mesh", "single", "--no-probes",
         "--out", "/tmp/dryrun_test"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    assert "0 failures" in r.stdout
