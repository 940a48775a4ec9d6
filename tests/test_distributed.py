"""Distributed-path tests. These run in subprocesses because
xla_force_host_platform_device_count must be set before jax initializes
(the main pytest process stays single-device for the smoke tests)."""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8, timeout=420):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_moe_ep_matches_gather_impl():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.configs.base import ModelConfig
        from repro.launch.mesh import make_mesh
        from repro.nn.moe import init_moe, moe, moe_ep
        from repro.sharding.param import ArrayMaker
        from repro.sharding.ctx import sharding_ctx
        from repro.sharding.rules import DEFAULT_RULES, filter_rules
        cfg = ModelConfig(name="t", family="moe", num_layers=1, d_model=32,
                          num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                          num_experts=8, num_experts_per_tok=2, moe_d_ff=16,
                          n_shared_experts=1, capacity_factor=8.0, tp=4)
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = filter_rules(DEFAULT_RULES, mesh)
        p = init_moe(ArrayMaker(jax.random.PRNGKey(0)), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
        y_ref, _ = moe(cfg, p, x)
        with sharding_ctx(mesh, rules), jax.set_mesh(mesh):
            y_ep, _ = jax.jit(lambda p, x: moe_ep(cfg, p, x))(p, x)
            g_ref = jax.grad(lambda p, x: moe(cfg.with_(moe_impl='gather'),
                                              p, x)[0].sum())(p, x)
        err = float(jnp.abs(y_ref - y_ep).max())
        assert err < 1e-5, err
        # full-EP (experts over model+data)
        rules2 = dict(rules, experts=("model", "data"))
        with sharding_ctx(mesh, rules2), jax.set_mesh(mesh):
            y_full, _ = jax.jit(lambda p, x: moe_ep(cfg, p, x))(p, x)
        err2 = float(jnp.abs(y_ref - y_full).max())
        assert err2 < 1e-5, err2
        print("ok", err, err2)
    """)
    assert "ok" in out


def test_dryrun_cell_compiles_on_small_mesh():
    out = run_py("""
        import jax
        from repro.launch.mesh import make_mesh
        from repro.launch.dryrun import lower_cell
        mesh = make_mesh((2, 4), ("data", "model"))
        # reduced-scale check of the full lowering path on 8 virtual devices
        from repro.configs import SHAPES
        import repro.launch.dryrun as dr
        import repro.configs.shapes as shapes_mod
        from dataclasses import replace
        # seq must exceed internvl's 256 frontend tokens
        SHAPES["train_4k"] = replace(SHAPES["train_4k"], global_batch=8,
                                     seq_len=512)
        rep = lower_cell("internvl2-1b", "train_4k", mesh)
        assert rep["flops_per_chip"] > 0
        assert rep["terms"].dominant() in ("compute", "memory", "collective")
        print("ok")
    """, devices=8)
    assert "ok" in out


def test_elastic_reshard_restore():
    out = run_py("""
        import tempfile, jax, jax.numpy as jnp
        from repro.checkpoint import CheckpointManager
        from repro.configs.registry import make_model, smoke_config
        from repro.core.losses import init_train_state
        from repro.launch.ft import reshard_state
        from repro.launch.mesh import make_mesh
        from repro.optim import adamw
        cfg = smoke_config("qwen3-14b").with_(tp=2)
        bundle = make_model(cfg)
        opt = adamw(1e-3)
        state = init_train_state(bundle, opt, jax.random.PRNGKey(0))
        d = tempfile.mkdtemp()
        mgr = CheckpointManager(d, async_save=False)
        mgr.save(state, 5)
        # restore onto a DIFFERENT mesh (elastic: 8 -> 4 devices worth)
        mesh = make_mesh((2, 2), ("data", "model"))
        restored, step = reshard_state(mgr, bundle, opt, cfg, mesh)
        assert step == 5
        a = jax.tree.leaves(state["params"])[0]
        b = jax.tree.leaves(restored["params"])[0]
        import numpy as np
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0)
        print("ok")
    """, devices=8)
    assert "ok" in out
