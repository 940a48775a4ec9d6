"""What keeps the program honest about where it runs: actor-host children
run JAX on the CPU whatever the parent's environment says, and the
persistent compile cache lives where the rule says."""

import os
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.system import SeedSystem
from repro.envs.catch import CatchEnv
from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def _det_policy(obs, ids):
    return np.zeros((obs.shape[0],), np.int32)


@pytest.mark.parametrize("parent_platforms", ["tpu", None])
def test_spawned_actor_host_runs_jax_on_cpu(monkeypatch, parent_platforms):
    """A CatchEnv host steps a pure-JAX env. The child inherits the
    parent's environment; with JAX_PLATFORMS=tpu and no pin it would fail
    to start its backend here (and on the chip's host, contend with the
    parent for the chip)."""
    jax.devices()                    # this process's backend is settled
    if parent_platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", parent_platforms)
    sys_ = SeedSystem(env_factory=CatchEnv, policy_step=_det_policy,
                      num_actors=1, unroll=4, envs_per_actor=2,
                      transport="socket", deadline_ms=1.0)
    stats = sys_.run(seconds=0.5, with_learner=False)
    assert stats["host_errors"] == [], stats["host_errors"]
    assert stats["env_frames"] > 0
    assert [s["jax_platform"] for s in sys_.pool.last_stats] == ["cpu"]


def test_compile_cache_honours_env_var(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before    # set nothing


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch,
                                                       tmp_path):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.chdir(tmp_path)              # not derived from the cwd
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.enable_compile_cache() == got    # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
    assert not os.path.relpath(got, REPO).startswith("..")
