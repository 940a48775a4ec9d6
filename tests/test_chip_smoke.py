"""`chip_smoke.py` off the chip: every phase at tiny sizes on the CPU (the
phases take their sizes and check placement against the default device),
the checks that fail a phase, and the script itself refusing to run
without a TPU."""

import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

import chip_smoke
from repro.configs.r2d2_atari import AtariConfig

REPO = Path(__file__).resolve().parents[1]
TINY_ATARI = AtariConfig(obs_size=36, obs_channels=1, core_dim=16,
                         num_actions=4, burn_in=2, unroll=6, n_step=2)


def _ledger_exact(stats):
    led = stats["onpolicy"]
    assert led["frames_generated"] == (led["frames_trained"]
                                       + led["frames_dropped"]
                                       + led["frames_pending"]), led
    assert led["frames_pending"] == 0, led
    assert led["frames_trained"] > 0, led


def _no_errors(stats):
    assert stats["learner_error"] is None, stats["learner_error"]
    assert stats["inference_error"] is None, stats["inference_error"]
    assert stats.get("host_errors", []) == []


def test_r2d2_phase_tiny():
    stats = chip_smoke.phase_r2d2_full_width(
        TINY_ATARI, num_actors=2, envs_per_actor=2, learner_batch=2,
        seconds=2.0)
    _no_errors(stats)
    assert stats["learner_steps"] >= 3
    assert stats["env_frames"] == stats["actor_iterations"] * 2


def test_vtrace_device_phase_tiny():
    stats = chip_smoke.phase_vtrace_device(
        num_workers=1, lanes=8, unroll=8, learner_batch=4, seconds=1.5)
    _no_errors(stats)
    _ledger_exact(stats)
    assert stats["learner_steps"] >= 3


def test_vtrace_shm_hosts_phase_tiny():
    stats = chip_smoke.phase_vtrace_shm_hosts(
        actors_per_host=1, envs_per_actor=2, unroll=8, learner_batch=2,
        seconds=2.0)
    _no_errors(stats)
    _ledger_exact(stats)
    assert stats["gateway_shm_frames"] > 0


_LEDGER = {"frames_generated": 10, "frames_trained": 6,
           "frames_dropped": 4, "frames_pending": 0}
_CLEAN = {"learner_error": None, "inference_error": None, "host_errors": [],
          "learner_steps": 3, "onpolicy": _LEDGER}


@pytest.mark.parametrize("broken, match", [
    ({"learner_error": "Traceback: boom"}, "learner_error"),
    ({"inference_error": "Traceback: boom"}, "inference_error"),
    ({"host_errors": ["host 1: Traceback: boom"]}, "host errors"),
    ({"onpolicy": dict(_LEDGER, frames_dropped=3)}, "not conserved"),
    ({"onpolicy": dict(_LEDGER, frames_trained=5, frames_pending=1)},
     "still pending"),
    ({"learner_steps": 2}, "learner steps < 3"),
])
def test_checks_fail_a_broken_run(broken, match):
    """A run that reports an error, leaks a frame or barely trained fails
    its phase instead of passing quietly."""
    stats = dict(_CLEAN, **broken)
    with pytest.raises(chip_smoke.SmokeFailure, match=match):
        chip_smoke._check_errors("p", stats)
        chip_smoke._check_ledger("p", stats)
        chip_smoke._require_learner_steps("p", stats)


def test_checks_pass_a_clean_run():
    chip_smoke._check_errors("p", _CLEAN)
    assert chip_smoke._check_ledger("p", _CLEAN) == _LEDGER
    chip_smoke._require_learner_steps("p", _CLEAN)


def _run(args, cwd, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    full.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=240)


def test_sharded_engine_phase_on_four_virtual_devices():
    code = textwrap.dedent("""
        import jax
        import chip_smoke
        assert len(jax.devices()) == 4
        chip_smoke.phase_sharded_engine(jax.devices(), lanes=8, unroll=6,
                                        learner_batch=4, seconds=1.5)
    """)
    out = _run(["-c", code], REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert out.returncode == 0, out.stderr[-4000:]
    assert "distinct_devices=4" in out.stdout
    assert "first_unroll_equal=True" in out.stdout


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_script_refuses_without_a_tpu(argv):
    out = _run([str(REPO / "chip_smoke.py"), *argv], REPO)
    assert out.returncode != 0
    assert "no TPU" in out.stderr and "'cpu'" in out.stderr, out.stderr
    assert '"ok"' not in out.stdout


def test_script_fails_without_the_repo(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    out = _run([str(tmp_path / "chip_smoke.py")], tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
