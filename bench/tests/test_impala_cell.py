"""The `impala_deep_atari.device` cell on the CPU, cut down as `tiny.py`
cuts the others, and the FLOP counts of its network:

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_impala_cell.py
"""

import argparse
import copy
import time

import jax
import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)

import flops_impala  # noqa: E402
import harness  # noqa: E402

NAME = "impala_deep_atari.device"


def tiny_cell():
    cell = copy.deepcopy(harness.load_cell(NAME))
    cell["config"]["model"].update(obs_size=20, obs_channels=2,
                                   channels=[4, 8, 8], fc_dim=16,
                                   core_dim=16, unroll=6)
    cell["config"].update(learner_batch=4)
    cell["traffic"].update(num_workers=2, envs_per_worker=8,
                           queue_capacity=16, fill_allowance_s=120)
    cell["traffic"]["env_kwargs"].update(frame=20, channels=2, step_cost=64)
    return cell


def run(fault=None, controls=False):
    args = argparse.Namespace(workload=NAME, seed=123456789012,
                              seconds=tiny.SECONDS, trace=0)
    return harness.run(args, time.perf_counter(), cell=tiny_cell(),
                       require_chip=False, fault=fault, controls=controls)


def test_forward_macs_and_parameters_match_the_hand_count():
    from repro.models.impala import impala_actor_critic
    from cells.impala_device import network_config

    model = harness.load_cell(NAME)["config"]["model"]
    macs = flops_impala.layer_macs(model)
    assert macs == {"stack0.conv": 4064256, "stack0.res": 16257024,
                    "stack1.conv": 8128512, "stack1.res": 16257024,
                    "stack2.conv": 4064256, "stack2.res": 4460544,
                    "fc": 991232, "lstm": 543744, "policy": 4608,
                    "baseline": 256}
    assert sum(macs.values()) == 54771456
    fwd, bwd = 54771456, 2 * 54771456 - 4064256
    assert flops_impala.step_flops(model, 32) == 2 * 32 * (20 * fwd
                                                           + 19 * bwd)
    assert flops_impala.policy_flops(model) == 2 * (54771456 - 256)
    init_fn, _, _ = impala_actor_critic(network_config(model))
    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    sizes = {k: sum(x.size for x in jax.tree.leaves(shapes[k]))
             for k in shapes}
    assert sum(sizes.values()) == 1638883
    assert sizes["fc"] == 991488 and sizes["lstm"] == 544768
    assert sizes["policy"] + sizes["baseline"] == 4883


def test_cell_reaches_its_window_and_is_correct():
    r = run()
    assert r["correct"], r["checks"]
    assert not r["_errors"]
    assert r["_compiles_in_window"] == 0
    assert r["attempted"] > 0
    assert "train_frames_per_s" in r["metrics"]
    assert r["checks"]["ledger_gap"]["value"] == 0.0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "core_zeroed"])
def test_a_fault_in_the_timed_path_is_not_correct(fault):
    r = run(fault=fault)
    assert not r["correct"], r["checks"]
    caught_by = {"state_unchanged": "update_norm_gap",
                 "core_zeroed": "logprob_gap"}
    if fault in caught_by:
        check = r["checks"][caught_by[fault]]
        assert check["value"] > check["limit"], check


def test_the_bfloat16_control_and_the_zeroed_core_are_not_correct():
    r = run(controls=True)
    limits = tiny_cell()["traffic"]["limits"]
    for kind in ("control", "core_zeroed"):
        readings = r["_readings"][kind]
        assert any(v > limits[k] for k, (v, _) in readings.items()
                   if k in limits), (kind, readings)
