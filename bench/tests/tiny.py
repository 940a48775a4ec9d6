"""Cut-down copies of the cells, small enough for the CPU, for the tests."""

import argparse
import copy
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402

SECONDS = 1.5


def r2d2_cell():
    cell = copy.deepcopy(harness.load_cell("r2d2_atari.inproc"))
    cell["config"]["model"].update(obs_size=36, core_dim=32, burn_in=4,
                                   unroll=12, n_step=3)
    cell["config"].update(learner_batch=4, replay_capacity=16)
    cell["traffic"].update(num_actors=2, envs_per_actor=2, min_replay=4,
                           fill_allowance_s=120)
    cell["traffic"]["env_kwargs"].update(frame=36, step_cost=64)
    return cell


def vtrace_cell():
    cell = copy.deepcopy(harness.load_cell("vtrace_mlp.device"))
    cell["config"]["model"].update(hidden=16, unroll=6)
    cell["config"].update(learner_batch=4)
    cell["traffic"].update(num_workers=2, envs_per_worker=8,
                           queue_capacity=16, fill_allowance_s=120)
    return cell


def shm_cell():
    cell = copy.deepcopy(harness.load_cell("vtrace_mlp.shm_hosts"))
    cell["config"]["model"].update(hidden=16, unroll=6)
    cell["config"].update(learner_batch=4)
    cell["traffic"].update(num_hosts=2, num_actors=4, envs_per_actor=2,
                           queue_capacity=16, fill_allowance_s=30)
    cell["traffic"]["env_kwargs"].update(obs_dim=16, step_cost=64)
    return cell


CELLS = {"r2d2_atari.inproc": r2d2_cell, "vtrace_mlp.device": vtrace_cell,
         "vtrace_mlp.shm_hosts": shm_cell}


def run(name, seed=123456789012, fault=None, controls=False):
    args = argparse.Namespace(workload=name, seed=seed, seconds=SECONDS,
                              trace=0)
    return harness.run(args, time.perf_counter(), cell=CELLS[name](),
                       require_chip=False, fault=fault, controls=controls)
