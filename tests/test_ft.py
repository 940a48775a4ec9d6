"""Fault-tolerance behaviours: supervisor restart, straggler detection,
inference batching deadline, and generation smoke."""

import queue
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import CheckpointManager
from repro.core.inference import InferenceServer
from repro.launch.ft import HeartbeatMonitor, SimulatedFailure, Supervisor


def test_supervisor_restarts_from_checkpoint(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    calls = {"n": 0}

    def make_state():
        return {"w": jnp.zeros((2,)), "step": jnp.array(0)}

    def train_loop(state, start):
        for i in range(start, 10):
            state = {"w": state["w"] + 1.0, "step": jnp.array(i + 1)}
            if i == 4 and calls["n"] == 0:
                calls["n"] += 1
                mgr.save(state, i + 1)
                raise SimulatedFailure("boom")
        return state

    sup = Supervisor(mgr, max_restarts=2)
    final = sup.run(make_state, train_loop)
    assert int(final["step"]) == 10
    assert len(sup.restarts) == 1
    # progress was preserved: exactly 10 increments happened in total
    assert float(final["w"][0]) == 10.0


def test_supervisor_gives_up_after_max_restarts(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)

    def train_loop(state, start):
        raise SimulatedFailure("always")

    sup = Supervisor(mgr, max_restarts=2)
    with pytest.raises(RuntimeError, match="restarts"):
        sup.run(lambda: {"w": jnp.zeros(())}, train_loop)


def test_inference_deadline_closes_partial_batches():
    seen = []

    def policy_step(obs, ids):
        seen.append(len(ids))
        return np.zeros((obs.shape[0],), np.int32)

    srv = InferenceServer(policy_step, max_batch=64, deadline_ms=5.0)
    srv.start()
    reply = srv.submit_batch(0, np.zeros((1, 4), np.float32))
    action = reply.get(timeout=2.0)[0]
    srv.stop()
    assert action == 0
    assert seen and seen[0] == 1          # batch closed at deadline, not at 64


def test_heartbeat_monitor_flags_stalled_actor():
    class FakeActor:
        def __init__(self, i):
            self.actor_id = i
            self.steps = 0

    actors = [FakeActor(0), FakeActor(1)]
    mon = HeartbeatMonitor(stall_s=0.05)
    assert mon.check(actors) == []
    actors[0].steps = 5                   # actor 0 progresses, actor 1 stalls
    time.sleep(0.08)
    assert mon.check(actors) == [1]


def test_greedy_generate_smoke():
    from repro.configs.registry import make_model, smoke_config
    from repro.launch.serve import greedy_generate
    cfg = smoke_config("qwen2.5-32b")
    bundle = make_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    toks = jnp.ones((2, 6), jnp.int32)
    out = greedy_generate(bundle, params, {"tokens": toks}, steps=5,
                          max_len=32, dtype=jnp.float32)
    assert out.shape == (2, 5)
    assert int(out.max()) < cfg.padded_vocab
