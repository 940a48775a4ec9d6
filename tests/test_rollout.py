"""Device-resident rollout subsystem tests: fused env+policy `lax.scan`
unrolls (`repro.rollout`) vs the host loop, frame accounting, learner
integration through `SeedSystem(backend="device")`, and the throughput
acceptance gate (device >= vectorized host at equal (num_actors, E))."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.system import SeedSystem
from repro.envs.alesim import ALESimEnv
from repro.envs.cartpole import CartPoleEnv
from repro.envs.catch import CatchEnv
from repro.rollout import DeviceRolloutEngine, RolloutWorker, action_key
from repro.rollout.engine import first_inputs, next_inputs


def _random_policy_apply(num_actions):
    def policy_apply(params, core, inputs, key):
        n = inputs.obs.shape[0]
        return jax.random.randint(key, (n,), 0, num_actions), core
    return policy_apply


def _host_reference(env, E, T, seed, policy_apply, params=None):
    """Step-by-step host loop following the engine's exact key streams:
    lane keys `split(PRNGKey(seed), E)`, action keys `action_key(seed)`
    split once per step."""
    keys = jax.random.split(jax.random.PRNGKey(seed), E)
    vreset = jax.vmap(env.reset)
    vstep = jax.vmap(env.step)
    state, obs = vreset(keys)
    key, core = action_key(seed), None
    inputs = first_inputs(obs)
    out = {"obs": [], "actions": [], "rewards": [], "dones": []}
    for _ in range(T):
        key, sub = jax.random.split(key)
        actions, core = policy_apply(params, core, inputs, sub)
        actions = actions.astype(jnp.int32)
        out["obs"].append(np.asarray(inputs.obs))
        out["actions"].append(np.asarray(actions))
        state, obs, rewards, dones = vstep(state, actions)
        inputs = next_inputs(obs, actions, rewards, dones)
        out["rewards"].append(np.asarray(rewards, np.float32))
        out["dones"].append(np.asarray(dones))
    return {k: np.stack(v) for k, v in out.items()}


# ------------------------------ parity ---------------------------------------

@pytest.mark.parametrize("env_cls", [CartPoleEnv, CatchEnv])
def test_scan_rollout_matches_host_loop(env_cls):
    """Acceptance: the fused scan is step-for-step identical to a host loop
    over the same PRNG keys — same env-state evolution, actions, rewards,
    dones, across auto-reset boundaries."""
    env = env_cls()
    E, T, seed = 4, 50, 11
    policy = _random_policy_apply(env.num_actions)
    eng = DeviceRolloutEngine(env, policy, E, T, seed=seed)
    traj = eng.rollout(None)
    ref = _host_reference(env, E, T, seed, policy)
    np.testing.assert_allclose(traj["obs"], ref["obs"], atol=1e-6)
    np.testing.assert_array_equal(traj["actions"], ref["actions"])
    np.testing.assert_allclose(traj["rewards"], ref["rewards"], atol=1e-6)
    np.testing.assert_array_equal(traj["dones"], ref["dones"])


def test_scan_rollout_resumes_across_calls():
    """Back-to-back rollouts continue the same trajectories: two scans of T
    must equal one host loop of 2T (carry persists between device calls)."""
    env = CatchEnv()
    E, T, seed = 3, 20, 5
    policy = _random_policy_apply(env.num_actions)
    eng = DeviceRolloutEngine(env, policy, E, T, seed=seed)
    t1, t2 = eng.rollout(None), eng.rollout(None)
    ref = _host_reference(env, E, 2 * T, seed, policy)
    np.testing.assert_array_equal(
        np.concatenate([t1["actions"], t2["actions"]]), ref["actions"])
    np.testing.assert_allclose(
        np.concatenate([t1["rewards"], t2["rewards"]]), ref["rewards"],
        atol=1e-6)


def test_engine_with_recurrent_core_state():
    """Core state threads through the scan: an accumulator policy must see
    its own running sum advance T steps within one rollout."""
    env = CatchEnv()
    E, T = 2, 7

    def policy_apply(params, core, inputs, key):
        core = core + 1
        return jnp.zeros((inputs.obs.shape[0],), jnp.int32), core

    eng = DeviceRolloutEngine(env, policy_apply, E, T,
                              init_core=lambda e: jnp.zeros((e,), jnp.int32))
    eng.rollout(None)
    _, core, _, _ = eng._carry
    np.testing.assert_array_equal(np.asarray(core), np.full((E,), T))
    eng.rollout(None)
    _, core, _, _ = eng._carry
    np.testing.assert_array_equal(np.asarray(core), np.full((E,), 2 * T))


def test_engine_rejects_host_env():
    with pytest.raises(ValueError, match="pure-JAX env"):
        DeviceRolloutEngine(ALESimEnv(frame=8, step_cost=16),
                            _random_policy_apply(18), 2, 4)


# --------------------------- frame accounting --------------------------------

def test_engine_frame_accounting():
    E, T = 4, 12
    eng = DeviceRolloutEngine(CatchEnv, _random_policy_apply(3), E, T)
    for _ in range(3):
        eng.rollout(None)
    assert eng.scans == 3
    assert eng.frames == 3 * T * E


def test_worker_feeds_per_lane_unrolls_and_counts():
    E, T = 3, 6
    eng = DeviceRolloutEngine(CatchEnv, _random_policy_apply(3), E, T, seed=2)
    sunk = []
    w = RolloutWorker(0, eng, sunk.append, lambda: (None, 0))
    w.start()
    import time
    deadline = time.time() + 10.0
    while w.iterations < 2 and time.time() < deadline:
        time.sleep(0.01)
    w.stop()
    w.join()
    assert w.error is None, w.error
    assert w.iterations >= 2
    assert w.frames == w.iterations * T * E
    assert len(sunk) == w.iterations * E        # one unroll per lane per scan
    traj = sunk[0]
    assert traj["obs"].shape[0] == T
    assert traj["actions"].dtype == np.int32
    assert traj["rewards"].dtype == np.float32
    assert traj["dones"].dtype == np.float32
    # Catch episodes are rows-1 steps long, so scans crossed boundaries
    assert w.episodes > 0
    assert len(w.returns) == w.episodes


def test_seed_system_device_frame_accounting():
    E, T, N = 4, 8, 2
    sys_ = SeedSystem(env_factory=CatchEnv, backend="device",
                      policy_apply=_random_policy_apply(3),
                      num_actors=N, unroll=T, envs_per_actor=E)
    sys_.warmup()
    stats = sys_.run(seconds=0.6, with_learner=False)
    assert stats["backend"] == "device"
    assert stats["inference_error"] is None
    # frames = scans x T x E, exactly
    assert stats["env_frames"] == stats["scans"] * T * E
    assert stats["env_frames"] > 0
    for a in sys_.actors:
        assert a.frames == a.iterations * T * E
    # per-lane unrolls of length T landed in replay
    assert len(sys_.replay) > 0
    traj, _, _ = sys_.replay.sample(1)
    assert traj["obs"].shape[1] == T


# ------------------------- learner integration -------------------------------

def test_seed_system_device_with_learner_and_param_lag():
    """The learner publishes versioned params; workers refresh between
    scans and track the on-policy lag."""
    E, T = 4, 8

    def train_step(state, batch):
        return {"params": {"w": state["params"]["w"] + 1.0},
                "step": state.get("step", 0) + 1}, {"loss": np.float32(0.0)}

    sys_ = SeedSystem(env_factory=CatchEnv, backend="device",
                      policy_apply=_random_policy_apply(3),
                      num_actors=1, unroll=T, envs_per_actor=E,
                      train_step=train_step, state={"params": {"w": np.zeros(())},
                                                    "step": 0},
                      learner_batch=2, min_replay=2)
    sys_.warmup()
    stats = sys_.run(seconds=1.0)
    assert stats["learner_error"] is None, stats["learner_error"]
    assert stats["learner_steps"] > 0
    assert stats["param_refreshes"] > 0         # workers picked up new params
    assert stats["mean_param_lag"] > 0          # learner advanced between scans
    # all published versions were consumed in order: lag sums to the last
    # version each worker saw
    for a in sys_.actors:
        assert a.param_lag_total == a.param_version


def test_worker_error_is_surfaced():
    def bad_policy(params, core, inputs, key):
        raise TypeError("tracer-leak")

    eng = DeviceRolloutEngine(CatchEnv, bad_policy, 2, 4)
    w = RolloutWorker(0, eng, lambda t: None, lambda: (None, 0))
    w.start()
    w.join(timeout=10.0)
    assert w.error is not None and "tracer-leak" in w.error


# --------------------------- throughput gate ---------------------------------

@pytest.mark.skipif(os.environ.get("CI") == "true",
                    reason="wall-clock throughput ratio; shared CI runners "
                           "are too noisy for a hard perf gate")
def test_device_backend_beats_vectorized_host():
    """Acceptance: at equal (num_actors, E) on a pure-JAX env, the fused
    scan must supply at least the vectorized host backend's frames/s — it
    replaces T inference round-trips per unroll with one transfer."""
    N, E, T = 2, 8, 16

    def host_policy(obs, ids):
        return np.random.randint(0, 3, size=(obs.shape[0],))

    def run_host():
        sys_ = SeedSystem(env_factory=CatchEnv, policy_step=host_policy,
                          num_actors=N, unroll=T, envs_per_actor=E,
                          deadline_ms=1.0)
        sys_.warmup()
        return sys_.run(seconds=1.0, with_learner=False)["env_frames_per_s"]

    def run_device():
        sys_ = SeedSystem(env_factory=CatchEnv, backend="device",
                          policy_apply=_random_policy_apply(3),
                          num_actors=N, unroll=T, envs_per_actor=E)
        sys_.warmup()
        return sys_.run(seconds=1.0, with_learner=False)["env_frames_per_s"]

    host = max(run_host(), run_host())
    device = max(run_device(), run_device())
    assert device >= host, (host, device)


# ---------------------- provisioning: device point ---------------------------

def test_system_model_device_operating_point():
    from repro.core.provisioning import fit_paper_actor_model

    model, err = fit_paper_actor_model()
    assert err < 0.05
    dev = model.with_envs(8).with_device()
    # beats both host points at the paper's operating point
    assert float(dev.throughput(40)) > float(model.with_envs(8).throughput(40))
    assert float(dev.throughput(40)) > float(model.throughput(40))
    # not bounded by host threads: scales past the H/t_env ceiling
    cap = model.hw_threads / model.t_env
    assert float(dev.throughput(256)) > cap
    # ... but bounded by scan throughput: asymptote is 1/t_dev1
    assert float(dev.throughput(1e9)) <= 1.0 / dev.t_dev1 + 1e-6


def test_derating_model_envs_axis():
    from repro.core.provisioning import fit_paper_derating

    m = fit_paper_derating()
    assert m.envs_per_actor == 1
    # E=1 calibration unchanged (Fig 4 anchor)
    assert float(m.slowdown(0.5)) == pytest.approx(1.06, abs=1e-6)
    # more lanes per actor -> more overlap -> derating hides better
    assert float(m.with_envs(8).slowdown(0.5)) < float(m.slowdown(0.5))
    ss = [float(m.with_envs(E).slowdown(0.25)) for E in (1, 2, 4, 8)]
    assert all(b < a for a, b in zip(ss, ss[1:]))
    assert float(m.with_envs(8).slowdown(1.0)) == 1.0
