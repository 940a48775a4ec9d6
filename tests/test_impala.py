"""IMPALA's deep ResNet-LSTM under V-trace on the device backend, at a
small size on the CPU, against the plain reference the benchmark checks
the chip with (`bench/reference/impala_deep.py`, loaded by path as the
harness loads it).

Tolerances: program and reference both compute in float32 here, and on
the CPU the default matmul precision is full float32, so they differ only
in the order of sums: 1e-5 relative (1e-6 absolute) on forwards,
logprobs, losses and gradients. A wrong core, reset or input moves a
logprob by 1e-2 or more.
"""

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.impala_atari import ImpalaConfig
from repro.core.actor import flush_lane_unrolls
from repro.core.system import SeedSystem
from repro.envs.alesim import ALESimEnv, ALESimJaxEnv, ALESimState
from repro.envs.catch import CatchEnv
from repro.models.impala import impala_actor_critic
from repro.onpolicy import (VTraceLearner, assemble_vtrace_batch,
                            mlp_actor_critic)
from repro.optim import adamw
from repro.rollout import DeviceRolloutEngine, ShardedRolloutEngine

BENCH = Path(__file__).resolve().parents[1] / "bench"
RTOL, ATOL = 1e-5, 1e-6
GAP = 1e-2                 # the least a wrong core moves a logprob here


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{name}", BENCH / "reference" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("impala_deep")
common = _load("common")

CFG = ImpalaConfig(obs_size=20, obs_channels=2, channels=(4, 8, 8),
                   fc_dim=16, core_dim=16)
MODEL = {"obs_size": 20, "obs_channels": 2, "num_actions": 18,
         "channels": [4, 8, 8], "res_blocks": 2, "fc_dim": 16,
         "core_dim": 16, "rho_bar": 1.0, "c_bar": 1.0,
         "baseline_cost": 0.5, "entropy_cost": 0.01}
OPT = {"learning_rate": 1e-3, "adam_b1": 0.9, "adam_b2": 0.95,
       "adam_epsilon": 1e-8, "max_grad_norm": 1.0}
SEED = 3
E, T = 4, 10


def _env(episode_len=1000):
    return functools.partial(ALESimJaxEnv, frame=20, channels=2,
                             step_cost=64, episode_len=episode_len)


@pytest.fixture(scope="module")
def net():
    init_fn, apply_fn, init_core = impala_actor_critic(CFG)
    vl = VTraceLearner(apply_fn, adamw(OPT["learning_rate"]),
                       init_core=init_core)
    return init_fn(jax.random.PRNGKey(SEED)), apply_fn, init_core, vl


def _rollout_batch(vl, init_core, params, episode_len=1000, scans=2,
                   shards=1):
    """The batch of the last of ``scans`` device scans of E lanes, from
    one engine or from ``shards`` of a `ShardedRolloutEngine`."""
    kw = dict(init_core=init_core, with_logprobs=True, seed=SEED)
    if shards == 1:
        eng = DeviceRolloutEngine(_env(episode_len), vl.device_policy_apply(),
                                  E, T, **kw)
    else:
        eng = ShardedRolloutEngine(_env(episode_len),
                                   vl.device_policy_apply(), E, T,
                                   num_shards=shards, **kw)
    for _ in range(scans):
        traj = eng.rollout(params)
    unrolls = []
    flush_lane_unrolls(traj, unrolls.append)
    return assemble_vtrace_batch(unrolls, gamma=0.99)


def test_weights_and_forward_equal_the_reference(net):
    params, apply_fn, init_core, _ = net
    ref_params = ref.init_params(MODEL, SEED)
    assert jax.tree.structure(params) == jax.tree.structure(ref_params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
        np.testing.assert_array_equal(a, b)
    rng = np.random.default_rng(0)
    b, t = 3, 5
    inputs = {"obs": rng.integers(0, 256, (b, t, 20, 20, 2), np.uint8),
              "prev_action": rng.integers(0, 18, (b, t)).astype(np.int32),
              "prev_reward": rng.normal(size=(b, t)).astype(np.float32) * 2,
              "first": rng.random((b, t)) < 0.3}
    core = rng.normal(size=(b, 2, 16)).astype(np.float32) * 0.5
    logits, values, _ = apply_fn(params, core, inputs)
    want_logits, want_values = ref.forward(MODEL, ref_params,
                                           dict(inputs, core=core))
    np.testing.assert_allclose(logits, want_logits, rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(values, want_values, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("shards", [1, 2])
def test_rollout_logprobs_follow_the_recorded_core(net, shards):
    """The scan's behaviour logprobs are the learner network's, unrolled
    from the core each unroll recorded; from a zeroed core they are not.
    Sharded engines hand back each shard's lanes with their own cores."""
    params, _, init_core, vl = net
    batch = _rollout_batch(vl, init_core, params, shards=shards)
    assert batch["core"].shape == (E, 2, 16)
    assert not batch["first"][:, 0].any()      # the second scan's unrolls
    assert np.abs(batch["core"]).max() > 0.05
    lp = ref.logprob_of(MODEL, params, batch)
    np.testing.assert_allclose(lp, batch["behavior_logprobs"], rtol=RTOL,
                               atol=ATOL)
    zeroed = dict(batch, core=np.zeros_like(batch["core"]))
    gap = np.abs(ref.logprob_of(MODEL, params, zeroed)
                 - batch["behavior_logprobs"]).max()
    assert gap > GAP, gap


def test_core_resets_where_an_episode_starts_inside_an_unroll(net):
    params, apply_fn, init_core, vl = net
    batch = _rollout_batch(vl, init_core, params, episode_len=7)
    starts = np.argwhere(batch["first"][:, 1:])
    assert len(starts), "no episode started inside an unroll"
    # the step after an episode's end starts afresh: no previous action
    # or reward, and the learner's logprobs still follow the scan's
    lane, step = starts[0][0], starts[0][1] + 1
    assert batch["discounts"][lane, step - 1] == 0.0
    assert batch["prev_action"][lane, step] == 0
    assert batch["prev_reward"][lane, step] == 0.0
    np.testing.assert_allclose(ref.logprob_of(MODEL, params, batch),
                               batch["behavior_logprobs"], rtol=RTOL,
                               atol=ATOL)
    # from the start on, the unroll reads as one begun there from zeros
    inputs = {k: batch[k] for k in ("obs", "prev_action", "prev_reward",
                                     "first")}
    whole, _, _ = apply_fn(params, batch["core"], inputs)
    tail = {k: v[:, step:] for k, v in inputs.items()}
    fresh, _, _ = apply_fn(params, init_core(E), tail)
    np.testing.assert_allclose(whole[lane, step:], fresh[lane], rtol=RTOL,
                               atol=ATOL)


def test_recurrent_train_step_equals_the_reference(net):
    params, _, init_core, vl = net
    batch = _rollout_batch(vl, init_core, params, episode_len=7)
    state = vl.init_state(params)
    new, metrics = vl.train_step(state, batch)
    want = common.train(ref.make_loss(MODEL), ref.init_params(MODEL, SEED),
                        [batch], OPT)
    np.testing.assert_allclose(float(metrics["loss"]), want["losses"][0],
                               rtol=RTOL)
    b1 = OPT["adam_b1"]
    for m1, g in zip(jax.tree.leaves(new["opt_state"]["m"]),
                     jax.tree.leaves(want["grads"])):
        np.testing.assert_allclose(np.asarray(m1) / (1 - b1), g, rtol=1e-4,
                                   atol=1e-7)
    for p, q in zip(jax.tree.leaves(new["params"]),
                    jax.tree.leaves(want["params"][1])):
        np.testing.assert_allclose(p, q, rtol=RTOL, atol=ATOL)


def test_device_backend_trains_the_recurrent_net_and_closes_the_ledger(net):
    params, _, init_core, vl = net
    state = vl.init_state(params)
    vl.warmup(state, batch_size=4, unroll=T, obs_shape=(20, 20, 2),
              obs_dtype=np.uint8)
    sys_ = SeedSystem(env_factory=_env(), backend="device",
                      policy_apply=vl.device_policy_apply(),
                      init_core=init_core, num_actors=2, unroll=T,
                      envs_per_actor=E, algo="vtrace",
                      train_step=vl.train_step, state=state, learner_batch=4,
                      queue_capacity=16)
    sys_.warmup()
    stats = sys_.run(seconds=2.0)
    assert stats["learner_error"] is None, stats["learner_error"]
    assert stats["inference_error"] is None, stats["inference_error"]
    assert stats["learner_steps"] > 0
    led = stats["onpolicy"]
    assert led["frames_generated"] == (led["frames_trained"]
                                       + led["frames_dropped"]
                                       + led["frames_pending"]), led
    assert led["frames_pending"] == 0 and led["frames_trained"] > 0, led
    timings = stats["timings"]
    assert timings["rollout_scan_s"] > 0
    # per frame: obs, action, reward, logprob and done; per unroll: the
    # core and step 0's previous action, previous reward and first
    per_scan = T * E * (20 * 20 * 2 + 4 + 4 + 4 + 1) + E * (2 * 16 * 4 + 9)
    assert timings["rollout_fetch_bytes"] == stats["scans"] * per_scan


def test_host_backend_refuses_a_recurrent_vtrace_core(net):
    _, _, init_core, vl = net
    with pytest.raises(ValueError, match="per-slot V-trace core"):
        SeedSystem(env_factory=CatchEnv, policy_step=lambda o, i: o,
                   init_core=init_core, num_actors=1, unroll=4,
                   algo="vtrace")
    with pytest.raises(ValueError, match="per-slot V-trace core"):
        vl.sampling_policy(None)


def test_stateless_batch_keeps_its_exact_keys():
    init_fn, apply_fn = mlp_actor_critic(50, 3)
    vl = VTraceLearner(apply_fn, adamw(1e-3))
    eng = DeviceRolloutEngine(CatchEnv, vl.device_policy_apply(), E, T,
                              with_logprobs=True)
    traj = eng.rollout(init_fn(jax.random.PRNGKey(0)))
    assert sorted(traj) == ["actions", "behavior_logprobs", "dones", "obs",
                            "rewards"]
    unrolls = []
    flush_lane_unrolls(traj, unrolls.append)
    assert sorted(unrolls[0]) == sorted(traj)
    batch = assemble_vtrace_batch(unrolls, gamma=0.99)
    assert sorted(batch) == ["actions", "behavior_logprobs", "discounts",
                             "obs", "param_version", "rewards"]
    assert batch["obs"].dtype == np.float32


def test_alesim_jax_env_follows_the_host_env():
    host = ALESimEnv(frame=20, channels=3, step_cost=64, episode_len=6,
                     seed=5)
    env = ALESimJaxEnv(frame=20, channels=3, step_cost=64, episode_len=6)
    st, _ = env.reset(jax.random.PRNGKey(0))
    st = ALESimState(frame=jnp.asarray(host._state),
                     work=jnp.asarray(host._work), t=st.t, key=st.key)
    np.testing.assert_array_equal(env.render(st.frame), host._render())
    step = jax.jit(env.step)
    for i, a in enumerate([3, 0, 17, 5, 5]):
        st, obs, reward, done = step(st, jnp.int32(a))
        h_obs, h_reward, h_done = host.step(a)
        np.testing.assert_allclose(st.frame, host._state, rtol=1e-5)
        assert float(reward) == h_reward and bool(done) == h_done
        # a uint8 cast of a value that rounds across an integer may differ
        assert np.abs(obs.astype(int) - h_obs.astype(int)).max() <= 1
    st, obs, reward, done = step(st, jnp.int32(1))   # the 6th step ends it
    host.step(1)
    assert bool(done) and int(st.t) == 0
    assert obs.shape == (20, 20, 3) and obs.dtype == jnp.uint8
    lanes = jax.vmap(env.reset)(jax.random.split(jax.random.PRNGKey(1), 4))
    out = jax.vmap(env.step)(lanes[0], jnp.arange(4, dtype=jnp.int32))
    assert out[1].shape == (4, 20, 20, 3)
