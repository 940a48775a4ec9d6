"""Sharded-inference-plane tests: slot stickiness under lane sharding,
bit-identical `num_replicas=1` parity with the single-server semantics,
multi-gateway end-to-end, engine-sharded device scans, validation, and
the (loose, best-of-5) sharded throughput gate.

The parity test is the load-bearing one: with `num_replicas=1` the
refactored server must produce byte-for-byte the same per-lane unroll
stream as the pre-sharding single-loop server — which, under a
deterministic slot-order-independent policy, equals a direct host loop
over the same seeded vector env. Sharding must then change NOTHING about
trajectories (only which thread computes them), so `num_replicas=2` is
held to the same reference.
"""

import os
import threading
import time

import numpy as np
import pytest

from repro.core.actor import Actor
from repro.core.inference import InferenceServer
from repro.core.system import SeedSystem
from repro.envs.catch import CatchEnv
from repro.envs.vector import make_vector_env
from repro.launch.actor_host import ActorHostPool


def det_policy(obs, ids):
    """Deterministic and slot-order independent, so batching/arrival order
    (which legitimately differs across replicas) cannot change actions."""
    flat = np.abs(obs.reshape(obs.shape[0], -1))
    return (flat.sum(axis=1) * 997.0).astype(np.int64) % CatchEnv.num_actions


# ------------------------------------------------------------ validation

def test_num_replicas_validation_is_a_clear_valueerror():
    with pytest.raises(ValueError, match="num_replicas"):
        InferenceServer(det_policy, max_batch=2, num_replicas=3)
    with pytest.raises(ValueError, match="num_replicas"):
        InferenceServer(det_policy, max_batch=4, num_replicas=0)
    with pytest.raises(ValueError, match="num_replicas"):
        SeedSystem(env_factory=CatchEnv, policy_step=det_policy,
                   num_actors=1, unroll=4, envs_per_actor=2,
                   num_replicas=4)
    # the device backend has no central server to shard
    with pytest.raises(ValueError, match="num_replicas"):
        SeedSystem(env_factory=CatchEnv, backend="device",
                   policy_apply=lambda p, c, o, k: (o, c),
                   num_actors=1, unroll=4, num_replicas=2)


def test_multi_gateway_fixed_port_is_a_clear_valueerror():
    # two gateways cannot bind one fixed port; must fail at construction,
    # not leak a half-started plane from inside run()
    with pytest.raises(ValueError, match="gateway_port"):
        SeedSystem(env_factory=CatchEnv, policy_step=det_policy,
                   num_actors=2, unroll=4, transport="socket",
                   num_actor_hosts=2, num_gateways=2, gateway_port=5555)


def test_num_gateways_validation_is_a_clear_valueerror():
    with pytest.raises(ValueError, match="num_gateways"):
        SeedSystem(env_factory=CatchEnv, policy_step=det_policy,
                   num_actors=2, unroll=4, transport="socket",
                   num_actor_hosts=1, num_gateways=2)
    with pytest.raises(ValueError, match="num_gateways"):
        SeedSystem(env_factory=CatchEnv, policy_step=det_policy,
                   num_actors=2, unroll=4, num_gateways=2)  # inproc
    with pytest.raises(ValueError, match="num_gateways"):
        SeedSystem(env_factory=CatchEnv, policy_step=det_policy,
                   num_actors=2, unroll=4, transport="socket",
                   num_gateways=0)


def test_engine_shards_validation_is_a_clear_valueerror():
    from repro.rollout import ShardedRolloutEngine

    def pol(params, core, inputs, key):
        return np.zeros(inputs.obs.shape[0]), core

    with pytest.raises(ValueError, match="num_shards"):
        ShardedRolloutEngine(CatchEnv, pol, 2, 4, num_shards=3)
    with pytest.raises(ValueError, match="num_shards"):
        ShardedRolloutEngine(CatchEnv, pol, 2, 4, num_shards=0)
    with pytest.raises(ValueError, match="engine_shards"):
        SeedSystem(env_factory=CatchEnv, policy_step=det_policy,
                   num_actors=1, unroll=4, engine_shards=2)  # host backend


def test_model_with_sharded_validation():
    from repro.core.provisioning import fit_paper_actor_model

    model, _ = fit_paper_actor_model()
    with pytest.raises(ValueError, match="n_replicas"):
        model.with_sharded(0)
    with pytest.raises(ValueError, match="n_replicas"):
        model.with_sharded(model.batch_cap + 1)
    # mirrors the runtime: no central inference on the device point
    with pytest.raises(ValueError, match="with_sharded"):
        model.with_device().with_sharded(2)


def test_wire_compression_validation_is_a_clear_valueerror():
    with pytest.raises(ValueError, match="wire_compression"):
        SeedSystem(env_factory=CatchEnv, policy_step=det_policy,
                   num_actors=2, unroll=4, wire_compression=True)  # inproc


# -------------------------------------------------------- slot stickiness

def test_lane_slots_never_migrate_replicas():
    """THE sharding invariant: a lane's (actor_id, env_id) recurrent slot
    is only ever presented to ONE replica's policy forward, across many
    interleaved requests from many actors."""
    seen = {}
    lock = threading.Lock()

    def recording_policy(obs, ids):
        name = threading.current_thread().name
        with lock:
            for slot in np.asarray(ids):
                seen.setdefault(int(slot), set()).add(name)
        return det_policy(obs, ids)

    srv = InferenceServer(recording_policy, max_batch=12, deadline_ms=2.0,
                          num_replicas=3)
    srv.start()
    try:
        obs = np.random.rand(2, 50).astype(np.float32)
        for round_ in range(4):
            replies = [srv.submit_batch(aid, obs) for aid in range(6)]
            for r in replies:
                out = r.get(timeout=5.0)
                assert out.shape == (2,), out
    finally:
        srv.stop()
    assert srv.error is None, srv.error
    # every slot pinned to exactly one replica thread, and the routing
    # actually sharded (more than one replica saw traffic)
    assert seen and all(len(names) == 1 for names in seen.values()), seen
    assert len({next(iter(v)) for v in seen.values()}) > 1
    assert srv.num_slots == 12          # 6 actors x 2 lanes, no duplicates


def test_replica_stats_are_per_replica_and_aggregate():
    srv = InferenceServer(det_policy, max_batch=8, deadline_ms=1.0,
                          num_replicas=2)
    srv.start()
    try:
        obs = np.random.rand(2, 50).astype(np.float32)
        for aid in (0, 1, 2, 3):
            srv.submit_batch(aid, obs).get(timeout=5.0)
    finally:
        srv.stop()
    per = srv.per_replica_stats()
    assert [p["replica"] for p in per] == [0, 1]
    assert all(p["lane_budget"] == 4 for p in per)      # ceil(8 / 2)
    # aggregate == sum of shards, and both shards actually served lanes
    assert sum(p["requests"] for p in per) == srv.stats["requests"] == 8
    assert all(p["requests"] == 4 for p in per)
    d = srv.derived_stats()
    assert d["mean_lanes_per_rpc"] == pytest.approx(2.0)


# ----------------------------------------------------------------- parity

def _reference_unrolls(num_envs, unroll, n_traj, actor_id=0):
    """The pre-PR single-server semantics, computed directly: a host loop
    over the same seeded vector env under the same deterministic policy.
    (The single-loop server produced exactly this stream — asserted by
    the pre-existing transport parity suite.)"""
    vec = make_vector_env(CatchEnv, num_envs, seed=actor_id)
    obs = vec.reset()
    out, buf = [], {"obs": [], "actions": [], "rewards": [], "dones": []}
    while len(out) < n_traj:
        actions = det_policy(obs, None)
        nobs, rewards, dones = vec.step(actions)
        buf["obs"].append(obs)
        buf["actions"].append(actions)
        buf["rewards"].append(rewards)
        buf["dones"].append(dones)
        if len(buf["actions"]) >= unroll:
            stacked = {k: np.stack(v) for k, v in buf.items()}
            for lane in range(num_envs):
                out.append({
                    "obs": stacked["obs"][:, lane],
                    "actions": stacked["actions"][:, lane].astype(np.int32),
                    "rewards": stacked["rewards"][:, lane].astype(np.float32),
                    "dones": stacked["dones"][:, lane].astype(np.float32),
                })
            buf = {"obs": [], "actions": [], "rewards": [], "dones": []}
        obs = nobs
    return out[:n_traj]


def _run_replicated_rollout(num_replicas, n_traj, num_envs=3, unroll=4):
    srv = InferenceServer(det_policy, max_batch=max(3, num_replicas),
                          deadline_ms=2.0, num_replicas=num_replicas)
    trajs = []
    actor = Actor(0, CatchEnv, srv, lambda t: trajs.append(t),
                  unroll=unroll, num_envs=num_envs)
    srv.start()
    actor.start()
    deadline = time.perf_counter() + 30.0
    while len(trajs) < n_traj and time.perf_counter() < deadline:
        time.sleep(0.01)
    actor.stop()
    srv.stop()
    actor.join()
    assert actor.error is None, actor.error
    assert len(trajs) >= n_traj, \
        f"replicated rollout produced {len(trajs)} < {n_traj} unrolls"
    return trajs[:n_traj]


@pytest.mark.parametrize("num_replicas", [1, 2])
def test_replicated_rollout_bit_identical_to_single_server_reference(
        num_replicas):
    """`num_replicas=1` must be the pre-PR single-server path bit-for-bit,
    and sharding must not change trajectories at all — both compared
    against the directly-computed reference stream under fixed seeds."""
    n = 6
    got = _run_replicated_rollout(num_replicas, n)
    ref = _reference_unrolls(3, 4, n)
    for i, (ta, tb) in enumerate(zip(got, ref)):
        assert sorted(ta) == sorted(tb)
        for k in ta:
            va, vb = np.asarray(ta[k]), np.asarray(tb[k])
            assert va.dtype == vb.dtype, (num_replicas, i, k)
            assert np.array_equal(va, vb), \
                f"replicas={num_replicas} unroll {i} key {k} diverged"


# ------------------------------------------------- multi-gateway e2e

def test_multi_gateway_two_hosts_end_to_end():
    """2 gateways x 2 actor hosts through `SeedSystem`: hosts hash across
    gateway addresses, frames flow through BOTH accept loops, trajectory
    frames from both gateways land in the shared replay sink, and the
    run is error-free."""
    sys_ = SeedSystem(env_factory=CatchEnv, policy_step=det_policy,
                      num_actors=2, unroll=4, envs_per_actor=2,
                      deadline_ms=1.0, transport="socket",
                      num_actor_hosts=2, num_gateways=2, num_replicas=2,
                      wire_compression=True)
    stats = sys_.run(seconds=1.0, with_learner=False)
    assert stats["inference_error"] is None, stats["inference_error"]
    assert stats["host_errors"] == []
    # wire_compression threaded through the spawned hosts: each actor
    # connection HELLOed its gateway (Catch obs are float32, so no RLE
    # frames follow — the uint8 compression itself is unit-tested)
    assert sum(gw.stats["hello_frames"] for gw in sys_.gateways) == 2
    assert stats["num_gateways"] == 2
    assert stats["num_replicas"] == 2
    # host h dialed gateway h % 2 -> exactly one host (of 1 actor each,
    # one SyncSocketTransport per actor) behind each gateway
    assert stats["per_gateway_connections"] == [1, 1]
    assert stats["env_frames"] > 0
    assert stats["gateway_traj_frames"] > 0
    assert len(sys_.replay) > 0, "trajectories did not reach replay"
    # both replicas served lanes (actor 0 -> replica 0, actor 1 -> 1)
    assert all(n > 0 for n in stats["replica_lanes"]), stats["replica_lanes"]


def test_multi_gateway_socket_parity_with_inproc():
    """The transport parity contract survives sharding: a 2-gateway,
    2-host, 2-replica socket rollout produces the same per-lane unroll
    multiset as the in-proc reference (frames arrive interleaved across
    gateways, so compare as multisets keyed by content hash)."""
    n = 4
    ref = _reference_unrolls(2, 4, n, actor_id=0) + \
        _reference_unrolls(2, 4, n, actor_id=1)

    srv = InferenceServer(det_policy, max_batch=4, deadline_ms=2.0,
                          num_replicas=2)
    trajs = []
    lock = threading.Lock()

    def sink(t):
        with lock:
            trajs.append(t)

    from repro.transport.socket import InferenceGateway
    gws = [InferenceGateway(srv, sink=sink) for _ in range(2)]
    srv.start()
    addrs = [gw.start() for gw in gws]
    pool = ActorHostPool(CatchEnv, num_actors=2, envs_per_actor=2,
                         unroll=4, num_hosts=2)
    stats = pool.run(addrs, seconds=2.5)
    for gw in reversed(gws):
        gw.stop()
    srv.stop()
    assert all(s["error"] is None for s in stats), stats
    assert len(trajs) >= len(ref), (len(trajs), len(ref))

    def key(t):
        return tuple(sorted((k, np.asarray(v).tobytes())
                            for k, v in t.items()))

    got_keys = {key(t) for t in trajs}
    for i, r in enumerate(ref):
        assert key(r) in got_keys, f"reference unroll {i} missing"


# --------------------------------------------- engine-sharded device scans

def test_sharded_engine_frame_accounting_and_schema():
    import jax

    from repro.rollout import RolloutWorker, ShardedRolloutEngine

    def pol(params, core, inputs, key):
        return jax.random.randint(key, (inputs.obs.shape[0],), 0,
                                  CatchEnv.num_actions), core

    E, T = 5, 6                      # uneven split: shards of 3 and 2 lanes
    eng = ShardedRolloutEngine(CatchEnv, pol, E, T, num_shards=2, seed=0)
    assert [e.num_envs for e in eng.engines] == [3, 2]
    assert all(e.device is not None for e in eng.engines)
    traj = eng.rollout(None)
    assert traj["obs"].shape[:2] == (T, E)
    assert traj["actions"].shape == (T, E)
    assert eng.scans == 1 and eng.shard_scans == 2
    assert eng.frames == T * E
    # rides RolloutWorker unchanged
    sunk = []
    w = RolloutWorker(0, eng, sunk.append, lambda: (None, 0))
    w.start()
    deadline = time.time() + 15.0
    while w.iterations < 3 and time.time() < deadline:
        time.sleep(0.01)
    w.stop()
    w.join()
    assert w.error is None, w.error
    assert w.frames == w.iterations * T * E
    assert len(sunk) == (w.iterations - 1) * E  # first rollout above sank none


def test_seed_system_engine_sharded_device_backend():
    import jax

    def pol(params, core, inputs, key):
        return jax.random.randint(key, (inputs.obs.shape[0],), 0,
                                  CatchEnv.num_actions), core

    E, T = 4, 8
    sys_ = SeedSystem(env_factory=CatchEnv, backend="device",
                      policy_apply=pol, num_actors=2, unroll=T,
                      envs_per_actor=E, engine_shards=2)
    sys_.warmup()
    stats = sys_.run(seconds=0.6, with_learner=False)
    assert stats["inference_error"] is None, stats["inference_error"]
    assert stats["engine_shards"] == 2
    assert stats["env_frames"] == stats["scans"] * T * E
    assert stats["env_frames"] > 0
    assert len(sys_.replay) > 0
    traj, _, _ = sys_.replay.sample(1)
    assert traj["obs"].shape[1] == T


# -------------------------------------------------------- throughput gate

@pytest.mark.skipif(os.environ.get("CI") == "true",
                    reason="wall-clock throughput ratio; shared CI runners "
                           "are too noisy for a hard perf gate")
def test_sharded_throughput_gate_best_of_5():
    """Loose acceptance on a 2-core noisy box: best-of-5, sharded
    (2 replicas) must reach >= 0.9x the single-replica throughput at equal
    (num_actors, E). The forward is LATENCY-bound (a GIL-releasing sleep —
    what a real accelerator forward looks like from the host), so the
    single server loop serializes forwards while replicas overlap them:
    the GA3C single-predictor regime sharding exists for, measurable on a
    2-core box because overlapping waits needs no extra cores. (A
    CPU-bound forward is NOT shardable here: numpy's BLAS already uses
    both cores, so replicas would only oversubscribe — measured and
    rejected as a gate workload.)"""

    def latency_policy(obs, ids):
        time.sleep(0.005)                     # the "device forward"
        flat = np.abs(obs.reshape(obs.shape[0], -1))
        return (flat.sum(axis=1) * 997.0).astype(np.int64) \
            % CatchEnv.num_actions

    def run_once(num_replicas):
        sys_ = SeedSystem(env_factory=CatchEnv, policy_step=latency_policy,
                          num_actors=4, unroll=8, envs_per_actor=2,
                          deadline_ms=1.0, num_replicas=num_replicas)
        sys_.warmup()
        stats = sys_.run(seconds=0.8, with_learner=False)
        assert stats["inference_error"] is None, stats["inference_error"]
        return stats["env_frames_per_s"]

    time.sleep(0.3)       # let prior tests' teardown (spawned hosts,
    best_rel = 0.0        # daemon threads) settle off the 2 cores
    for _ in range(5):
        single = run_once(1)
        sharded = run_once(2)
        best_rel = max(best_rel, sharded / max(single, 1e-9))
        if best_rel >= 1.0:
            break
    assert best_rel >= 0.9, \
        f"sharded inference {best_rel:.2f}x single-replica: sharding regressed"
