"""Bring-up check on the TPU: drive the SEED stack's main paths once through
the `SeedSystem` entry points and check what comes out.

    python chip_smoke.py              # one chip, three phases (below)
    python chip_smoke.py --chips 4    # the sharded rollout engine, 4 chips

One chip runs, in this process:
  * r2d2_full_width — the paper's R2D2 conv-LSTM agent at the published
    `AtariConfig()` widths (84x84x4 frames, LSTM 512, 18 actions, burn-in
    40 + unroll 80) on `ALESimEnv`: host backend, in-process actors,
    central inference, prioritized replay and the learner;
  * vtrace_device — V-trace on the device backend: fused env+policy scans
    over pure-JAX Catch lanes feeding the on-policy queue;
  * vtrace_shm_hosts — V-trace with the actors in spawned host processes
    over shared-memory rings, 2 gateways and 2 inference replicas. The
    hosts step a pure-JAX env, so they run JAX too: on the CPU, while this
    process holds the chip.

`--chips 4` runs only the path that spans chips: `ShardedRolloutEngine`
over four devices, checked against the same engine on one device, then a
short `SeedSystem(backend="device", engine_shards=4)`.

Each phase prints its own line and raises on the first failed check. There
is no CPU fallback: when JAX finds no TPU the script names the platform it
found and exits nonzero before any phase. The last line of a passing run
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.r2d2_atari import AtariConfig  # noqa: E402
from repro.core.r2d2_agent import build_r2d2_system  # noqa: E402
from repro.core.system import SeedSystem  # noqa: E402
from repro.envs.alesim import ALESimEnv  # noqa: E402
from repro.envs.catch import CatchEnv  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.onpolicy import VTraceLearner, mlp_actor_critic  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.rollout import ShardedRolloutEngine  # noqa: E402

CATCH_OBS = CatchEnv().obs_shape[0]
MIN_LEARNER_STEPS = 3


class SmokeFailure(AssertionError):
    pass


def _require(ok: bool, phase: str, what: str):
    if not ok:
        raise SmokeFailure(f"{phase}: {what}")


def _device_of(tree):
    return next(iter(jax.tree.leaves(tree)[0].devices()))


def _platform_of(tree) -> str:
    return _device_of(tree).platform


def _require_placed(phase: str, **trees):
    """Every tree sits on the default device's platform: the TPU, since
    `main` refuses to start on anything else."""
    want = jax.devices()[0].platform
    found = {name: _platform_of(tree) for name, tree in trees.items()}
    _require(set(found.values()) == {want}, phase,
             f"placed on {found}, expected {want!r}")


def _check_errors(phase: str, stats: dict):
    for key in ("learner_error", "inference_error"):
        _require(not stats.get(key), phase, f"{key}:\n{stats.get(key)}")
    hosts = stats.get("host_errors", [])
    _require(not hosts, phase, "host errors:\n" + "\n".join(hosts))


def _check_ledger(phase: str, stats: dict) -> dict:
    """The conserved frame ledger, settled: returns its four counts."""
    led = stats["onpolicy"]
    accounted = (led["frames_trained"] + led["frames_dropped"]
                 + led["frames_pending"])
    _require(led["frames_generated"] == accounted, phase,
             f"frame ledger not conserved: {led}")
    _require(led["frames_pending"] == 0, phase,
             f"frames still pending after close: {led}")
    return {k: led[k] for k in ("frames_generated", "frames_trained",
                                "frames_dropped", "frames_pending")}


def _require_learner_steps(phase: str, stats: dict):
    _require(stats["learner_steps"] >= MIN_LEARNER_STEPS, phase,
             f"{stats['learner_steps']} learner steps < {MIN_LEARNER_STEPS}")


def _vtrace_learner(seed: int = 0):
    init_fn, apply_fn = mlp_actor_critic(CATCH_OBS, CatchEnv.num_actions)
    vl = VTraceLearner(apply_fn, adamw(1e-3))
    return vl, vl.init_state(init_fn(jax.random.PRNGKey(seed)))


def _say(phase: str, **fields):
    print(phase + " " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def phase_r2d2_full_width(acfg: AtariConfig = AtariConfig(), *,
                          num_actors: int = 4, envs_per_actor: int = 8,
                          learner_batch: int = 8,
                          seconds: float = 20.0) -> dict:
    phase = "r2d2_full_width"
    t0 = time.perf_counter()
    env = functools.partial(ALESimEnv, frame=acfg.obs_size,
                            channels=acfg.obs_channels)
    sys_ = build_r2d2_system(
        acfg, env, num_actors=num_actors, envs_per_actor=envs_per_actor,
        learner_batch=learner_batch, replay_capacity=4 * learner_batch,
        min_replay=learner_batch)
    setup_s = time.perf_counter() - t0
    stats = sys_.run(seconds=seconds)
    _check_errors(phase, stats)
    params_on = _platform_of(sys_.learner.state["params"])
    _say(phase, setup_s=setup_s, env_frames=stats["env_frames"],
         learner_steps=stats["learner_steps"], params_platform=params_on,
         env_frames_per_s=stats["env_frames_per_s"])
    _require(stats["env_frames"] > 0, phase, "no env frames")
    _require_learner_steps(phase, stats)
    _require_placed(phase, params=sys_.learner.state["params"])
    return stats


def phase_vtrace_device(*, num_workers: int = 2, lanes: int = 256,
                        unroll: int = 20, learner_batch: int = 32,
                        seconds: float = 10.0) -> dict:
    phase = "vtrace_device"
    t0 = time.perf_counter()
    vl, state = _vtrace_learner()
    vl.warmup(state, batch_size=learner_batch, unroll=unroll,
              obs_shape=(CATCH_OBS,))
    sys_ = SeedSystem(env_factory=CatchEnv, backend="device",
                      policy_apply=vl.device_policy_apply(),
                      num_actors=num_workers, unroll=unroll,
                      envs_per_actor=lanes, algo="vtrace",
                      train_step=vl.train_step, state=state,
                      learner_batch=learner_batch,
                      queue_capacity=4 * learner_batch)
    sys_.warmup()
    setup_s = time.perf_counter() - t0
    stats = sys_.run(seconds=seconds)
    _check_errors(phase, stats)
    led = _check_ledger(phase, stats)
    params_on = _platform_of(sys_.learner.state["params"])
    scans_on = _platform_of(sys_.actors[0].engine._carry)
    _say(phase, setup_s=setup_s, env_frames=stats["env_frames"],
         learner_steps=stats["learner_steps"], **led,
         params_platform=params_on, scan_platform=scans_on)
    _require_learner_steps(phase, stats)
    _require_placed(phase, params=sys_.learner.state["params"],
                    scans=sys_.actors[0].engine._carry)
    return stats


def phase_vtrace_shm_hosts(*, num_hosts: int = 2, num_gateways: int = 2,
                           num_replicas: int = 2, actors_per_host: int = 2,
                           envs_per_actor: int = 8, unroll: int = 20,
                           learner_batch: int = 8,
                           seconds: float = 8.0) -> dict:
    phase = "vtrace_shm_hosts"
    t0 = time.perf_counter()
    vl, state = _vtrace_learner()
    policy = vl.sampling_policy(state["params"])
    # a replica batches the requests of the actors routed to it: warm every
    # lane count it can see
    num_actors = num_hosts * actors_per_host
    per_replica = -(-num_actors // num_replicas)
    for k in range(1, per_replica + 1):
        policy(np.zeros((k * envs_per_actor, CATCH_OBS), np.float32), None)
    vl.warmup(state, batch_size=learner_batch, unroll=unroll,
              obs_shape=(CATCH_OBS,))
    sys_ = SeedSystem(env_factory=CatchEnv, policy_step=policy,
                      num_actors=num_actors, unroll=unroll,
                      envs_per_actor=envs_per_actor, transport="shm",
                      num_actor_hosts=num_hosts, num_gateways=num_gateways,
                      num_replicas=num_replicas, algo="vtrace",
                      train_step=vl.train_step, state=state,
                      learner_batch=learner_batch,
                      policy_publish=policy.publish)
    setup_s = time.perf_counter() - t0
    stats = sys_.run(seconds=seconds)
    _check_errors(phase, stats)
    led = _check_ledger(phase, stats)
    host_platforms = sorted({s.get("jax_platform")
                             for s in sys_.pool.last_stats})
    params_on = _platform_of(sys_.learner.state["params"])
    _say(phase, setup_s=setup_s, env_frames=stats["env_frames"],
         learner_steps=stats["learner_steps"], **led,
         shm_frames=stats["gateway_shm_frames"],
         host_platforms=",".join(map(str, host_platforms)),
         params_platform=params_on)
    _require(stats["env_frames"] > 0, phase, "no env frames")
    _require(stats["learner_steps"] >= 1, phase, "no learner step")
    _require(stats["gateway_shm_frames"] > 0, phase,
             "no frame rode the shared-memory rings")
    _require(host_platforms == ["cpu"], phase,
             f"actor hosts ran JAX on {host_platforms}, expected cpu")
    _require_placed(phase, params=sys_.learner.state["params"])
    return stats


def phase_sharded_engine(devices, *, lanes: int = 256, unroll: int = 20,
                         learner_batch: int = 32,
                         seconds: float = 8.0) -> dict:
    """`ShardedRolloutEngine` across `devices` (one shard each) against the
    same engine on `devices[0]` alone, then a short sharded SeedSystem."""
    phase = "sharded_engine"
    k = len(devices)
    vl, state = _vtrace_learner()
    policy_apply = vl.device_policy_apply()

    def engine(devs):
        return ShardedRolloutEngine(CatchEnv, policy_apply, lanes, unroll,
                                    num_shards=k, devices=devs,
                                    with_logprobs=True)

    sharded, single = engine(devices), engine(devices[:1])
    sharded.reset()
    placed = [_device_of(e._carry) for e in sharded.engines]
    _require(len(set(placed)) == k, phase,
             f"{k} shards landed on {len(set(placed))} distinct devices")
    _require({d.platform for d in placed} == {jax.devices()[0].platform},
             phase, f"shards on {sorted({d.platform for d in placed})}")
    a = sharded.rollout(state["params"])
    b = single.rollout(state["params"])
    same = sorted(a) == sorted(b) and all(
        np.array_equal(a[key], b[key]) for key in a)
    _say(phase, shards=k, distinct_devices=len(set(placed)),
         devices=",".join(str(d.id) for d in placed),
         first_unroll_equal=same)
    _require(same, phase, f"first unroll differs across {k} devices")

    t0 = time.perf_counter()
    vl.warmup(state, batch_size=learner_batch, unroll=unroll,
              obs_shape=(CATCH_OBS,))
    sys_ = SeedSystem(env_factory=CatchEnv, backend="device",
                      policy_apply=policy_apply, num_actors=1,
                      unroll=unroll, envs_per_actor=lanes,
                      engine_shards=k, algo="vtrace",
                      train_step=vl.train_step, state=state,
                      learner_batch=learner_batch,
                      queue_capacity=4 * learner_batch)
    sys_.warmup()
    setup_s = time.perf_counter() - t0
    stats = sys_.run(seconds=seconds)
    _check_errors(phase, stats)
    led = _check_ledger(phase, stats)
    _say(phase + "_system", setup_s=setup_s, engine_shards=k,
         env_frames=stats["env_frames"],
         learner_steps=stats["learner_steps"], **led)
    _require(stats["learner_steps"] >= 1, phase, "no learner step")
    return stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the three one-chip phases; 4: only the "
                         "sharded rollout engine across four chips")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    cache = enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found platform {platform!r} "
              f"({len(devices)} device(s))", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    print(f"chip_smoke: {len(devices)} x {devices[0].device_kind}, "
          f"compile cache {cache}", flush=True)
    if args.chips == 4:
        phase_sharded_engine(devices[:4])
    else:
        phase_r2d2_full_width()
        phase_vtrace_device()
        phase_vtrace_shm_hosts()
    print(f"chip_smoke: total_s={time.perf_counter() - t0}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
