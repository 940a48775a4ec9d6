"""Quickstart: the whole stack in one minute on CPU.

1. Builds a reduced LM policy (`--arch`, default qwen3-14b family),
2. trains it with the V-trace learner on synthetic trajectories,
3. checkpoints, restores, and serves a few greedy tokens,
4. runs the SEED actor/inference system with vectorized (vmapped) env
   lanes and shows the envs-per-actor throughput axis,
5. re-runs it under the telemetry plane and prints the measured
   BottleneckReport (which plane gates throughput, and the CPU/GPU ratio),
6. crashes the learner with a `ChaosMonkey` mid-training and brings the
   run back via `SeedSystem.resume()` from the live-loop checkpoints.

    PYTHONPATH=src python examples/quickstart.py
"""

import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.registry import make_model, smoke_config
from repro.core.losses import init_train_state, make_train_step
from repro.core.system import SeedSystem
from repro.envs.catch import CatchEnv
from repro.envs.tokenworld import synthetic_vtrace_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import greedy_generate
from repro.optim import adamw


def _quickstart_policy(obs, ids):
    # module-level (not a closure): the socket transport's spawned actor
    # hosts never see it, but the env_factory they DO receive must pickle
    return np.random.randint(0, 3, size=(obs.shape[0],))


def vector_actor_demo(env_counts=(1, 8), seconds=0.6):
    """SEED system over a vmapped JAX env: each actor steps E Catch lanes
    per inference round-trip; frames/s grows with E on the same threads.
    The device backend then fuses env+policy into one `lax.scan`, removing
    the per-step round-trip entirely (one transfer per unroll)."""
    for E in env_counts:
        def policy_step(obs, ids):
            return np.random.randint(0, 3, size=(obs.shape[0],))

        sys_ = SeedSystem(env_factory=CatchEnv, policy_step=policy_step,
                          num_actors=2, unroll=8, envs_per_actor=E,
                          deadline_ms=2.0)
        sys_.warmup()            # jit-compile vmapped reset/step up front
        stats = sys_.run(seconds=seconds, with_learner=False)
        assert stats["env_frames"] == stats["actor_iterations"] * E
        print(f"  E={E}: {stats['env_frames_per_s']:8.0f} env-frames/s "
              f"({stats['actor_iterations']} iterations x {E} lanes)")

    def policy_apply(params, core, obs, key):
        return jax.random.randint(key, (obs.shape[0],), 0, 3), core

    E = env_counts[-1]
    sys_ = SeedSystem(env_factory=CatchEnv, backend="device",
                      policy_apply=policy_apply, num_actors=2, unroll=8,
                      envs_per_actor=E)
    sys_.warmup()                # compile the fused scan up front
    stats = sys_.run(seconds=seconds, with_learner=False)
    print(f"  E={E} device-resident: {stats['env_frames_per_s']:8.0f} "
          f"env-frames/s ({stats['scans']} fused scans x 8 steps x {E} lanes)")

    # disaggregated: the same system with actors in a SEPARATE OS process
    # dialing a loopback TCP gateway (repro.transport) — the paper's
    # CPU/GPU-ratio knob as a runnable deployment shape
    sys_ = SeedSystem(env_factory=CatchEnv, policy_step=_quickstart_policy,
                      num_actors=2, unroll=8, envs_per_actor=E,
                      deadline_ms=1.0, transport="socket", num_actor_hosts=1)
    stats = sys_.run(seconds=max(seconds, 0.8), with_learner=False)
    print(f"  E={E} socket-transport: {stats['env_frames_per_s']:8.0f} "
          f"env-frames/s ({stats['gateway_connections']} actor-host conns, "
          f"{stats['gateway_traj_frames']} unrolls over the wire)")

    # co-located hosts can skip the TCP hot path entirely: transport="shm"
    # negotiates CODEC_SHM in HELLO and each connection rides a
    # shared-memory ring pair (request/reply memcpys, no per-frame
    # syscalls), with the TCP socket kept as spill + liveness channel
    sys_ = SeedSystem(env_factory=CatchEnv, policy_step=_quickstart_policy,
                      num_actors=2, unroll=8, envs_per_actor=E,
                      deadline_ms=1.0, transport="shm", num_actor_hosts=1)
    stats = sys_.run(seconds=max(seconds, 0.8), with_learner=False)
    print(f"  E={E} shm-transport:    {stats['env_frames_per_s']:8.0f} "
          f"env-frames/s ({stats['host_shm_frames']} ring frames, "
          f"{stats['host_spill_frames']} TCP spills, "
          f"{stats['gateway_shm_conns']} ring conns)")


def sharded_inference_demo(E=8, seconds=0.8):
    """Sharding the inference plane: the same disaggregated system with
    `num_replicas` data-parallel policy workers (sticky actor->replica
    routing keeps each lane's recurrent slot on one replica),
    `num_gateways` accept loops (actor hosts hash across their
    addresses), and trajectory frames from every gateway feeding the one
    learner sink. `num_replicas=1, num_gateways=1` is bit-for-bit the
    unsharded path; the model point for this knob is
    `SystemModel.with_sharded` (see examples/provision_system.py)."""
    sys_ = SeedSystem(env_factory=CatchEnv, policy_step=_quickstart_policy,
                      num_actors=2, unroll=8, envs_per_actor=E,
                      deadline_ms=1.0, transport="socket",
                      num_actor_hosts=2, num_gateways=2, num_replicas=2)
    stats = sys_.run(seconds=seconds, with_learner=False)
    print(f"  E={E} sharded ({stats['num_replicas']} replicas x "
          f"{stats['num_gateways']} gateways): "
          f"{stats['env_frames_per_s']:8.0f} env-frames/s "
          f"(conns/gateway={stats['per_gateway_connections']}, "
          f"lanes/replica={stats['replica_lanes']})")

    # the device path shards the other way: engine_shards=K places K fused
    # scan engines round-robin over jax.devices() (one carry per device)
    def policy_apply(params, core, obs, key):
        return jax.random.randint(key, (obs.shape[0],), 0, 3), core

    sys_ = SeedSystem(env_factory=CatchEnv, backend="device",
                      policy_apply=policy_apply, num_actors=2, unroll=8,
                      envs_per_actor=E, engine_shards=2)
    sys_.warmup()
    stats = sys_.run(seconds=seconds, with_learner=False)
    print(f"  E={E} engine-sharded device (K={stats['engine_shards']}): "
          f"{stats['env_frames_per_s']:8.0f} env-frames/s "
          f"({stats['scans']} sharded scans)")


def onpolicy_demo(E=4, seconds=2.0):
    """The on-policy training plane (`repro.onpolicy`): the same SEED
    system with `algo="vtrace"` — actors' unrolls carry behavior logprobs
    and a behavior-param version stamp into a bounded staleness-aware
    `TrajectoryQueue` (NOT replay), and the learner trains V-trace batches
    while publishing params back through the same version seam. The frame
    ledger is conserved: generated == trained + dropped. The model twin of
    the printed drop rate is `SystemModel.onpolicy_point` (see
    examples/provision_system.py)."""
    import numpy as np

    from repro.onpolicy import VTraceLearner, mlp_actor_critic

    obs_dim = int(np.prod(CatchEnv().obs_shape))
    init_fn, apply_fn = mlp_actor_critic(obs_dim, CatchEnv.num_actions)
    vl = VTraceLearner(apply_fn, adamw(1e-3))
    params = init_fn(jax.random.PRNGKey(0))
    state = vl.init_state(params)
    # pay the train-step jit up front so the measured windows train
    # instead of compiling (the first real batch would otherwise eat them)
    vl.warmup(state, batch_size=4, unroll=8, obs_shape=(obs_dim,))

    # host backend: the central inference server samples actions AND
    # returns their logprobs; the learner's publish hook swaps its params
    policy = vl.sampling_policy(params)
    for lanes in (E, 2 * E):                 # server batches 1 or 2 actors
        policy(np.zeros((lanes, obs_dim), np.float32), None)
    sys_ = SeedSystem(env_factory=CatchEnv, policy_step=policy,
                      num_actors=2, unroll=8, envs_per_actor=E,
                      deadline_ms=1.0, algo="vtrace",
                      train_step=vl.train_step, state=state,
                      learner_batch=4, max_param_lag=50,
                      policy_publish=policy.publish)
    sys_.warmup()
    stats = sys_.run(seconds=seconds)
    onp = stats["onpolicy"]
    print(f"  host  vtrace: {stats['env_frames_per_s']:7.0f} gen-frames/s, "
          f"{stats['learner_steps']} learner steps, "
          f"drop_rate={onp['drop_rate']:.2f}, "
          f"mean_param_lag={stats['mean_param_lag']:.2f}")
    assert onp["frames_generated"] == (onp["frames_trained"]
                                       + onp["frames_dropped"])

    # device backend: logprobs ride the fused scan; generation outruns the
    # learner by design, so the bounded queue VISIBLY drops — the paper's
    # actor-scaling knee from the algorithm side
    sys_ = SeedSystem(env_factory=CatchEnv, backend="device",
                      policy_apply=vl.device_policy_apply(),
                      num_actors=2, unroll=8, envs_per_actor=E,
                      algo="vtrace", train_step=vl.train_step,
                      state=vl.init_state(params),
                      learner_batch=4, max_param_lag=10)
    sys_.warmup()
    stats = sys_.run(seconds=seconds)
    onp = stats["onpolicy"]
    print(f"  device vtrace: {stats['env_frames_per_s']:7.0f} gen-frames/s, "
          f"{stats['learner_steps']} learner steps, "
          f"drop_rate={onp['drop_rate']:.2f} "
          f"(bounded queue sheds what the learner cannot absorb)")


def telemetry_demo(E=4, seconds=1.0):
    """The measurement plane (`repro.telemetry`): the same SEED system run
    under a `Telemetry` bundle — per-request spans stitched by trace_seq,
    latency histograms behind the stats dicts, per-process CPU sampling —
    ending in the paper's question answered from measurement: which plane
    gates throughput, and what is the measured CPU/GPU ratio? `tel.dump()`
    writes trace.json (load at ui.perfetto.dev) + metrics.jsonl."""
    from repro.telemetry import Telemetry

    tel = Telemetry(process_name="learner", out_dir="/tmp/repro_quickstart")
    sys_ = SeedSystem(env_factory=CatchEnv, policy_step=_quickstart_policy,
                      num_actors=2, unroll=8, envs_per_actor=E,
                      deadline_ms=2.0, telemetry=tel)
    sys_.warmup()
    stats = sys_.run(seconds=seconds, with_learner=False)
    report = tel.bottleneck_report(stats)
    for line in str(report).splitlines():
        print(f"  {line}")
    rtt = tel.merged_histogram("wire/rtt_s")
    print(f"  inference rtt p50={rtt['p50'] * 1e6:.0f}us "
          f"p99={rtt['p99'] * 1e6:.0f}us over {rtt['count']} round-trips")
    paths = tel.dump()
    print(f"  wrote {paths['trace']} (open at ui.perfetto.dev) "
          f"and {paths['metrics']}")


def ops_demo(E=4, seconds=2.0):
    """The LIVE half of the measurement plane: `SeedSystem(ops_port=0)`
    binds a loopback HTTP server next to the learner — `/metrics` is the
    Prometheus text scrape (counters match the conserved frame ledger
    exactly), `/healthz` the watchdog's verdict over every loop's
    heartbeat, `/varz` the bottleneck report + ledger as JSON, `/trace` an
    on-demand Chrome trace. Here: run in a background thread, scrape
    mid-flight with nothing but urllib, and print the live bottleneck."""
    import json
    import threading
    import time
    import urllib.request

    from repro.telemetry import Telemetry

    tel = Telemetry(process_name="learner", out_dir="/tmp/repro_quickstart")
    sys_ = SeedSystem(env_factory=CatchEnv, policy_step=_quickstart_policy,
                      num_actors=2, unroll=8, envs_per_actor=E,
                      deadline_ms=2.0, telemetry=tel, ops_port=0)
    host, port = sys_.ops_address
    print(f"  ops plane listening on http://{host}:{port}")
    sys_.warmup()
    runner = threading.Thread(
        target=lambda: sys_.run(seconds=seconds, with_learner=False),
        daemon=True)
    runner.start()
    time.sleep(seconds / 2)                      # scrape MID-run
    with urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=5) as resp:
        metrics_text = resp.read().decode()
    with urllib.request.urlopen(
            f"http://{host}:{port}/varz", timeout=5) as resp:
        varz = json.load(resp)
    runner.join()
    sample = [l for l in metrics_text.splitlines()
              if l.startswith("inference_") and not l.startswith("# ")]
    print(f"  /metrics: {len(metrics_text.splitlines())} lines, e.g. "
          f"{sample[0] if sample else '(warming up)'}")
    bn = varz.get("bottleneck", {})
    print(f"  /varz live bottleneck: {bn.get('bottleneck', '?')} "
          f"(cpu/gpu ratio {bn.get('cpu_gpu_ratio', 0.0):.2f})")
    print(f"  /healthz verdict: {varz.get('health', {}).get('verdict', '?')}")
    sys_.stop_ops()


def chaos_demo(E=4, seconds=1.5):
    """The survival plane (`repro.fault`): a `ChaosMonkey` crashes the
    learner thread mid-V-trace-training (the same seam a real OOM or
    assert would use), the live-loop checkpointer has been persisting
    {params, opt_state, step} on a cadence, and `SeedSystem.resume()`
    restores from the latest step, republishes params at a monotonic
    version, reopens the trajectory queue, and the run continues — with
    the frame ledger exactly conserved across the crash. The wire-level
    half (actor-host SIGKILL + gateway sever + reconnect) runs in CI as
    `benchmarks/fig3_actor_scaling.py --chaos`."""
    import tempfile

    import numpy as np

    from repro.fault import ChaosEvent, ChaosMonkey
    from repro.onpolicy import VTraceLearner, mlp_actor_critic

    obs_dim = int(np.prod(CatchEnv().obs_shape))
    init_fn, apply_fn = mlp_actor_critic(obs_dim, CatchEnv.num_actions)
    vl = VTraceLearner(apply_fn, adamw(1e-3))
    state = vl.init_state(init_fn(jax.random.PRNGKey(0)))
    vl.warmup(state, batch_size=4, unroll=8, obs_shape=(obs_dim,))
    policy = vl.sampling_policy(state["params"])
    for lanes in (E, 2 * E):
        policy(np.zeros((lanes, obs_dim), np.float32), None)

    ckpt_dir = tempfile.mkdtemp(prefix="repro_chaos_")
    sys_ = SeedSystem(env_factory=CatchEnv, policy_step=policy,
                      num_actors=2, unroll=8, envs_per_actor=E,
                      deadline_ms=1.0, algo="vtrace",
                      train_step=vl.train_step, state=state,
                      learner_batch=4, max_param_lag=50,
                      policy_publish=policy.publish,
                      checkpoint_dir=ckpt_dir, checkpoint_every_s=0.3)
    sys_.warmup()        # jit the env up front: the crash must land in a
    #                      window that is actually training
    monkey = ChaosMonkey.scripted(
        ChaosEvent(0.6, "crash_learner_step"))
    monkey.start(sys_)
    stats = sys_.run(seconds=seconds)
    monkey.stop()
    err = (stats["learner_error"] or "crash missed the window").splitlines()
    print(f"  chaos: learner crashed after {stats['learner_steps']} steps "
          f"({err[-1]})")
    version = sys_.resume()
    print(f"  resume: restored from checkpoint, republished params at "
          f"version {version} "
          f"(saves={sys_._recovery_stats()['checkpoint_saves']}, "
          f"restores={sys_._recovery_stats()['checkpoint_restores']})")
    stats = sys_.run(seconds=seconds / 2)
    onp = stats["onpolicy"]
    assert onp["frames_generated"] == (onp["frames_trained"]
                                       + onp["frames_dropped"]
                                       + onp["frames_pending"])
    print(f"  after resume: {stats['learner_steps']} learner steps "
          f"(> {version}), ledger conserved across the crash "
          f"(generated={onp['frames_generated']} == trained + dropped + "
          f"pending)")


def main():
    enable_compile_cache()
    arch = sys.argv[1] if len(sys.argv) > 1 else "qwen3-14b"
    cfg = smoke_config(arch)
    bundle = make_model(cfg)
    opt = adamw(1e-3)
    step = jax.jit(make_train_step(bundle, opt), donate_argnums=(0,))
    rng = jax.random.PRNGKey(0)
    state = init_train_state(bundle, opt, rng)

    print(f"== training reduced {arch} with V-trace for 20 steps")
    for i in range(20):
        batch = synthetic_vtrace_batch(jax.random.fold_in(rng, i), 4, 32,
                                       cfg.vocab_size)
        state, metrics = step(state, batch)
        if (i + 1) % 5 == 0:
            print(f"  step {i+1:3d} loss={float(metrics['loss']):.4f} "
                  f"pg={float(metrics['pg_loss']):.4f} "
                  f"grad_norm={float(metrics['grad_norm']):.2f}")

    print("== checkpoint round-trip")
    mgr = CheckpointManager("/tmp/repro_quickstart", async_save=False)
    mgr.save(state, 20)
    state, restored_step = mgr.restore(state)
    print(f"  restored step {restored_step}")

    print("== greedy decode 8 tokens from the trained policy")
    toks = jnp.zeros((2, 8), jnp.int32)
    out = greedy_generate(bundle, state["params"], {"tokens": toks}, steps=8,
                          max_len=32, dtype=jnp.float32)
    print("  generated:", out.tolist())

    print("== vectorized SEED actors (JaxVectorEnv over Catch)")
    vector_actor_demo()
    print("== sharded inference plane (replicas x gateways, engine shards)")
    sharded_inference_demo()
    print("== on-policy training plane (algo='vtrace', trajectory queue)")
    onpolicy_demo()
    print("== telemetry plane (spans, histograms, bottleneck attribution)")
    telemetry_demo()
    print("== live ops plane (/metrics, /healthz, /varz over HTTP)")
    ops_demo()
    print("== survival plane (chaos-injected learner crash + resume)")
    chaos_demo()
    print("ok")


if __name__ == "__main__":
    main()
