"""Fig 3 reproduction: actor-count sweep, plus the envs-per-actor axis.

Four parts:
  (a) MEASURED (scaled-down): the real SEED system (threads + central
      inference + ALESim envs) swept over actor counts on this host. With 1
      hardware core the saturation knee appears immediately — the same
      phenomenon the paper measured at 40 threads.
  (b) MODEL (paper scale): the calibrated actor/learner throughput model,
      validated against the paper's 5.8x (4->40) and 2.0x (40->256).
  (c) ENV VECTORIZATION (measured + model): env-frames/s per actor thread
      as each actor steps E lanes per inference round-trip (CuLE-style
      batching) — the highest-leverage knob on the CPU/GPU ratio.
  (d) DESIGN POINTS (measured + model): per-step host vs vectorized host vs
      device-resident (fused env+policy `lax.scan`, `repro.rollout`) at
      equal (num_actors, E) on a pure-JAX env — the paper's CPU/GPU-ratio
      endgame, where env stepping leaves the host entirely.
  (e) SHARDED INFERENCE (measured + model): the same SEED system with the
      central policy forward split across `num_replicas` data-parallel
      workers (sticky actor->replica routing) — the GA3C single-predictor
      bottleneck removed — plus the `with_sharded` model at paper scale
      and an engine-sharded device point (`engine_shards`).

  (f) ALGORITHM AXIS (`--algo vtrace`, measured + model): the same system
      with the on-policy training plane (`repro.onpolicy`) instead of
      replay — frames generated vs trained vs DROPPED by the bounded
      staleness-aware trajectory queue, and the mean behavior-param lag.
      This is the actor-scaling knee seen from the algorithm side: past
      the learner's consumption rate, actors buy drop rate, not learning.

`--smoke` shrinks every measured window so CI can exercise the full
measured path in seconds; `--replicas N` sets the sharded sweep's widest
point (CI runs `--smoke --replicas 2` and `--smoke --algo vtrace`).

`--telemetry` runs part (g): a socket-transport system under the full
`repro.telemetry` plane, then VALIDATES what it produced — trace.json
parses as Chrome trace events with at least one round-trip stitched
across two processes by wire trace_seq, metrics.jsonl is non-empty with
p50/p95/p99 for replica batch wait and wire RTT, the frame ledger agrees
with the telemetry counters, and the measured CPU/GPU ratio is finite
and classified. The run also binds the live ops plane (`ops_port=0`): a
sidecar thread scrapes `/metrics` + `/healthz` MID-run and the exposition
must pass the in-repo Prometheus validator (names, TYPE backing, bucket
monotonicity, +Inf == _count); afterwards a best-of-N in-proc pair gates
the full ops plane (HTTP server + watchdog + auditor) at < 3% frames/s
overhead vs telemetry-only. Writes trace.json and metrics.jsonl to
--out-dir and prints the results (the measured ops-overhead delta
included) as one JSON line; exits nonzero if any check fails (CI runs
`--smoke --telemetry`).
"""

import argparse
import json
import os
import sys
import time
from collections import defaultdict

import numpy as np

from repro.core.provisioning import fit_paper_actor_model
from repro.core.system import SeedSystem
from repro.envs.alesim import ALESimEnv
from repro.envs.catch import CatchEnv
from repro.launch.compile_cache import enable_compile_cache


def measured_sweep(actor_counts=(1, 2, 4, 8), seconds=1.2, step_cost=2048,
                   envs_per_actor=1):
    rows = []
    for n in actor_counts:
        def policy_step(obs, ids):
            return np.random.randint(0, 18, size=(obs.shape[0],))

        sys_ = SeedSystem(
            env_factory=lambda: ALESimEnv(frame=32, step_cost=step_cost),
            policy_step=policy_step, num_actors=n, unroll=16, deadline_ms=2.0,
            envs_per_actor=envs_per_actor)
        stats = sys_.run(seconds=seconds, with_learner=False)
        rows.append((n, stats["env_frames_per_s"],
                     stats["mean_batch_occupancy"],
                     stats["mean_queue_wait_ms"]))
    return rows


def measured_env_sweep(env_counts=(1, 2, 4, 8), actors=2, seconds=1.2,
                       step_cost=512):
    """Fixed actor-thread count, sweep lanes per actor: frames/s per thread."""
    rows = []
    for E in env_counts:
        (_, fps, occ, wait), = measured_sweep(
            actor_counts=(actors,), seconds=seconds, step_cost=step_cost,
            envs_per_actor=E)
        rows.append((E, fps, fps / actors, occ, wait))
    return rows


def model_sweep():
    model, err = fit_paper_actor_model()
    counts = (4, 8, 16, 32, 40, 64, 128, 256)
    return model, err, [(n, float(model.speedup(n, 4))) for n in counts]


def model_env_sweep(env_counts=(1, 2, 4, 8, 16), n_actors=40):
    """Calibrated model at paper scale along the second (E) axis."""
    model, _ = fit_paper_actor_model()
    base = float(model.throughput(n_actors))
    return [(E, float(model.with_envs(E).throughput(n_actors)) / base)
            for E in env_counts]


def measured_backend_sweep(num_actors=2, envs_per_actor=8, seconds=1.0,
                           unroll=16):
    """Part (d), measured: the three design points at equal (num_actors, E)
    on a pure-JAX env (Catch), so the env itself is identical across all
    three and only the rollout architecture changes."""
    import jax

    def host_policy(obs, ids):
        return np.random.randint(0, CatchEnv.num_actions, size=(obs.shape[0],))

    def device_policy(params, core, inputs, key):
        return jax.random.randint(key, (inputs.obs.shape[0],), 0,
                                  CatchEnv.num_actions), core

    points = (("per_step_host", "host", 1),
              ("vectorized_host", "host", envs_per_actor),
              ("device_resident", "device", envs_per_actor))
    rows = []
    for name, backend, E in points:
        kwargs = dict(env_factory=CatchEnv, num_actors=num_actors,
                      unroll=unroll, envs_per_actor=E)
        if backend == "device":
            sys_ = SeedSystem(backend="device", policy_apply=device_policy,
                              **kwargs)
        else:
            sys_ = SeedSystem(policy_step=host_policy, deadline_ms=2.0,
                              **kwargs)
        sys_.warmup()
        stats = sys_.run(seconds=seconds, with_learner=False)
        rows.append((name, E, stats["env_frames_per_s"]))
    return rows


def model_backend_sweep(envs_per_actor=8, n_actors=40):
    """Part (d), model: the same three design points at paper scale."""
    model, _ = fit_paper_actor_model()
    return [
        ("per_step_host", float(model.throughput(n_actors))),
        ("vectorized_host",
         float(model.with_envs(envs_per_actor).throughput(n_actors))),
        ("device_resident",
         float(model.with_envs(envs_per_actor).with_device()
               .throughput(n_actors))),
    ]


def measured_replica_sweep(replica_counts=(1, 2), num_actors=4,
                           envs_per_actor=2, seconds=1.0, unroll=8):
    """Part (e), measured: equal (num_actors, E) with the inference plane
    split across R data-parallel replicas. The policy forward is
    LATENCY-bound (a GIL-releasing sleep — the host's view of a real
    accelerator forward), so the single loop serializes forwards and
    replicas overlap them: the GA3C single-predictor regime, measurable
    even on a 2-core host because overlapping waits needs no extra
    cores."""

    def busy_policy(obs, ids):
        time.sleep(0.005)                     # the "device forward"
        flat = np.abs(obs.reshape(obs.shape[0], -1))
        return (flat.sum(axis=1) * 997.0).astype(np.int64) \
            % CatchEnv.num_actions

    rows = []
    for R in replica_counts:
        sys_ = SeedSystem(env_factory=CatchEnv, policy_step=busy_policy,
                          num_actors=num_actors, unroll=unroll,
                          envs_per_actor=envs_per_actor, deadline_ms=1.0,
                          num_replicas=R)
        sys_.warmup()
        stats = sys_.run(seconds=seconds, with_learner=False)
        rows.append((R, stats["env_frames_per_s"],
                     stats["mean_batch_occupancy"],
                     stats.get("replica_lanes", [stats["inference_lanes"]])))
    return rows


def measured_engine_shard_sweep(shard_counts=(1, 2), num_actors=2,
                                envs_per_actor=8, seconds=1.0, unroll=8):
    """Part (e), measured, device path: the fused scan split across K
    placed engines. On a CPU-only host the K scans serialize on the one
    device, so this measures the sharding overhead floor; on a multi-GPU
    host the same code overlaps them."""
    import jax

    def device_policy(params, core, inputs, key):
        return jax.random.randint(key, (inputs.obs.shape[0],), 0,
                                  CatchEnv.num_actions), core

    rows = []
    for K in shard_counts:
        sys_ = SeedSystem(env_factory=CatchEnv, backend="device",
                          policy_apply=device_policy, num_actors=num_actors,
                          unroll=unroll, envs_per_actor=envs_per_actor,
                          engine_shards=K)
        sys_.warmup()
        stats = sys_.run(seconds=seconds, with_learner=False)
        rows.append((K, stats["env_frames_per_s"]))
    return rows


def model_replica_sweep(replica_counts=(1, 2, 4, 8), n_actors=40):
    """Part (e), model at paper scale: `with_sharded` — forward capacity
    xN until per-replica batch fill starves (t_inf0 floor). E=1, so the
    inference term is not already amortized away by lane vectorization."""
    model, _ = fit_paper_actor_model()
    base = float(model.throughput(n_actors))
    return [(R, float(model.with_sharded(R).throughput(n_actors)) / base)
            for R in replica_counts]


def measured_vtrace_sweep(actor_counts=(1, 2), envs_per_actor=4, seconds=1.2,
                          unroll=8, learner_batch=4, max_param_lag=50):
    """Part (f), measured: `SeedSystem(algo='vtrace')` on Catch with a
    real (tiny MLP) sampling policy and V-trace learner. Reports the
    conserved frame ledger per actor count — generation vs training vs
    drops — and the staleness of what trained."""
    import jax

    from repro.onpolicy import VTraceLearner, mlp_actor_critic
    from repro.optim import adamw

    obs_dim = int(np.prod(CatchEnv().obs_shape))
    init_fn, apply_fn = mlp_actor_critic(obs_dim, CatchEnv.num_actions)
    vl = VTraceLearner(apply_fn, adamw(1e-3))
    params = init_fn(jax.random.PRNGKey(0))
    policy = vl.sampling_policy(params)
    # pay both jit compiles outside every measured window (the batch
    # pytree is structurally stable, so one warmup covers every run)
    for n in actor_counts:
        policy(np.zeros((n * envs_per_actor, obs_dim), np.float32), None)
    vl.warmup(vl.init_state(params), batch_size=learner_batch,
              unroll=unroll, obs_shape=(obs_dim,))

    rows = []
    for n in actor_counts:
        state = vl.init_state(params)
        # sweep points must be comparable: reset the behavior policy to
        # the same initial params the learner restarts from (otherwise
        # point n generates under point n-1's trained params)
        policy.publish(params, 0)
        sys_ = SeedSystem(env_factory=CatchEnv, policy_step=policy,
                          num_actors=n, unroll=unroll,
                          envs_per_actor=envs_per_actor, deadline_ms=1.0,
                          algo="vtrace", train_step=vl.train_step,
                          state=state, learner_batch=learner_batch,
                          max_param_lag=max_param_lag,
                          policy_publish=policy.publish)
        sys_.warmup()
        stats = sys_.run(seconds=seconds)
        onp = stats["onpolicy"]
        rows.append((n, stats["env_frames_per_s"],
                     onp["frames_trained"] / stats["elapsed_s"],
                     onp["drop_rate"], stats["mean_param_lag"],
                     onp["mean_trained_lag"], stats["learner_steps"]))
    return rows


def model_vtrace_sweep(actor_counts=(4, 16, 40, 128, 256),
                       learner_step_s=8.0, batch_size=8, unroll=20):
    """Part (f), model at paper scale: `SystemModel.onpolicy_point` — the
    drop-rate/staleness knee as a function of actor count."""
    model, _ = fit_paper_actor_model()
    return [(n, model.onpolicy_point(n, learner_step_s=learner_step_s,
                                     batch_size=batch_size, unroll=unroll))
            for n in actor_counts]


def run_vtrace(args, sec):
    actor_counts = (1, 2) if args.smoke else (1, 2, 4)
    print("# fig3f: on-policy (V-trace) measured sweep — frame ledger")
    print("name,value,derived")
    rows = measured_vtrace_sweep(actor_counts=actor_counts,
                                 seconds=max(sec, 0.8))
    for n, gen, trained, drop, lag, tlag, steps in rows:
        print(f"fig3f_vtrace_actors_{n},{gen:.1f},gen_frames_per_s "
              f"trained_per_s={trained:.1f} drop_rate={drop:.2f} "
              f"mean_param_lag={lag:.2f} trained_lag={tlag:.2f} "
              f"learner_steps={steps}")
    print("# fig3f: onpolicy_point model at paper scale (40 hw threads)")
    for n, p in model_vtrace_sweep():
        print(f"fig3f_model_actors_{n},{p.drop_rate:.2f},drop_rate "
              f"trained_per_s={p.frames_trained_per_s:.1f} "
              f"mean_param_lag={p.mean_param_lag:.1f} "
              f"learner_bound={p.learner_bound}")


def _telemetry_policy(obs, ids):
    # module-level so spawned actor-host children can pickle the factory
    # chain (the policy itself stays learner-side; this is only for the
    # in-proc warmup parity)
    return np.random.randint(0, CatchEnv.num_actions, size=(obs.shape[0],))


def _http_get(url, timeout=2.0):
    """GET returning (status, body-text); a 503 /healthz still has a JSON
    body worth reading, so HTTPError is a result, not an exception."""
    import urllib.error
    import urllib.request
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _ops_overhead_gate(repeats=3, seconds=0.8):
    """Satellite of the PR-7 disabled-overhead gate: the FULL ops plane
    (HTTP server + watchdog + auditor, nothing scraping) must cost < 3%
    best-of-N frames/s vs the same in-proc system under telemetry only."""
    from repro.telemetry import Telemetry

    def best_fps(ops_port):
        best = 0.0
        for _ in range(repeats):
            tel = Telemetry(process_name="learner")
            sys_ = SeedSystem(
                env_factory=CatchEnv, policy_step=_telemetry_policy,
                num_actors=2, unroll=8, envs_per_actor=2,
                deadline_ms=2.0, telemetry=tel, ops_port=ops_port)
            sys_.warmup()
            stats = sys_.run(seconds=seconds, with_learner=False)
            sys_.stop_ops()
            best = max(best, stats["env_frames_per_s"])
        return best

    base = best_fps(None)          # telemetry only: no ops/watchdog/auditor
    withops = best_fps(0)          # full ops plane enabled
    overhead = 1.0 - withops / base if base > 0 else 0.0
    return base, withops, overhead


def run_telemetry(args, sec, out_dir="."):
    """Part (g): measured telemetry validation run (see module docstring).

    Every check appends to `failures` instead of raising, so one broken
    artifact still reports the state of all the others before exit(1).
    """
    import threading

    from repro.telemetry import Telemetry, validate_prometheus

    seconds = max(sec * 4, 1.2) if args.smoke else 4.0
    tel = Telemetry(process_name="learner", out_dir=out_dir)
    sys_ = SeedSystem(env_factory=CatchEnv, policy_step=_telemetry_policy,
                      num_actors=2, unroll=8, envs_per_actor=2,
                      deadline_ms=2.0, transport="socket",
                      num_actor_hosts=2, telemetry=tel, ops_port=0)
    ops_host, ops_port = sys_.ops_address
    ops_base = f"http://{ops_host}:{ops_port}"
    # scrape the live plane MID-run from a sidecar thread — the same shape
    # a Prometheus agent would use against a real deployment
    scrapes = {"metrics": [], "healthz": [], "errors": []}
    scr_stop = threading.Event()

    def _scrape_loop():
        while not scr_stop.wait(0.4):
            try:
                _, text = _http_get(ops_base + "/metrics")
                scrapes["metrics"].append(text)
                _, hz = _http_get(ops_base + "/healthz")
                scrapes["healthz"].append(json.loads(hz))
            except Exception as e:       # noqa: BLE001 — recorded, checked
                scrapes["errors"].append(str(e))

    scraper = threading.Thread(target=_scrape_loop, daemon=True)
    scraper.start()
    stats = sys_.run(seconds=seconds, with_learner=False)
    scr_stop.set()
    scraper.join(timeout=5.0)
    report = tel.bottleneck_report(stats)
    paths = tel.dump(out_dir)

    failures = []

    def check(ok, what):
        if not ok:
            failures.append(what)
        return ok

    check(not stats["host_errors"], f"host errors: {stats['host_errors']}")
    check(stats["env_frames"] > 0, "no env frames in the measured window")

    # 1. trace.json parses and is Chrome-trace shaped
    events = []
    try:
        with open(paths["trace"]) as f:
            doc = json.load(f)
        events = doc.get("traceEvents", [])
        check(isinstance(events, list) and events,
              "trace.json has no traceEvents")
        check(all("ph" in e and "pid" in e for e in events),
              "trace event missing ph/pid")
    except (OSError, ValueError) as e:
        failures.append(f"trace.json unreadable: {e}")

    # 2. >=1 round-trip stitched across >=2 processes by trace_seq
    by_seq = defaultdict(set)
    for e in events:
        if e.get("ph") == "X" and e.get("args", {}).get("trace_seq"):
            by_seq[e["args"]["trace_seq"]].add(e["pid"])
    stitched = sum(1 for pids in by_seq.values() if len(pids) >= 2)
    check(stitched >= 1,
          f"no round-trip stitched across 2+ processes "
          f"({len(by_seq)} seqs seen)")

    # 3. metrics.jsonl non-empty, with percentiles for batch wait + RTT
    lines = []
    try:
        with open(paths["metrics"]) as f:
            lines = [json.loads(ln) for ln in f if ln.strip()]
        check(bool(lines), "metrics.jsonl is empty")
    except (OSError, ValueError) as e:
        failures.append(f"metrics.jsonl unreadable: {e}")
    wait_h = tel.merged_histogram("inference/batch_wait_s")
    rtt_h = tel.merged_histogram("wire/rtt_s")
    check(bool(wait_h and wait_h.get("p50") is not None
               and wait_h.get("p99") is not None),
          "no p50/p99 for inference/batch_wait_s")
    check(bool(rtt_h and rtt_h.get("p50") is not None
               and rtt_h.get("p99") is not None),
          "no p50/p99 for wire/rtt_s")

    # 4. frame ledger vs telemetry counters: the registry's lane counter
    # IS the source of stats["inference_lanes"] (exact), and actor frames
    # can trail served lanes only by the in-flight round-trips at stop
    lanes = tel._counter_total("/requests")
    check(int(lanes) == int(stats["inference_lanes"]),
          f"registry lanes {lanes} != stats {stats['inference_lanes']}")
    in_flight = 2 * 2  # num_actors * envs_per_actor
    check(0 <= lanes - stats["env_frames"] <= in_flight,
          f"ledger drift: {lanes} lanes served vs "
          f"{stats['env_frames']} frames stepped")

    # 5. measured CPU/GPU ratio is finite and the window classified
    check(np.isfinite(report.cpu_gpu_ratio), "cpu_gpu_ratio not finite")
    check(report.bottleneck.endswith("-bound") or report.bottleneck == "idle",
          f"unclassified window: {report.bottleneck!r}")

    # 6. live ops plane: mid-run scrapes happened and the LAST /metrics
    # (plus a final post-run one) passes the in-repo Prometheus validator
    # (names, TYPE backing, bucket monotonicity, +Inf == _count)
    check(bool(scrapes["metrics"]),
          f"no mid-run /metrics scrape landed (errors: {scrapes['errors']})")
    check(bool(scrapes["healthz"]), "no mid-run /healthz scrape landed")
    promlint = []
    for text in scrapes["metrics"][-1:]:
        promlint.extend(validate_prometheus(text))
    _, final_text = _http_get(ops_base + "/metrics", timeout=5.0)
    promlint.extend(validate_prometheus(final_text))
    for v in promlint:
        check(False, f"prometheus exposition: {v}")
    verdicts = sorted({h.get("verdict", "?") for h in scrapes["healthz"]})
    check(all(v in ("healthy", "degraded", "stalled") for v in verdicts),
          f"unparseable /healthz verdicts: {verdicts}")
    sys_.stop_ops()

    # 7. ops plane overhead vs telemetry-only (in-proc, best-of-N)
    fps_base, fps_ops, ops_overhead = _ops_overhead_gate(
        seconds=max(sec * 2, 0.6))
    check(ops_overhead < 0.03,
          f"ops plane costs {ops_overhead:.1%} frames/s "
          f"({fps_ops:.0f} vs {fps_base:.0f}) — gate is 3%")

    payload = {
        "seconds": seconds,
        "env_frames": stats["env_frames"],
        "env_frames_per_s": stats["env_frames_per_s"],
        "stitched_roundtrips": stitched,
        "trace_events": len(events),
        "metrics_lines": len(lines),
        "batch_wait_p50_s": wait_h.get("p50") if wait_h else None,
        "batch_wait_p99_s": wait_h.get("p99") if wait_h else None,
        "wire_rtt_p50_s": rtt_h.get("p50") if rtt_h else None,
        "wire_rtt_p99_s": rtt_h.get("p99") if rtt_h else None,
        "bottleneck": report.as_dict(),
        "ops_scrapes": len(scrapes["metrics"]),
        "ops_healthz_verdicts": verdicts,
        "ops_metrics_lines": len(final_text.splitlines()),
        "fps_telemetry_only": fps_base,
        "fps_with_ops": fps_ops,
        "ops_overhead_frac": ops_overhead,
        "failures": failures,
    }
    print(json.dumps({"fig3_telemetry": payload}, sort_keys=True))

    print("# fig3g: telemetry validation (socket transport, 2 hosts)")
    print("name,value,derived")
    print(f"fig3g_frames_per_s,{stats['env_frames_per_s']:.1f},"
          f"frames={stats['env_frames']}")
    print(f"fig3g_stitched_roundtrips,{stitched},of {len(by_seq)} seqs")
    print(f"fig3g_trace_events,{len(events)},{paths['trace']}")
    print(f"fig3g_metrics_lines,{len(lines)},{paths['metrics']}")
    if rtt_h:
        print(f"fig3g_wire_rtt_p50_us,{rtt_h['p50'] * 1e6:.0f},"
              f"p99_us={rtt_h['p99'] * 1e6:.0f}")
    if wait_h:
        print(f"fig3g_batch_wait_p50_us,{wait_h['p50'] * 1e6:.0f},"
              f"p99_us={wait_h['p99'] * 1e6:.0f}")
    print(f"fig3g_cpu_gpu_ratio,{report.cpu_gpu_ratio:.2f},"
          f"{report.bottleneck}")
    print(f"fig3g_ops_scrapes,{len(scrapes['metrics'])},"
          f"mid-run /metrics+/healthz verdicts={'/'.join(verdicts)}")
    print(f"fig3g_ops_overhead_pct,{100.0 * ops_overhead:.2f},"
          f"with_ops={fps_ops:.0f} telemetry_only={fps_base:.0f} gate=3%")
    for line in str(report).splitlines():
        print(f"# {line}")
    if failures:
        for f_ in failures:
            print(f"fig3g_FAIL,1,{f_}")
        sys.exit(1)
    print("fig3g_ok,1,all telemetry checks passed")


def _fault_overhead_gate(repeats=3, seconds=0.8):
    """The survival plane must be free when nothing dies: a socket run
    with supervision + reconnect policies ARMED (but no chaos) must cost
    < 3% best-of-N frames/s vs the identical run without them."""
    from repro.fault import BackoffPolicy

    def best_fps(fault):
        kw = dict(supervise_hosts=True,
                  wire_reconnect=BackoffPolicy()) if fault else {}
        best = 0.0
        for _ in range(repeats):
            sys_ = SeedSystem(
                env_factory=CatchEnv, policy_step=_telemetry_policy,
                num_actors=2, unroll=8, envs_per_actor=2,
                deadline_ms=2.0, transport="socket", num_actor_hosts=1,
                **kw)
            stats = sys_.run(seconds=seconds, with_learner=False)
            best = max(best, stats["env_frames_per_s"])
        return best

    base = best_fps(False)       # the historical fail-fast wire
    withf = best_fps(True)       # supervision + reconnect armed, idle
    overhead = 1.0 - withf / base if base > 0 else 0.0
    return base, withf, overhead


def run_chaos(args, sec, out_dir="."):
    """Part (h): the survivable serving plane under injected faults.

    A vtrace socket training run (2 actor hosts, 2 gateways, live-loop
    checkpointing, supervision + reconnect armed) has an actor host
    KILLED and a gateway connection SEVERED mid-run by a scripted
    `ChaosMonkey`. The run must complete with zero host errors, the host
    respawned, the client reconnected, /healthz observed degraded
    mid-run and healthy at the end, and the frame ledger EXACTLY
    conserved. Afterwards the fault-path overhead gate checks the armed-
    but-idle survival plane costs < 3% frames/s. Prints the results as
    one JSON line under ``fig3_chaos``; exits nonzero on any
    failed check (CI runs ``--smoke --chaos`` under a hard timeout).
    """
    import threading

    import jax

    from repro.fault import BackoffPolicy, ChaosEvent, ChaosMonkey
    from repro.onpolicy import VTraceLearner, mlp_actor_critic
    from repro.optim import adamw
    from repro.telemetry import Telemetry

    failures = []

    def check(ok, what):
        if not ok:
            failures.append(what)
        return ok

    obs_dim = int(np.prod(CatchEnv().obs_shape))
    init_fn, apply_fn = mlp_actor_critic(obs_dim, CatchEnv.num_actions)
    vl = VTraceLearner(apply_fn, adamw(1e-3))
    params = init_fn(jax.random.PRNGKey(0))
    state = vl.init_state(params)
    policy = vl.sampling_policy(params)
    for lanes in (4, 8):
        policy(np.zeros((lanes, obs_dim), np.float32), None)
    vl.warmup(state, batch_size=4, unroll=8, obs_shape=(obs_dim,))
    tel = Telemetry(process_name="learner", out_dir=out_dir)
    tel.health.event_window_s = 3.0   # fault events age out before the
    #                                   final "healed" check below
    sys_ = SeedSystem(env_factory=CatchEnv, policy_step=policy,
                      num_actors=2, unroll=8, envs_per_actor=4,
                      deadline_ms=1.0, algo="vtrace", max_param_lag=100,
                      train_step=vl.train_step, state=state,
                      learner_batch=4, policy_publish=policy.publish,
                      transport="socket", num_actor_hosts=2,
                      num_gateways=2, telemetry=tel, ops_port=0,
                      checkpoint_dir=os.path.join(out_dir, "chaos_ckpt"),
                      checkpoint_every_s=1.0,
                      supervise_hosts=True, host_stall_s=4.0,
                      wire_reconnect=BackoffPolicy(base_s=0.05, cap_s=0.5,
                                                   max_retries=8, seed=0))
    ops_host, ops_port = sys_.ops_address
    base_url = f"http://{ops_host}:{ops_port}"
    seconds = 8.0 if args.smoke else 12.0
    # the schedule is fixed data; its anchor is adaptive (children pay
    # jax import + jit warmup before serving, so wall-clock offsets from
    # run() start would race the spawn). Host 1 hashes to gateway 1, so
    # the sever hits the SURVIVING host's wire — the one that must
    # reconnect and live to report it.
    monkey = ChaosMonkey.scripted(
        ChaosEvent(0.5, "kill_actor_host", target=0),
        ChaosEvent(2.5, "sever_gateway_conn", target=1))
    verdicts = set()
    done = threading.Event()

    def _poll():
        while not done.wait(0.25):
            try:
                _, hz = _http_get(base_url + "/healthz")
                verdicts.add(json.loads(hz)["verdict"])
            except Exception:
                pass

    def _arm_when_hosts_up():
        deadline = time.perf_counter() + 60.0
        while time.perf_counter() < deadline and not done.is_set():
            try:
                _, hz = _http_get(base_url + "/healthz")
                comps = json.loads(hz)["components"]
                if "actor-host-0" in comps and "actor-host-1" in comps:
                    monkey.start(sys_)
                    return
            except Exception:
                pass
            time.sleep(0.2)

    threading.Thread(target=_poll, daemon=True).start()
    threading.Thread(target=_arm_when_hosts_up, daemon=True).start()
    try:
        stats = sys_.run(seconds=seconds)
    finally:
        done.set()
        monkey.stop()
    check(len(monkey.injected) == 2 and all(i[2] for i in monkey.injected),
          f"chaos injection incomplete: {monkey.injected}")
    check(stats["host_errors"] == [],
          f"host errors: {stats['host_errors']}")
    check(stats["learner_steps"] > 0, "learner never stepped")
    onp = stats["onpolicy"]
    check(onp["frames_generated"] == (onp["frames_trained"]
                                      + onp["frames_dropped"]
                                      + onp["frames_pending"]),
          f"frame ledger NOT conserved: {onp}")
    check(onp["frames_pending"] == 0,
          f"frames still pending at rest: {onp['frames_pending']}")
    rec = stats["recovery"]
    check(rec["host_restarts"] >= 1, f"no host respawn: {rec}")
    check(rec["reconnects"] >= 1, f"no client reconnect: {rec}")
    check(rec["checkpoint_saves"] >= 1, f"no live-loop checkpoint: {rec}")
    check(sys_.server.num_slots <= sys_.num_actors * sys_.envs_per_actor,
          f"slot table grew past the lane budget: {sys_.server.num_slots}")
    check("degraded" in verdicts,
          f"faults were never observable on /healthz: {verdicts}")
    check(any("host_death" in b for b in tel.flightrec.bundles),
          f"no host_death postmortem: {tel.flightrec.bundles}")
    healed = False
    deadline = time.perf_counter() + 6.0
    while time.perf_counter() < deadline:
        status, hz = _http_get(base_url + "/healthz")
        if status == 200 and json.loads(hz)["verdict"] == "healthy":
            healed = True
            break
        time.sleep(0.25)
    check(healed, f"/healthz never healed after the faults: {hz}")
    sys_.stop_ops()

    fps_base, fps_fault, frac = _fault_overhead_gate(
        seconds=max(sec * 2, 0.6))
    check(frac < 0.03,
          f"armed fault plane costs {frac:.1%} frames/s "
          f"({fps_fault:.0f} vs {fps_base:.0f}) — gate is 3%")

    payload = {
        "seconds": seconds,
        "env_frames": stats["env_frames"],
        "env_frames_per_s": stats["env_frames_per_s"],
        "learner_steps": stats["learner_steps"],
        "ledger": {k: onp[k] for k in
                   ("frames_generated", "frames_trained", "frames_dropped",
                    "frames_dropped_fault", "frames_pending")},
        "recovery": rec,
        "healthz_verdicts": sorted(verdicts),
        "fps_fail_fast": fps_base,
        "fps_fault_armed": fps_fault,
        "fault_overhead_frac": frac,
        "failures": failures,
    }
    print(json.dumps({"fig3_chaos": payload}, sort_keys=True))
    print("# fig3h: chaos-injected survival run (vtrace, socket, 2 hosts)")
    print("name,value,derived")
    print(f"fig3h_frames_per_s,{stats['env_frames_per_s']:.1f},"
          f"frames={stats['env_frames']} learner_steps="
          f"{stats['learner_steps']}")
    print(f"fig3h_host_restarts,{rec['host_restarts']},"
          f"host_faults={rec['host_faults']} "
          f"reconnects={rec['reconnects']} "
          f"gateway_failovers={rec['gateway_failovers']}")
    print(f"fig3h_frames_dropped_fault,{onp['frames_dropped_fault']},"
          f"generated={onp['frames_generated']} "
          f"trained={onp['frames_trained']} pending={onp['frames_pending']}")
    print(f"fig3h_checkpoint_saves,{rec['checkpoint_saves']},"
          f"live-loop cadence 1.0s")
    print(f"fig3h_healthz,{'/'.join(sorted(verdicts))},"
          f"healed={healed}")
    print(f"fig3h_fault_overhead_pct,{100.0 * frac:.2f},"
          f"armed={fps_fault:.0f} fail_fast={fps_base:.0f} gate=3%")
    if failures:
        for f_ in failures:
            print(f"fig3h_FAIL,1,{f_}")
        sys.exit(1)
    print("fig3h_ok,1,all chaos checks passed")


def _autoscale_overhead_gate(repeats=3, seconds=0.8):
    """The closed loop must be free while it merely watches: an in-proc
    run with the autoscale controller ARMED (sensing, deciding, logging
    every tick — but with no pool to resize) must cost < 3% best-of-N
    frames/s vs the identical telemetry-only run."""
    from repro.autoscale import AutoscaleConfig
    from repro.telemetry import Telemetry

    def best_fps(armed):
        best = 0.0
        for _ in range(repeats):
            kw = {"autoscale": AutoscaleConfig(interval_s=0.25)} \
                if armed else {}
            tel = Telemetry(process_name="learner")
            sys_ = SeedSystem(
                env_factory=CatchEnv, policy_step=_telemetry_policy,
                num_actors=2, unroll=8, envs_per_actor=2,
                deadline_ms=2.0, telemetry=tel, **kw)
            sys_.warmup()
            stats = sys_.run(seconds=seconds, with_learner=False)
            best = max(best, stats["env_frames_per_s"])
        return best

    base = best_fps(False)       # telemetry only, controller absent
    armed = best_fps(True)       # controller sensing/deciding every tick
    overhead = 1.0 - armed / base if base > 0 else 0.0
    return base, armed, overhead


def run_autoscale(args, sec, out_dir="."):
    """Part (i): the closed-loop elastic autoscaler, end to end.

    A DELIBERATELY actor-bound vtrace socket run (FlatSimEnv burns real
    CPU per step behind a flat observation; one actor host to start) runs
    with `SeedSystem(autoscale=AutoscaleConfig(...))` armed. Gates:

    - the controller grows actor hosts until the live BottleneckReport
      flips away from actor-bound OR the host cap binds (a saturated
      ``grow_hosts`` decision) — the convergence criterion;
    - at least one grow was actually applied, and EVERY applied resize
      has a decision-log entry scrapeable at ``/autoscaler`` carrying its
      evidence (trigger series, bottleneck class, SLO verdicts, topology
      before/after);
    - the frame ledger stays exactly conserved across the topology
      changes (generated == trained + dropped + pending, pending == 0);
    - the armed-but-idle controller costs < 3% frames/s vs autoscale-off
      (in-proc best-of-N pair).

    Prints the full evidence payload as one JSON line under
    ``fig3_autoscale``; exits nonzero on any failed check (CI runs ``--smoke --autoscale`` under a hard
    timeout).
    """
    import functools
    import threading

    import jax

    from repro.autoscale import AutoscaleConfig
    from repro.envs.alesim import FlatSimEnv
    from repro.onpolicy import VTraceLearner, mlp_actor_critic
    from repro.optim import adamw
    from repro.telemetry import Telemetry

    failures = []

    def check(ok, what):
        if not ok:
            failures.append(what)
        return ok

    os.makedirs(out_dir, exist_ok=True)
    env_factory = functools.partial(FlatSimEnv, step_cost=20000)
    obs_dim = FlatSimEnv().obs_dim
    init_fn, apply_fn = mlp_actor_critic(obs_dim, FlatSimEnv.num_actions)
    vl = VTraceLearner(apply_fn, adamw(1e-3))
    params = init_fn(jax.random.PRNGKey(0))
    state = vl.init_state(params)
    policy = vl.sampling_policy(params)
    for lanes in (4, 8, 16):
        policy(np.zeros((lanes, obs_dim), np.float32), None)
    vl.warmup(state, batch_size=2, unroll=8, obs_shape=(obs_dim,))
    tel = Telemetry(process_name="learner", out_dir=out_dir)
    # generous staleness bound + small learner batch: the learner must
    # keep up, so the window stays ACTOR-bound (the premise under test)
    sys_ = SeedSystem(env_factory=env_factory, policy_step=policy,
                      num_actors=4, unroll=8, envs_per_actor=2,
                      deadline_ms=2.0, algo="vtrace",
                      train_step=vl.train_step, state=state,
                      learner_batch=2, max_param_lag=10 ** 6,
                      policy_publish=policy.publish,
                      transport="socket", num_actor_hosts=1,
                      telemetry=tel, ops_port=0,
                      autoscale=AutoscaleConfig(
                          interval_s=0.25, max_hosts=3,
                          grow_after_ticks=2, cooldown_s=1.5,
                          churn_window_s=2.0))
    ops_host, ops_port = sys_.ops_address
    base_url = f"http://{ops_host}:{ops_port}"
    seconds = 8.0 if args.smoke else 12.0
    scrapes = {"autoscaler": [], "timeseries": [], "errors": []}
    done = threading.Event()

    def _scrape_loop():
        while not done.wait(0.4):
            try:
                _, body = _http_get(base_url + "/autoscaler")
                scrapes["autoscaler"].append(json.loads(body))
                _, ts = _http_get(base_url + "/timeseries?window=30")
                scrapes["timeseries"].append(json.loads(ts))
            except Exception as e:       # noqa: BLE001 — recorded, checked
                scrapes["errors"].append(str(e))

    threading.Thread(target=_scrape_loop, daemon=True).start()
    try:
        stats = sys_.run(seconds=seconds)
    finally:
        done.set()
    # final scrape AFTER the window: the complete decision log, over HTTP
    # (the acceptance path — not the in-process object)
    status, body = _http_get(base_url + "/autoscaler", timeout=5.0)
    final = json.loads(body) if status == 200 else {}
    sys_.stop_ops()

    check(status == 200, f"/autoscaler returned {status}")
    check(stats["host_errors"] == [],
          f"host errors: {stats['host_errors']}")
    check(stats["learner_steps"] > 0, "learner never stepped")

    # conserved ledger across grow (and any drain)
    onp = stats["onpolicy"]
    check(onp["frames_generated"] == (onp["frames_trained"]
                                      + onp["frames_dropped"]
                                      + onp["frames_pending"]),
          f"frame ledger NOT conserved across resizes: {onp}")
    check(onp["frames_pending"] == 0,
          f"frames still pending at rest: {onp['frames_pending']}")

    # convergence: grew, then flipped away from actor-bound or hit the cap
    entries = final.get("decisions", {}).get("entries", [])
    grown = stats.get("hosts_grown", 0)
    applied_total = sum(final.get("actions_applied", {}).values())
    check(grown >= 1, f"actor-bound run never grew a host "
                      f"(hosts_grown={grown})")
    saturated = any(e["action"]["saturated"]
                    and e["action"]["candidate"] == "grow_hosts"
                    for e in entries)
    tail = [e["bottleneck"].get("bottleneck") for e in entries[-8:]]
    flipped = bool(tail) and tail[-1] != "actor-bound"
    check(saturated or flipped,
          f"no convergence: never saturated grow_hosts nor flipped away "
          f"from actor-bound (tail classes: {tail})")

    # every applied resize is scrapeable evidence at /autoscaler
    applied_entries = [e for e in entries if e.get("applied")]
    check(len(applied_entries) == applied_total,
          f"{applied_total} applied actions but {len(applied_entries)} "
          f"applied decision-log entries scraped")
    for e in applied_entries:
        ok = (e.get("trigger") and "bottleneck" in e
              and "slo" in e and "topology_before" in e
              and "topology_after" in e)
        check(ok, f"applied decision entry missing evidence: "
                  f"{sorted(e.keys())}")
    check(bool(scrapes["autoscaler"]),
          f"no mid-run /autoscaler scrape landed "
          f"(errors: {scrapes['errors'][:3]})")
    series_seen = set()
    for ts_doc in scrapes["timeseries"][-1:]:
        series_seen = set(ts_doc.get("series", {}))
    check("frames_generated" in series_seen,
          f"/timeseries missing frames_generated (saw {sorted(series_seen)[:8]})")

    # armed-but-idle controller overhead (in-proc best-of-N pair)
    fps_off, fps_armed, frac = _autoscale_overhead_gate(
        seconds=max(sec * 2, 0.6))
    check(frac < 0.03,
          f"armed-but-idle autoscaler costs {frac:.1%} frames/s "
          f"({fps_armed:.0f} vs {fps_off:.0f}) — gate is 3%")

    payload = {
        "seconds": seconds,
        "env_frames": stats["env_frames"],
        "env_frames_per_s": stats["env_frames_per_s"],
        "learner_steps": stats["learner_steps"],
        "hosts_grown": grown,
        "hosts_drained": stats.get("hosts_drained", 0),
        "actor_hosts_live": stats.get("actor_hosts_live"),
        "actions_applied": final.get("actions_applied", {}),
        "decision_entries": len(entries),
        "converged_by": ("saturated" if saturated else
                         "flipped" if flipped else "none"),
        "ledger": {k: onp[k] for k in
                   ("frames_generated", "frames_trained", "frames_dropped",
                    "frames_pending")},
        "fps_autoscale_off": fps_off,
        "fps_autoscale_armed": fps_armed,
        "autoscale_overhead_frac": frac,
        "failures": failures,
    }
    print(json.dumps({"fig3_autoscale": payload}, sort_keys=True))

    print("# fig3i: closed-loop autoscaler (vtrace, socket, actor-bound)")
    print("name,value,derived")
    print(f"fig3i_frames_per_s,{stats['env_frames_per_s']:.1f},"
          f"frames={stats['env_frames']} "
          f"learner_steps={stats['learner_steps']}")
    print(f"fig3i_hosts_grown,{grown},"
          f"live={stats.get('actor_hosts_live')} "
          f"drained={stats.get('hosts_drained', 0)} cap=3")
    print(f"fig3i_decisions,{len(entries)},"
          f"applied={applied_total} "
          f"converged_by={payload['converged_by']}")
    print(f"fig3i_ledger,{onp['frames_generated']},"
          f"trained={onp['frames_trained']} "
          f"dropped={onp['frames_dropped']} pending={onp['frames_pending']}")
    print(f"fig3i_scrapes,{len(scrapes['autoscaler'])},"
          f"mid-run /autoscaler + /timeseries")
    print(f"fig3i_overhead_pct,{100.0 * frac:.2f},"
          f"armed={fps_armed:.0f} off={fps_off:.0f} gate=3%")
    if failures:
        for f_ in failures:
            print(f"fig3i_FAIL,1,{f_}")
        sys.exit(1)
    print("fig3i_ok,1,all autoscale checks passed")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny measured windows (CI: exercise the path)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="widest point of the sharded-inference sweep (e)")
    ap.add_argument("--algo", choices=("r2d2", "vtrace"), default="r2d2",
                    help="r2d2: parts (a-e); vtrace: the on-policy "
                         "training-plane sweep (f)")
    ap.add_argument("--telemetry", action="store_true",
                    help="part (g): socket run under the telemetry plane, "
                         "validating trace/metrics/ratio artifacts")
    ap.add_argument("--chaos", action="store_true",
                    help="part (h): chaos-injected vtrace socket run "
                         "(host killed + gateway conn severed) gating the "
                         "conserved ledger and fault-path overhead")
    ap.add_argument("--autoscale", action="store_true",
                    help="part (i): deliberately actor-bound vtrace socket "
                         "run under the closed-loop autoscaler, gating "
                         "convergence, /autoscaler decision evidence, the "
                         "conserved ledger and armed-idle overhead")
    ap.add_argument("--out-dir", default=".",
                    help="where --telemetry/--chaos/--autoscale write "
                         "trace.json, metrics.jsonl and postmortems")
    args = ap.parse_args()
    sec = 0.3 if args.smoke else 1.2
    if args.telemetry:
        run_telemetry(args, sec, out_dir=args.out_dir)
        return
    if args.chaos:
        run_chaos(args, sec, out_dir=args.out_dir)
        return
    if args.autoscale:
        run_autoscale(args, sec, out_dir=args.out_dir)
        return
    if args.algo == "vtrace":
        run_vtrace(args, sec)
        return
    actor_counts = (1, 2) if args.smoke else (1, 2, 4, 8)
    env_counts = (1, 4) if args.smoke else (1, 2, 4, 8)
    print("# fig3a: measured actor sweep (scaled-down, this host)")
    print("name,value,derived")
    rows = measured_sweep(actor_counts=actor_counts, seconds=sec)
    base = rows[0][1]
    for n, fps, occ, wait in rows:
        print(f"fig3a_actors_{n},{fps:.1f},frames_per_s speedup={fps/base:.2f} "
              f"occupancy={occ:.2f} queue_wait_ms={wait:.2f}")
    print("# fig3b: calibrated model at paper scale (40 hw threads)")
    model, err, sw = model_sweep()
    for n, s in sw:
        print(f"fig3b_speedup_{n},{s:.2f},relative_to_4_actors")
    s40 = dict(sw)[40]
    s256_40 = dict(sw)[256] / dict(sw)[40]
    print(f"fig3b_check_4to40,{s40:.2f},paper=5.8 err={abs(s40-5.8)/5.8:.1%}")
    print(f"fig3b_check_40to256,{s256_40:.2f},paper=2.0 err={abs(s256_40-2.0)/2.0:.1%}")
    print(f"fig3b_fit_residual,{err:.4f},rms")
    print("# fig3c: envs-per-actor sweep (measured, fixed actor threads)")
    env_rows = measured_env_sweep(env_counts=env_counts, seconds=sec)
    per_thread_base = env_rows[0][2]
    for E, fps, per_thread, occ, wait in env_rows:
        print(f"fig3c_envs_{E},{fps:.1f},frames_per_s per_thread={per_thread:.1f} "
              f"per_thread_speedup={per_thread/per_thread_base:.2f} "
              f"occupancy={occ:.2f} queue_wait_ms={wait:.2f}")
    print("# fig3c: model at paper scale (40 actors, E lanes each)")
    for E, s in model_env_sweep():
        print(f"fig3c_model_envs_{E},{s:.2f},throughput_vs_E1_at_40_actors")
    print("# fig3d: design points at equal (num_actors, E) — measured, Catch")
    d_rows = measured_backend_sweep(seconds=sec, unroll=8 if args.smoke else 16)
    d_base = d_rows[0][2]
    for name, E, fps in d_rows:
        print(f"fig3d_{name},{fps:.1f},frames_per_s E={E} "
              f"vs_per_step={fps/d_base:.2f}x")
    dev = dict((n, f) for n, _, f in d_rows)
    if dev["device_resident"] <= dev["vectorized_host"]:
        print("fig3d_WARNING,0,device_resident did not beat vectorized_host")
    print("# fig3d: model at paper scale (40 actors x 8 lanes)")
    m_rows = model_backend_sweep()
    m_base = m_rows[0][1]
    for name, t in m_rows:
        print(f"fig3d_model_{name},{t:.1f},frames_per_s_model "
              f"vs_per_step={t/m_base:.2f}x")
    print("# fig3e: sharded inference — measured replica sweep (this host)")
    replica_counts = tuple(sorted({1, max(args.replicas, 1)}))
    r_rows = measured_replica_sweep(replica_counts=replica_counts,
                                    seconds=sec)
    r_base = r_rows[0][1]
    for R, fps, occ, lanes in r_rows:
        print(f"fig3e_replicas_{R},{fps:.1f},frames_per_s "
              f"vs_single={fps/max(r_base, 1e-9):.2f}x occupancy={occ:.2f} "
              f"replica_lanes={lanes}")
    print("# fig3e: engine-sharded device scans (measured)")
    k_rows = measured_engine_shard_sweep(shard_counts=replica_counts,
                                         seconds=sec,
                                         unroll=8 if args.smoke else 16)
    k_base = k_rows[0][1]
    for K, fps in k_rows:
        print(f"fig3e_engine_shards_{K},{fps:.1f},frames_per_s "
              f"vs_single={fps/max(k_base, 1e-9):.2f}x")
    print("# fig3e: with_sharded model at paper scale (40 actors, E=1)")
    for R, s in model_replica_sweep():
        print(f"fig3e_model_replicas_{R},{s:.2f},throughput_vs_1_replica")
    # GPU power / perf-per-watt (paper's right axis): utilization-linear model
    from repro.hw import V100
    for n, s in sw:
        util = min(1.0, s / max(x for _, x in sw))
        power = V100.idle_power_w + (V100.peak_power_w - V100.idle_power_w) * util
        ppw = s / power
        print(f"fig3b_perf_per_watt_{n},{ppw*100:.3f},speedup_per_100W power={power:.0f}W")


if __name__ == "__main__":
    main()
