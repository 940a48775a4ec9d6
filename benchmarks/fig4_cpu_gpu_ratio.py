"""Fig 4 reproduction: accelerator derating (SM-disable), the CPU/GPU-ratio
metric across real systems + the provisioning rule — and, now that the
ratio is a real knob (`repro.transport`), the measured cost of turning it:
the same SEED system run in-proc vs over a loopback-TCP gateway vs the
shared-memory ring transport, with each wire's RTT threaded back through
`SystemModel.with_network(..., wire=...)` and the ratio decomposed per
disaggregated actor host.

The wire hot-path numbers (frames/s per transport, best-of-N round-trip
probes for both planes, bytes/frame under RAW/RLE/F16/Q8 framing) are
also written to `BENCH_wire.json` so regressions show up in review diffs.

`--smoke` shrinks the measured windows so CI exercises the full wire path
(spawned actor hosts, gateway, codec, shm rings) in seconds; `--transport
shm` restricts the system sweep to {inproc, shm} and turns the best-of-N
"shm beats loopback TCP" probe into a hard gate (nonzero exit).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

from repro.core.provisioning import (SystemModel, cpu_gpu_ratio,
                                     cpu_gpu_ratio_breakdown,
                                     fit_paper_actor_model,
                                     fit_paper_derating, provision)
from repro.core.system import SeedSystem
from repro.envs.catch import CatchEnv
from repro.hw import DGX1_HOST, TPU_V5E, V100, V5E_HOST
from repro.launch.compile_cache import enable_compile_cache


def _policy_step(obs, ids):
    # deterministic, slot-order independent: measured runs stay comparable
    flat = np.abs(obs.reshape(obs.shape[0], -1))
    return (flat.sum(axis=1) * 997.0).astype(np.int64) % CatchEnv.num_actions


def measured_transport_sweep(num_actors=2, envs_per_actor=4, seconds=1.0,
                             unroll=8, num_actor_hosts=2, num_gateways=1,
                             transports=("inproc", "socket", "shm"),
                             telemetry=False):
    """The same (num_actors, E) SEED system on Catch, in-proc vs loopback
    TCP vs shared-memory rings: frames/s, per-actor cycle time, and the
    implied wire RTT. With `num_gateways > 1` the socket run shards the
    accept loop: G gateways (+ G inference replicas, one per gateway)
    with actor hosts hashed across their addresses. ``telemetry=True``
    runs each point under its own `repro.telemetry.Telemetry`, so every
    stats dict carries a measured ``bottleneck`` attribution."""
    rows = []
    for transport in transports:
        tel = None
        if telemetry:
            from repro.telemetry import Telemetry
            tel = Telemetry(process_name="learner")
        kwargs = dict(env_factory=CatchEnv, policy_step=_policy_step,
                      num_actors=num_actors, unroll=unroll,
                      envs_per_actor=envs_per_actor, deadline_ms=1.0,
                      transport=transport, telemetry=tel)
        if transport in ("socket", "shm"):
            kwargs["num_actor_hosts"] = num_actor_hosts
            kwargs["num_gateways"] = num_gateways
            kwargs["num_replicas"] = num_gateways
        sys_ = SeedSystem(**kwargs)
        sys_.warmup()
        stats = sys_.run(seconds=seconds, with_learner=False)
        rows.append((transport, stats))
    return rows


def measure_wire_ping(envs_per_actor=4, pings=200, trials=3):
    """Best-of-N probe of both wire planes: the same lane-batched request
    round-tripped through a loopback-TCP gateway connection, through a
    CODEC_SHM ring pair on a second connection to the SAME gateway, and
    through the in-process queue. Best-of-N (min over trials) because the
    quantity of interest is the transport floor, not scheduler noise.
    Independent of the system sweep, so feeding the deltas to
    `with_network(..., wire=...)` is a real prediction, not a
    re-derivation of the measured frames/s.

    Returns ``(best, shm_active)`` — best maps {"tcp","shm","inproc"} to
    per-round-trip seconds; shm_active says whether the ring pair was
    actually granted + attached (False means the "shm" column silently
    measured the TCP spill path and must not gate anything).
    """
    import time

    from repro.core.inference import InferenceServer
    from repro.transport.socket import (InferenceGateway, ShmTransport,
                                        SyncSocketTransport)

    srv = InferenceServer(_policy_step, max_batch=envs_per_actor,
                          deadline_ms=0.5)
    srv.start()
    gw = InferenceGateway(srv)
    addr = gw.start()
    tcp = SyncSocketTransport.connect(addr)
    shm = ShmTransport.connect(addr)
    shm.wait_hello(5.0)
    obs = np.zeros((envs_per_actor,) + CatchEnv().obs_shape, np.float32)
    best = {}
    try:
        def ping(submit):
            for _ in range(20):                      # warm
                submit(obs).get(timeout=5.0)
            t0 = time.perf_counter()
            for _ in range(pings):
                submit(obs).get(timeout=5.0)
            return (time.perf_counter() - t0) / pings

        for _ in range(max(int(trials), 1)):
            for name, submit in (
                    ("tcp", lambda o: tcp.submit_batch(0, o)),
                    ("shm", lambda o: shm.submit_batch(1, o)),
                    ("inproc", lambda o: srv.submit_batch(2, o))):
                t = ping(submit)
                best[name] = min(best.get(name, t), t)
        shm_active = shm.shm_active and shm.shm_frames > 0
    finally:
        tcp.close()
        shm.close()
        gw.stop()
        srv.stop()
    return best, shm_active


def wire_bytes_table(envs_per_actor=4):
    """Bytes/frame ledger for representative payloads under each framing.

    Catch observations are (50,) float32 boards that are mostly zeros with
    a couple of ones — exactly the shape where RLE (on the uint8 view),
    F16 (2x), and Q8 (4x + 8-byte scale/offset prologue) earn their HELLO
    bits. TRAJ_BATCH amortizes the 24-byte frame header + per-record keys
    across a whole unroll flush.
    """
    from repro.transport import codec as C

    f32 = np.zeros((envs_per_actor,) + CatchEnv().obs_shape, np.float32)
    f32[:, 0] = 1.0
    f32[:, 7] = 1.0
    u8 = f32.astype(np.uint8)

    def req(obs, **kw):
        return len(C.encode_request(7, 1, obs, **kw))

    traj = {"obs": f32, "action": np.zeros(envs_per_actor, np.int64),
            "reward": np.zeros(envs_per_actor, np.float32)}
    rows = {
        "request_obs_f32_raw": req(f32),
        "request_obs_f32_f16": req(f32, quant="f16"),
        "request_obs_f32_q8": req(f32, quant="q8"),
        "request_obs_u8_raw": req(u8),
        "request_obs_u8_rle": req(u8, compress=True),
        "traj_record_solo": len(C.encode_trajectory(3, traj)),
        "traj_record_in_batch8":
            len(C.encode_traj_batch(3, [traj] * 8)) / 8.0,
    }
    return rows


def transport_model_check(rows, num_actors, envs_per_actor, t_rtt,
                          wire="tcp", measured_key="socket"):
    """Calibrate t_env from the in-proc run only, add the independently
    probed wire RTT via `with_network(..., wire=...)`, and predict the
    wire run — checking the model reproduces the measured throughput
    ordering. Called once per wire plane: the tcp and shm operating
    points are the SAME model at different probed t_rtt."""
    fps = {t: s["env_frames_per_s"] for t, s in rows}
    # per-actor cycle time: one cycle supplies E frames from each of n actors
    cycle_in = num_actors * envs_per_actor / fps["inproc"]
    base = SystemModel(t_env=cycle_in / envs_per_actor,
                       t_inf0=0.0, t_inf1=0.0,
                       hw_threads=os.cpu_count() or 1,
                       envs_per_actor=envs_per_actor)
    model_in = float(base.throughput(num_actors))
    model_net = float(base.with_network(t_rtt, wire=wire)
                      .throughput(num_actors))
    ordered = (model_net <= model_in) == \
        (fps[measured_key] <= fps["inproc"])
    return model_in, model_net, ordered


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny measured windows (CI: exercise the wire path)")
    ap.add_argument("--gateways", type=int, default=1,
                    help="shard the socket run across G gateways (+ G "
                         "inference replicas); hosts hash across addresses")
    ap.add_argument("--transport", choices=("socket", "shm", "all"),
                    default="all",
                    help="which wire planes to sweep against inproc; "
                         "'shm' also turns the best-of-N shm-vs-TCP "
                         "probe into a hard gate (nonzero exit)")
    ap.add_argument("--out", default=os.path.join(
                        os.path.dirname(os.path.abspath(__file__)), "..",
                        "BENCH_wire.json"),
                    help="where to write the wire benchmark ledger")
    ap.add_argument("--telemetry", action="store_true",
                    help="run each transport point under the telemetry "
                         "plane: print the MEASURED bottleneck/CPU-GPU "
                         "ratio per transport and merge the attributions "
                         "into BENCH_telemetry.json next to --out")
    args = ap.parse_args()
    sec = 0.5 if args.smoke else 1.5
    hosts = max(1 if args.smoke else 2, args.gateways)
    wire_transports = {"socket": ("inproc", "socket"),
                       "shm": ("inproc", "shm"),
                       "all": ("inproc", "socket", "shm")}[args.transport]

    print("# fig4: slowdown vs compute fraction (40 CPU threads fixed)")
    print("name,value,derived")
    m = fit_paper_derating()
    for sms in (80, 64, 40, 20, 8, 2):
        f = sms / 80.0
        print(f"fig4_slowdown_{sms}sm,{float(m.slowdown(f)):.3f},"
              f"paper_at_40sm=1.06")

    print("# cpu/gpu ratio of real systems (paper Conclusion 3: want >= 1)")
    rows = [
        ("dgx1", cpu_gpu_ratio(DGX1_HOST, V100, 8)),          # paper: 1/16
        ("dgx_a100", 256 / (8 * 108 * (312e12 / 108) / (125e12 / 80))),
        ("v5e_host_8chip", cpu_gpu_ratio(V5E_HOST, TPU_V5E, 8)),
    ]
    for name, r in rows:
        print(f"ratio_{name},{r:.4f},threads_per_v100_sm_equivalent")

    print("# ratio, disaggregated: K actor hosts behind repro.transport")
    for k in (1, 2, 4, 8, 16):
        b = cpu_gpu_ratio_breakdown([DGX1_HOST] * k, V100, 8)
        verdict = "balanced" if b.total >= 1.0 else "starved"
        print(f"ratio_dgx1_{k}hosts,{b.total:.4f},"
              f"{k}x{DGX1_HOST.hw_threads}threads {verdict}")

    print("# measured: in-proc vs loopback-TCP vs shm-ring (same system)")
    n_act, E = max(2, hosts), 4
    t_rows = measured_transport_sweep(num_actors=n_act, envs_per_actor=E,
                                      seconds=sec, num_actor_hosts=hosts,
                                      num_gateways=args.gateways,
                                      transports=wire_transports,
                                      telemetry=args.telemetry)
    bench = {"benchmark": "fig4_wire", "smoke": bool(args.smoke),
             "num_actors": n_act, "envs_per_actor": E,
             "num_actor_hosts": hosts, "seconds": sec,
             "transports": {}, "ping_rtt_s": {}, "ping_frames_per_s": {},
             "bytes_per_frame": wire_bytes_table(envs_per_actor=E)}
    fps = {}
    for transport, stats in t_rows:
        fps[transport] = stats["env_frames_per_s"]
        err = stats["inference_error"] or \
            (stats.get("host_errors") or [None])[0]
        shard = ""
        if transport in ("socket", "shm"):
            shard = (f" gateways={stats.get('num_gateways', 1)} "
                     f"conns_per_gateway="
                     f"{stats.get('per_gateway_connections')}")
        if transport == "shm":
            shard += (f" shm_frames={stats.get('host_shm_frames')} "
                      f"spill_frames={stats.get('host_spill_frames')}")
        print(f"fig4_transport_{transport},{stats['env_frames_per_s']:.1f},"
              f"frames_per_s occupancy={stats['mean_batch_occupancy']:.2f} "
              f"queue_wait_ms={stats['mean_queue_wait_ms']:.2f} "
              f"error={err}{shard}")
        bench["transports"][transport] = {
            "env_frames_per_s": stats["env_frames_per_s"],
            "mean_batch_occupancy": stats["mean_batch_occupancy"],
            "mean_queue_wait_ms": stats["mean_queue_wait_ms"],
            "host_shm_frames": stats.get("host_shm_frames"),
            "host_spill_frames": stats.get("host_spill_frames"),
            "error": err,
        }
        if args.telemetry and "bottleneck" in stats:
            b_ = stats["bottleneck"]
            print(f"fig4_measured_ratio_{transport},"
                  f"{b_['cpu_gpu_ratio']:.2f},{b_['bottleneck']} "
                  f"wire_share={b_['shares'].get('wire', 0.0):.2f}")
    if args.telemetry:
        from repro.telemetry import merge_bench_json
        tel_out = os.path.join(os.path.dirname(os.path.normpath(args.out)),
                               "BENCH_telemetry.json")
        merge_bench_json(tel_out, "fig4_transports", {
            "smoke": bool(args.smoke), "seconds": sec,
            "num_actors": n_act, "envs_per_actor": E,
            "attribution": {t: s["bottleneck"] for t, s in t_rows
                            if "bottleneck" in s},
        })
        print(f"# merged measured attributions into {tel_out}")
    gate_failed = None
    if min(fps.values()) <= 0:
        # a failed run reports its error above; don't bury it under a
        # ZeroDivisionError traceback
        print("fig4_transport_relative,NaN,run_produced_zero_frames")
        gate_failed = "system sweep produced zero frames"
    else:
        for wire_t in wire_transports[1:]:
            rel = fps[wire_t] / fps["inproc"]
            print(f"fig4_transport_relative_{wire_t},{rel:.3f},"
                  f"{wire_t}_over_inproc acceptance>=0.5")
        if "socket" in fps and "shm" in fps:
            print(f"fig4_transport_shm_over_tcp,"
                  f"{fps['shm'] / fps['socket']:.3f},"
                  f"system_sweep_single_trial (gate is the best-of-N probe)")
        # best-of-N round-trip probe of both planes on one gateway
        best, shm_active = measure_wire_ping(
            envs_per_actor=E, pings=100 if args.smoke else 200,
            trials=3 if args.smoke else 5)
        for name in ("inproc", "tcp", "shm"):
            bench["ping_rtt_s"][name] = best[name]
            bench["ping_frames_per_s"][name] = E / best[name]
            print(f"fig4_ping_{name},{1e6 * best[name]:.1f},"
                  f"us_per_roundtrip best_of_N "
                  f"frames_per_s={E / best[name]:.0f}")
        bench["shm_ring_active"] = bool(shm_active)
        shm_over_tcp = best["tcp"] / best["shm"]
        print(f"fig4_ping_shm_over_tcp,{shm_over_tcp:.3f},"
              f"probe_speedup ring_active={shm_active} acceptance>=1.0")
        if "shm" in wire_transports:
            if not shm_active:
                gate_failed = "CODEC_SHM ring never activated on loopback"
            elif best["shm"] > best["tcp"]:
                gate_failed = (f"shm probe slower than TCP loopback: "
                               f"{1e6 * best['shm']:.1f}us vs "
                               f"{1e6 * best['tcp']:.1f}us (best-of-N)")
        # model check per wire plane, each at its own probed RTT
        t_probe = {"socket": max(best["tcp"] - best["inproc"], 0.0),
                   "shm": max(best["shm"] - best["inproc"], 0.0)}
        wire_of = {"socket": "tcp", "shm": "shm"}
        for wire_t in wire_transports[1:]:
            t_rtt = t_probe[wire_t]
            model_in, model_net, ordered = transport_model_check(
                t_rows, n_act, E, t_rtt, wire=wire_of[wire_t],
                measured_key=wire_t)
            print(f"fig4_wire_rtt_ms_{wire_t},{1e3 * t_rtt:.3f},"
                  f"probed_{wire_of[wire_t]}_rtt_minus_inproc")
            print(f"fig4_model_network_{wire_t},{model_net:.1f},"
                  f"frames_per_s with_network({1e3 * t_rtt:.2f}ms,"
                  f"wire={wire_of[wire_t]})_prediction "
                  f"measured={fps[wire_t]:.1f} ordering_ok={ordered}")
        bench["shm_over_tcp_probe"] = shm_over_tcp
    out = os.path.normpath(args.out)
    with open(out, "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {out}")
    # trend-guard history: one point per wire transport measured this run
    # (BENCH_wire.json above is wholesale-replaced; the history ledger
    # accumulates — see benchmarks/check_trend.py)
    from repro.telemetry import append_bench_history, bench_commit
    hist_path = os.path.join(os.path.dirname(out), "BENCH_history.json")
    for wire_t in wire_transports[1:]:
        if fps.get(wire_t, 0) > 0:
            append_bench_history(
                hist_path, f"fig4_{wire_t}",
                {"commit": bench_commit(), "ts": time.time(),
                 "frames_per_s": fps[wire_t], "smoke": bool(args.smoke)})
    if gate_failed and "shm" in wire_transports:
        print(f"fig4_shm_gate,FAIL,{gate_failed}")
        sys.exit(1)

    print("# sharded inference plane: with_sharded at paper scale, and the")
    print("# per-replica ratio decomposition (hosts hash to replicas)")
    model, _ = fit_paper_actor_model()
    m_net = model.with_network(0.2, n_hosts=4)
    base = float(m_net.throughput(160))
    for R in (1, 2, 4, 8):
        t = float(m_net.with_sharded(R).throughput(160))
        print(f"fig4_model_sharded_{R},{t/base:.3f},"
              f"throughput_vs_1_replica_at_4hosts_160actors")
    b = cpu_gpu_ratio_breakdown([DGX1_HOST] * 3, V100, 8, n_replicas=2)
    for r, threads, ratio in b.per_replica:
        print(f"fig4_ratio_replica_{r},{ratio:.4f},"
              f"threads={threads:.0f} over_sm_slice "
              f"(3 hosts hashed across 2 replicas -> imbalance visible)")

    print("# provisioning: host threads needed per workload (v5e-8 host)")
    for name, flops_frame in (("r2d2_atari_2M", 2e6),
                              ("lm_policy_1B", 2e9),
                              ("lm_policy_32B_active", 6.4e10)):
        p = provision(TPU_V5E, V5E_HOST, 8,
                      train_flops_per_frame=6 * flops_frame,
                      infer_flops_per_frame=2 * flops_frame, mfu=0.4)
        print(f"provision_{name},{p.threads_required:.1f},"
              f"threads_needed demand={p.frames_demand_per_s:.0f}fps "
              f"balanced={p.balanced}")


if __name__ == "__main__":
    main()
