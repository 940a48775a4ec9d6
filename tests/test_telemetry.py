"""Telemetry-plane tests: registry atomicity, histogram percentiles, the
disabled-tracer overhead gate, ring wraparound, Chrome-trace schema, wire
trace_seq round-trips, CPU sampling, bottleneck attribution, and the
cross-process stitch e2e.

The atomicity tests are the load-bearing ones: the registry exists to fix
the old plain-dict stats shards, whose readers could observe a replica
that had counted a batch but not its requests. Here we hammer snapshots
against live writers and assert the cross-counter invariants hold at
EVERY observation point, not just at rest.
"""

import io
import json
import os
import threading
import time

import numpy as np
import pytest

from repro.core.inference import InferenceServer
from repro.core.system import SeedSystem
from repro.envs.catch import CatchEnv
from repro.telemetry import (BottleneckReport, Histogram, MetricsRegistry,
                             Telemetry, Tracer, attribute_bottleneck,
                             chrome_trace, flow_events, next_trace_seq,
                             read_process_cpu_s)
from repro.transport import codec


def det_policy(obs, ids):
    flat = np.abs(obs.reshape(obs.shape[0], -1))
    return (flat.sum(axis=1) * 997.0).astype(np.int64) % CatchEnv.num_actions


# ------------------------------------------------------------- registry

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("x/count")
    c.add()
    c.add(4)
    g = reg.gauge("x/depth")
    g.set(7)
    reg.gauge("x/live", fn=lambda: 3.5)
    h = reg.histogram("x/lat")
    for v in (1e-3, 2e-3, 4e-3):
        h.record(v)
    snap = reg.snapshot()
    assert snap["counters"]["x/count"] == 5
    assert snap["gauges"]["x/depth"] == 7.0
    assert snap["gauges"]["x/live"] == 3.5
    assert snap["histograms"]["x/lat"]["count"] == 3
    # get-or-create returns the same instrument
    assert reg.counter("x/count") is c


def test_gauge_callback_failure_is_nan_not_fatal():
    reg = MetricsRegistry()
    reg.gauge("bad", fn=lambda: 1 / 0)
    assert np.isnan(reg.snapshot()["gauges"]["bad"])


def test_histogram_percentiles_bracket_observations():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    vals = [10e-6] * 50 + [100e-6] * 45 + [10e-3] * 5
    for v in vals:
        h.record(v)
    s = h.snapshot()
    assert s["count"] == 100
    assert s["min"] == pytest.approx(10e-6)
    assert s["max"] == pytest.approx(10e-3)
    # log2 buckets: estimates within 2x of the true percentile, and the
    # ordering p50 <= p95 <= p99 always holds
    assert s["p50"] <= s["p95"] <= s["p99"] <= s["max"]
    assert 5e-6 <= s["p50"] <= 20e-6
    assert s["p99"] >= 100e-6


def test_empty_histogram_never_raises():
    reg = MetricsRegistry()
    s = reg.histogram("nothing").snapshot()
    assert s["count"] == 0
    assert s["p50"] is None and s["p99"] is None
    assert s["mean"] is None and s["min"] is None
    assert Histogram.merge_snapshots([s, None]) is None


def test_histogram_merge_is_exact_on_buckets():
    reg_a, reg_b = MetricsRegistry(), MetricsRegistry()
    ha, hb = reg_a.histogram("rtt"), reg_b.histogram("rtt")
    for v in (1e-4, 2e-4, 3e-4):
        ha.record(v)
    for v in (1e-2, 2e-2):
        hb.record(v)
    m = Histogram.merge_snapshots([ha.snapshot(), hb.snapshot()])
    assert m["count"] == 5
    assert m["sum"] == pytest.approx(6e-4 + 3e-2)
    assert m["min"] == pytest.approx(1e-4)
    assert m["max"] == pytest.approx(2e-2)
    assert sum(m["buckets"].values()) == 5


def test_snapshot_atomicity_under_batched_writers():
    """Writers keep `requests == 4 * batches` true under the lock; every
    concurrent snapshot must observe the invariant exactly — the property
    the per-instrument-lock design this registry replaced could not give."""
    reg = MetricsRegistry()
    c = reg.counters("rep", ("batches", "requests"))
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            with reg.lock:
                c["batches"].value += 1
                c["requests"].value += 4

    threads = [threading.Thread(target=writer, daemon=True)
               for _ in range(3)]
    for t in threads:
        t.start()
    try:
        deadline = time.perf_counter() + 0.5
        reads = 0
        while time.perf_counter() < deadline:
            snap = reg.read(c)
            assert snap["requests"] == 4 * snap["batches"], snap
            full = reg.snapshot()["counters"]
            assert full["rep/requests"] == 4 * full["rep/batches"]
            reads += 1
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=2.0)
    assert reads > 10


def test_live_system_stats_snapshot_consistency():
    """Hammer `InferenceServer.stats` / `per_replica_stats()` while a real
    system serves: the cross-counter invariants (every batch serves >= 1
    rpc, every rpc >= 1 lane, occupancy accumulates <= 1 per batch) and
    the aggregate == sum(decomposition) identity must hold mid-flight."""
    tel = Telemetry(enabled=False, process_name="learner")
    sys_ = SeedSystem(env_factory=CatchEnv, policy_step=det_policy,
                      num_actors=2, unroll=4, envs_per_actor=2,
                      num_replicas=2, deadline_ms=1.0, telemetry=tel)
    sys_.warmup()
    srv = sys_.server
    srv.start()
    for a in sys_.actors:
        a.start()
    try:
        deadline = time.perf_counter() + 1.0
        while time.perf_counter() < deadline:
            s = srv.stats
            assert s["requests"] >= s["rpcs"] >= s["batches"] >= 0, s
            assert s["batch_occupancy"] <= s["batches"] + 1e-9, s
            per = srv.per_replica_stats()
            assert sum(r["batches"] for r in per) <= srv.stats["batches"]
            for r in per:
                assert r["requests"] >= r["rpcs"] >= r["batches"], r
    finally:
        for a in sys_.actors:
            a.stop()
        srv.stop()
        for a in sys_.actors:
            a.join()
    assert srv.stats["batches"] > 0


def test_empty_system_derived_stats_never_raise():
    """Satellite regression: a server that served nothing must report 0.0
    means (and an empty telemetry window must classify as idle), never
    divide by zero."""
    srv = InferenceServer(det_policy, max_batch=4)
    d = srv.derived_stats()
    assert d["mean_batch_occupancy"] == 0.0
    assert d["mean_queue_wait_ms"] == 0.0
    assert d["mean_lanes_per_batch"] == 0.0
    assert srv.per_replica_stats()[0]["mean_lanes_per_rpc"] == 0.0
    tel = Telemetry(process_name="learner")
    rep = tel.bottleneck_report({})
    assert rep.bottleneck == "idle"
    assert np.isfinite(rep.cpu_gpu_ratio)
    assert all(np.isfinite(v) for v in rep.seconds_per_frame.values())


# --------------------------------------------------------------- tracer

def test_disabled_tracer_overhead_gate():
    """The disabled path must stay an attribute check + cached no-op —
    best-of-N per-call cost under a loose ceiling sized for a loaded
    2-core CI container (a regression to per-call allocation or a clock
    read lands an order of magnitude above it)."""
    tr = Tracer(enabled=False)
    n = 20000

    def timed():
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.trace_span("hot"):
                pass
        return (time.perf_counter() - t0) / n

    best = min(timed() for _ in range(5))
    assert best < 5e-6, f"disabled trace_span cost {best * 1e9:.0f}ns/call"
    assert tr.span_count() == 0
    assert tr.begin("x") is None
    tr.end(None)                      # no-op, must not raise
    tr.record("x", 0, 1)
    assert tr.span_count() == 0


def test_ring_wraparound_drops_oldest_keeps_newest():
    tr = Tracer(enabled=True, capacity=8)
    for i in range(50):
        tr.record(f"span{i}", t0_ns=i * 1000, dur_ns=100)
    assert tr.span_count() == 8
    names = [e["name"] for e in tr.export_events() if e["ph"] == "X"]
    assert names == [f"span{i}" for i in range(42, 50)]


def test_export_events_match_chrome_schema():
    tr = Tracer(enabled=True, process_name="learner")
    with tr.trace_span("work", seq=123, args={"lanes": 4}):
        time.sleep(0.001)
    events = tr.export_events()
    doc = chrome_trace(events)
    json.dumps(doc)                       # must serialize
    assert doc["traceEvents"] is events
    metas = [e for e in events if e["ph"] == "M"]
    assert {"process_name", "thread_name"} <= {e["name"] for e in metas}
    assert metas[0]["args"]["name"] == "learner"
    (x,) = [e for e in events if e["ph"] == "X"]
    assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(x)
    assert x["dur"] >= 1000.0             # ~1ms in microseconds
    assert x["args"]["trace_seq"] == 123 and x["args"]["lanes"] == 4


def test_flow_events_stitch_by_seq():
    evs = [
        {"name": "a", "ph": "X", "ts": 1.0, "pid": 1, "tid": 1,
         "args": {"trace_seq": 9}},
        {"name": "b", "ph": "X", "ts": 2.0, "pid": 2, "tid": 5,
         "args": {"trace_seq": 9}},
        {"name": "c", "ph": "X", "ts": 3.0, "pid": 1, "tid": 1,
         "args": {"trace_seq": 9}},
        {"name": "lonely", "ph": "X", "ts": 4.0, "pid": 1, "tid": 1,
         "args": {"trace_seq": 10}},      # < 2 events: no flow
    ]
    flows = flow_events(evs)
    assert [f["ph"] for f in flows] == ["s", "t", "f"]
    assert all(f["id"] == 9 for f in flows)
    assert flows[-1]["bp"] == "e"
    assert flows[1]["pid"] == 2           # the middle hop is the other proc


def test_cross_thread_begin_end_lands_on_ending_thread():
    tr = Tracer(enabled=True)
    token = tr.begin("handoff", seq=7)
    out = {}

    def finisher():
        tr.end(token, args={"done": 1})
        out["tid"] = threading.get_ident()

    t = threading.Thread(target=finisher)
    t.start()
    t.join()
    (x,) = [e for e in tr.export_events() if e["ph"] == "X"]
    assert x["name"] == "handoff" and x["tid"] == out["tid"]
    assert x["args"]["trace_seq"] == 7 and x["args"]["done"] == 1


def test_next_trace_seq_nonzero_u32_and_unique():
    seqs = [next_trace_seq() for _ in range(1000)]
    assert all(0 < s <= 0xFFFFFFFF for s in seqs)
    assert len(set(seqs)) == len(seqs)


# ----------------------------------------------------------------- wire

def test_codec_trace_seq_round_trips_every_frame_kind():
    obs = np.zeros((2, 5), np.float32)
    traj = {"obs": obs, "action": np.zeros(2, np.int32)}
    frames = [
        codec.encode_request(1, 2, obs, trace_seq=0xDEADBEEF),
        codec.encode_reply(2, np.zeros(2, np.int32),
                           trace_seq=0xDEADBEEF),
        codec.encode_trajectory(1, traj, trace_seq=77),
        codec.encode_traj_batch(1, [traj, traj], trace_seq=78),
    ]
    seqs = []
    for wire in frames:
        assert wire[6] == codec.VERSION
        frame = codec.read_frame(io.BytesIO(wire).read)
        seqs.append(frame.trace_seq)
    assert seqs == [0xDEADBEEF, 0xDEADBEEF, 77, 78]
    # default stays 0 = untraced
    plain = codec.read_frame(
        io.BytesIO(codec.encode_request(1, 2, obs)).read)
    assert plain.trace_seq == 0


# -------------------------------------------------------------- sampler

def test_read_process_cpu_s_self():
    cpu = read_process_cpu_s(os.getpid())
    assert cpu is not None and cpu > 0
    # burning CPU must move the reading
    t0 = time.perf_counter()
    x = 0
    while time.perf_counter() - t0 < 0.05:
        x += 1
    assert read_process_cpu_s(os.getpid()) >= cpu


def test_sampler_watch_and_totals():
    reg = MetricsRegistry()
    from repro.telemetry import UtilizationSampler
    s = UtilizationSampler(reg, interval_s=0.01)
    s.watch("learner", os.getpid())
    s.watch("ghost", 2 ** 30)             # nonexistent pid: skipped, no raise
    s.start()
    time.sleep(0.08)
    s.stop()
    assert len(s.ticks) >= 2
    totals = s.cpu_totals()
    assert "learner" in totals and totals["learner"] >= 0.0
    assert "ghost" not in totals
    tick = s.ticks[-1]
    assert "cpu_cores" in tick and "metrics" in tick


def test_attribute_bottleneck_classification():
    r = attribute_bottleneck(elapsed_s=1.0, frames=1000, actor_cpu_s=0.9,
                             inference_compute_s=0.05, learner_train_s=0.01)
    assert r.bottleneck == "actor-bound"
    assert r.cpu_gpu_ratio == pytest.approx(0.9 / 0.06)
    r = attribute_bottleneck(elapsed_s=1.0, frames=1000, actor_cpu_s=0.1,
                             wire_overhead_s=0.8)
    assert r.bottleneck == "wire-bound"
    # the queue shedding most frames overrides the seconds argmax
    r = attribute_bottleneck(elapsed_s=1.0, frames=1000, actor_cpu_s=0.9,
                             learner_train_s=0.01, drop_rate=0.8)
    assert r.bottleneck == "learner-bound"
    assert r.detail["drop_rate"] == 0.8
    idle = attribute_bottleneck(elapsed_s=1.0, frames=0)
    assert idle.bottleneck == "idle" and np.isfinite(idle.cpu_gpu_ratio)
    assert isinstance(r, BottleneckReport)
    assert "actor" in str(r)


# -------------------------------------------------------- system e2e

def test_onpolicy_queue_registers_gauges():
    from repro.onpolicy import TrajectoryQueue
    reg = MetricsRegistry()
    q = TrajectoryQueue(4, metrics=reg)
    q.put({"obs": np.zeros((3, 2), np.float32),
           "actions": np.zeros(3, np.int32),
           "rewards": np.zeros(3, np.float32),
           "dones": np.zeros(3, np.float32)})
    g = reg.snapshot()["gauges"]
    assert g["onpolicy/queue_depth"] == 1
    assert g["onpolicy/frames_pending"] == 3
    assert g["onpolicy/drop_rate"] == 0.0


def test_inproc_system_telemetry_end_to_end(tmp_path):
    tel = Telemetry(process_name="learner", out_dir=str(tmp_path))
    sys_ = SeedSystem(env_factory=CatchEnv, policy_step=det_policy,
                      num_actors=2, unroll=4, envs_per_actor=2,
                      deadline_ms=1.0, telemetry=tel)
    sys_.warmup()
    stats = sys_.run(seconds=0.6, with_learner=False)
    assert stats["env_frames"] > 0
    b = stats["bottleneck"]
    assert np.isfinite(b["cpu_gpu_ratio"])
    assert b["bottleneck"].endswith("-bound")
    # actor rtt spans + replica spans share seqs -> flows exist
    events = tel.trace_events()
    assert any(e["ph"] == "X" and e["name"] == "actor/inference_rtt"
               for e in events)
    assert any(e["ph"] == "s" for e in events)
    rtt = tel.merged_histogram("wire/rtt_s")
    assert rtt and rtt["count"] > 0 and rtt["p50"] is not None
    wait = tel.merged_histogram("inference/batch_wait_s")
    assert wait and wait["p99"] is not None
    paths = tel.dump()
    doc = json.load(open(paths["trace"]))
    assert doc["traceEvents"]
    lines = [json.loads(ln) for ln in open(paths["metrics"])]
    assert lines and "metrics" in lines[0]


def test_telemetry_disabled_adds_no_spans_and_server_accepts_none():
    tel = Telemetry(enabled=False, process_name="learner")
    sys_ = SeedSystem(env_factory=CatchEnv, policy_step=det_policy,
                      num_actors=1, unroll=4, envs_per_actor=2,
                      deadline_ms=1.0, telemetry=tel)
    sys_.warmup()
    stats = sys_.run(seconds=0.3, with_learner=False)
    assert stats["env_frames"] > 0
    assert tel.tracer.span_count() == 0
    # metrics still accumulate (counters are the stats backing store)
    assert tel.metrics.snapshot()["counters"]["inference/r0/batches"] > 0


def test_seed_system_rejects_non_telemetry_object():
    with pytest.raises(TypeError, match="telemetry"):
        SeedSystem(env_factory=CatchEnv, policy_step=det_policy,
                   num_actors=1, unroll=4, telemetry="yes please")


def test_socket_system_cross_process_stitch(tmp_path):
    """The acceptance e2e: one logical round-trip must appear in >= 2
    distinct processes (actor host + learner-side gateway/replica),
    joined by the wire-carried trace_seq."""
    tel = Telemetry(process_name="learner", out_dir=str(tmp_path))
    sys_ = SeedSystem(env_factory=CatchEnv, policy_step=det_policy,
                      num_actors=2, unroll=4, envs_per_actor=2,
                      deadline_ms=2.0, transport="socket",
                      num_actor_hosts=2, telemetry=tel)
    stats = sys_.run(seconds=2.0, with_learner=False)
    assert not stats["host_errors"]
    assert stats["env_frames"] > 0
    pids_by_seq = {}
    for e in tel.trace_events():
        if e.get("ph") == "X":
            seq = (e.get("args") or {}).get("trace_seq")
            if seq:
                pids_by_seq.setdefault(seq, set()).add(e["pid"])
    stitched = [s for s, pids in pids_by_seq.items() if len(pids) >= 2]
    assert stitched, f"no cross-process stitch in {len(pids_by_seq)} seqs"
    # host CPU was sampled from /proc -> the ratio is measured, not 0
    totals = tel.sampler.cpu_totals()
    assert any(k.startswith("actor-host") for k in totals)
    rep = tel.bottleneck_report(stats)
    assert np.isfinite(rep.cpu_gpu_ratio) and rep.frames > 0
    doc = json.load(open(tel.dump()["trace"]))
    pids = {e["pid"] for e in doc["traceEvents"]}
    assert len(pids) >= 2


# ------------------------------------------- spans at the layer boundaries

def _spans(tracer):
    """{name: [(start_ns, end_ns, tid)]} of the ring's complete events."""
    out = {}
    for e in tracer.export_events():
        if e["ph"] == "X":
            t0 = e["ts"] * 1e3
            out.setdefault(e["name"], []).append(
                (t0, t0 + e["dur"] * 1e3, e["tid"]))
    return out


def _inside(child, parent):
    return child[2] == parent[2] and parent[0] <= child[0] \
        and child[1] <= parent[1]


def test_enabled_span_reads_back_from_the_profilers_host_plane(tmp_path):
    import glob

    import jax

    tr = Tracer(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.trace_span("learner/step"):
            with tr.trace_span("learner/train"):
                jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    found = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("learner/"):
                        found[e.name] = (e.start_ns,
                                         e.start_ns + e.duration_ns)
    assert set(found) == {"learner/step", "learner/train"}
    step, train = found["learner/step"], found["learner/train"]
    assert step[0] <= train[0] and train[1] <= step[1]
    # the ring keeps its record as before
    assert set(_spans(tr)) == {"learner/step", "learner/train"}


def test_disabled_tracer_records_nothing_and_returns_the_null_span(
        monkeypatch):
    from repro.telemetry import tracer as tracer_mod

    def no_jax(name):
        raise AssertionError("the disabled path called the profiler")

    monkeypatch.setattr(tracer_mod, "TraceAnnotation", no_jax)
    tr = Tracer(enabled=False)
    assert tr.trace_span("learner/step") is tracer_mod._NULL_SPAN
    assert tracer_mod.maybe_span(None, "x") is tracer_mod._NULL_SPAN
    with tr.trace_span("learner/step"), tracer_mod.maybe_span(None, "x"):
        pass
    assert tr.span_count() == 0


class _Batches:
    def __init__(self, n=4):
        self.n = n

    def __call__(self):
        return ({"obs": np.ones((self.n, 3), np.float32),
                 "ids": np.arange(self.n, dtype=np.int64)},
                np.arange(self.n))


def _fake_train_step(seen):
    import jax

    @jax.jit
    def step(state, batch):
        loss = batch["obs"].sum() + batch["ids"].sum()
        return ({"step": state["step"] + 1,
                 "params": state["params"] + loss},
                {"loss": loss, "priorities": batch["obs"][:, 0]})

    def train_step(state, batch):
        seen.append(batch)
        return step(state, batch)
    return train_step


def test_learner_fills_the_post_counter():
    from repro.core.learner import Learner

    seen, published, prios = [], [], []

    def publish(params, step):
        time.sleep(0.01)
        published.append(step)

    ln = Learner(_fake_train_step(seen),
                 {"step": np.zeros((), np.int32), "params": np.zeros(())},
                 _Batches(), publish=publish,
                 priority_update=lambda idx, p: prios.append((idx, p)))
    t0 = time.perf_counter()
    ln.run_steps(3)
    wall = time.perf_counter() - t0
    assert ln.steps == 3 and published == [1, 2, 3] and len(prios) == 3
    assert ln.post_time_s >= 0.03              # three 10 ms publishes
    # the three counters cover the iterations, their release included
    counted = ln.wait_time_s + ln.train_time_s + ln.post_time_s
    assert 0 <= wall - counted < 0.01
    assert ln.wait_time_s > 0 and ln.train_time_s > 0
    # the step's scalars stay on the device until something reads them
    assert ln.metrics_pulls == 0
    assert ln.metrics["loss"] == pytest.approx(12.0 + 6.0)
    assert ln.metrics_pulls == 1
    # the step gets the batch as the batch source made it: its transfer
    # is the jitted call's, inside the train interval
    assert all(isinstance(v, np.ndarray) for v in seen[0].values())


def test_learner_spans_nest_under_the_step():
    from repro.core.learner import Learner

    tel = Telemetry(process_name="learner")
    ln = Learner(_fake_train_step([]),
                 {"step": np.zeros((), np.int32), "params": np.zeros(())},
                 _Batches(), publish=lambda p, s: None,
                 priority_update=lambda idx, p: None, telemetry=tel)
    ln.run_steps(2)
    spans = _spans(tel.tracer)
    children = ("learner/batch", "learner/train", "learner/post")
    post_children = ("learner/priority_update", "learner/publish")
    assert set(spans) == {"learner/step", *children, *post_children}
    assert len(spans["learner/step"]) == 2
    for i, step in enumerate(spans["learner/step"]):
        for name in children:
            assert _inside(spans[name][i], step), name
        for name in post_children:
            assert _inside(spans[name][i], spans["learner/post"][i]), name


def test_learner_metrics_pull_once_per_step_at_the_reader():
    from repro.core.learner import Learner

    tel = Telemetry(process_name="learner")
    ln = Learner(_fake_train_step([]),
                 {"step": np.zeros((), np.int32), "params": np.zeros(())},
                 _Batches(), telemetry=tel)
    assert ln.metrics == {} and ln.metrics_pulls == 0
    ln.run_steps(1)
    first = ln.metrics
    assert ln.metrics == first == {"loss": pytest.approx(18.0)}
    assert ln.metrics_pulls == 1
    assert len(_spans(tel.tracer)["learner/metrics_pull"]) == 1
    ln.run_steps(1)
    assert ln.metrics_pulls == 1              # a step alone pulls nothing
    assert ln.metrics["loss"] == pytest.approx(18.0)
    assert ln.metrics_pulls == 2
    assert len(_spans(tel.tracer)["learner/metrics_pull"]) == 2


def test_learner_steps_make_no_device_to_host_transfer(monkeypatch):
    import jax

    from repro.core.learner import Learner

    ln = Learner(_fake_train_step([]),
                 {"step": np.zeros((), np.int32), "params": np.zeros(())},
                 _Batches(), publish=lambda p, s: None)
    ln.run_steps(1)                             # compile outside the count
    # the transfer guard does not fire on the CPU backend, so count the
    # host calls that would move a device array
    pulls = []

    def counting(fn):
        def wrapped(x, *a, **k):
            if any(isinstance(leaf, jax.Array)
                   for leaf in jax.tree_util.tree_leaves(x)):
                pulls.append(fn.__name__)
            return fn(x, *a, **k)
        return wrapped

    for mod, name in ((np, "asarray"), (np, "array"), (jax, "device_get")):
        monkeypatch.setattr(mod, name, counting(getattr(mod, name)))
    with jax.transfer_guard_device_to_host("disallow"):
        ln.run_steps(3)
    assert ln.steps == 4 and pulls == []
    assert ln.metrics_pulls == 0
    assert ln.metrics["loss"] == pytest.approx(18.0)
    assert pulls == ["device_get"]


def test_replay_counts_samples_adds_and_the_adds_lock_wait():
    from repro.core.replay import PrioritizedReplay

    buf = PrioritizedReplay(capacity=8, seed=0)
    seq = {"obs": np.zeros((4, 2), np.float32)}
    for _ in range(3):
        buf.add(seq, priority=1.0)
    for _ in range(2):
        buf.sample(2)
    assert (buf.adds, buf.samples) == (3, 2)
    assert buf.sample_time_s > 0
    before = buf.add_wait_s
    held = threading.Event()

    def hold():
        with buf._lock:
            held.set()
            time.sleep(0.05)

    t = threading.Thread(target=hold)
    t.start()
    held.wait()
    buf.add(seq, priority=1.0)
    t.join()
    assert buf.adds == 4
    assert buf.add_wait_s - before >= 0.04


def test_replay_counts_every_add_under_concurrent_writers():
    import sys

    from repro.core.replay import PrioritizedReplay

    buf = PrioritizedReplay(capacity=16, seed=0)
    seq = {"obs": np.zeros(2, np.float32)}
    threads = [threading.Thread(
        target=lambda: [buf.add(seq, 1.0) for _ in range(200)])
        for _ in range(8)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert buf.adds == 8 * 200 and len(buf) == 16
    assert buf.add_wait_s >= 0


def test_replay_samples_whole_rows_under_concurrent_writers():
    """Every row a sample returns is one add's: all its keys carry the
    same id, while eight writers overwrite the ring."""
    import sys

    from repro.core.replay import PrioritizedReplay

    buf = PrioritizedReplay(capacity=16, seed=0)

    def seq(i):
        return {"obs": np.full((4, 3), i, np.float32),
                "actions": np.full((4,), i, np.int32),
                "rewards": np.full((2,), i, np.float32)}

    for i in range(16):
        buf.add(seq(i), 1.0)
    stop = threading.Event()
    torn = []

    def writer(w):
        for j in range(200):
            buf.add(seq(1000 * (w + 1) + j), 1.0)

    def sampler():
        while not stop.is_set():
            batch, _, _ = buf.sample(8)
            rows = np.concatenate([np.asarray(v).reshape(8, -1)
                                   for v in batch.values()], axis=1)
            torn.extend(r for r in rows if (r != r[0]).any())

    threads = [threading.Thread(target=writer, args=(w,)) for w in range(8)]
    reader = threading.Thread(target=sampler)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        reader.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
    finally:
        stop.set()
        reader.join(timeout=60.0)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads + [reader])
    assert buf.adds == 16 + 8 * 200 and buf.samples > 0
    assert not torn, torn[:3]
    assert buf.add_time_s >= buf.add_wait_s


def test_replay_spans():
    from repro.core.replay import PrioritizedReplay

    tr = Tracer(enabled=True)
    buf = PrioritizedReplay(capacity=4, seed=0, tracer=tr)
    buf.add({"obs": np.zeros(3, np.float32)}, priority=1.0)
    buf.sample(2)
    spans = _spans(tr)
    assert set(spans) == {"replay/add", "replay/sample", "replay/lock_wait",
                          "replay/gather"}
    add, sample = spans["replay/add"][0], spans["replay/sample"][0]
    waits = spans["replay/lock_wait"]
    assert _inside(waits[0], add) and _inside(waits[1], sample)
    assert _inside(spans["replay/gather"][0], sample)


def test_vtrace_batcher_spans():
    from repro.onpolicy import TrajectoryQueue, VTraceBatcher

    tr = Tracer(enabled=True)
    q = TrajectoryQueue(8)
    for _ in range(2):
        q.put({"obs": np.zeros((3, 2), np.float32),
               "actions": np.zeros(3, np.int32),
               "rewards": np.zeros(3, np.float32),
               "dones": np.zeros(3, np.float32),
               "behavior_logprobs": np.zeros(3, np.float32)})
    batch, _ = VTraceBatcher(q, batch_size=2, tracer=tr)()
    assert batch["obs"].shape == (2, 3, 2)
    assert set(_spans(tr)) == {"onpolicy/pop_batch", "onpolicy/assemble"}


def test_sampling_policy_spans():
    import jax.numpy as jnp

    from repro.onpolicy import SamplingPolicy

    def apply_fn(params, core, inputs):
        obs = inputs["obs"]
        return obs @ params, jnp.zeros(obs.shape[:-1]), core

    pol = SamplingPolicy(apply_fn, np.ones((50, 3), np.float32))
    assert pol.tracer is None
    # the system hands its tracer to the policy step it was given
    tel = Telemetry(process_name="learner")
    SeedSystem(env_factory=CatchEnv, policy_step=pol, num_actors=1,
               unroll=4, envs_per_actor=2, algo="vtrace",
               policy_publish=pol.publish, telemetry=tel)
    assert pol.tracer is tel.tracer
    out = pol(np.ones((4, 50), np.float32), None)
    assert out.shape == (4, 2)
    assert set(_spans(tel.tracer)) == {"policy/key", "policy/dispatch",
                                       "policy/fetch"}


def test_rollout_worker_counts_flush_time_and_traces_each_scan():
    import jax

    from repro.rollout import DeviceRolloutEngine, RolloutWorker

    def policy_apply(params, core, inputs, key):
        return jax.random.randint(key, inputs.obs.shape[:1], 0, 3), core

    tr = Tracer(enabled=True)
    eng = DeviceRolloutEngine(CatchEnv, policy_apply, 2, 4, seed=0)
    sunk = []
    w = RolloutWorker(0, eng, sunk.append, lambda: (None, 0), tracer=tr)
    w.start()
    deadline = time.time() + 10.0
    while w.iterations < 2 and time.time() < deadline:
        time.sleep(0.01)
    w.stop()
    w.join()
    assert w.error is None, w.error
    assert w.flush_time_s > 0
    assert w.scan_time_s > 0
    # obs (T, E, 50) f32, actions i32, rewards f32 and dones bool per scan
    assert w.fetch_bytes == w.iterations * 4 * 2 * (50 * 4 + 4 + 4 + 1)
    spans = _spans(tr)
    assert set(spans) == {"rollout/scan", "rollout/dispatch",
                          "rollout/fetch", "rollout/flush"}
    assert len(spans["rollout/flush"]) >= 2


def test_r2d2_system_traces_every_layer_it_runs():
    import functools

    from repro.configs.r2d2_atari import AtariConfig
    from repro.core.r2d2_agent import build_r2d2_system
    from repro.envs.alesim import ALESimEnv

    acfg = AtariConfig(obs_size=36, obs_channels=1, core_dim=16,
                       num_actions=4, burn_in=2, unroll=6, n_step=2)
    tel = Telemetry(process_name="learner")
    sys_ = build_r2d2_system(
        acfg, functools.partial(ALESimEnv, frame=36, channels=1),
        num_actors=2, envs_per_actor=2, learner_batch=2, replay_capacity=8,
        min_replay=2, telemetry=tel)
    stats = sys_.run(seconds=2.0)
    assert stats["learner_error"] is None, stats["learner_error"]
    assert stats["learner_steps"] >= 1
    names = set(_spans(tel.tracer))
    assert {"learner/step", "learner/input", "learner/train",
            "learner/priority_update", "replay/add", "replay/sample",
            "replay/gather", "inference/forward", "policy/slot_gather",
            "policy/dispatch", "policy/fetch", "policy/slot_scatter",
            "actor/env_step"} <= names
    replay, ln = sys_.replay, sys_.learner
    assert replay.samples >= ln.steps and replay.adds > 0
    assert ln.post_time_s > 0
    # the counters reach /varz (throughput) and the /metrics collector
    timings = stats["timings"]
    assert timings["replay_samples"] == replay.samples
    assert timings["replay_adds"] == replay.adds
    assert timings["learner_post_s"] == ln.post_time_s
    assert timings["learner_metrics_pulls"] == ln.metrics_pulls
    assert timings["rollout_flush_s"] == 0.0
    assert timings["rollout_scan_s"] == timings["rollout_fetch_bytes"] == 0
    assert timings["replay_add_s"] == replay.add_time_s >= replay.add_wait_s
    gauges = sys_._ops_ledger_gauges()
    assert gauges["timings/replay_add_wait_s"] == replay.add_wait_s
    assert gauges["timings/replay_add_s"] == replay.add_time_s
