"""One run of one cell: build it, fill it, open the window once every layer
is in steady state, read the counters at the window's two edges, check
what the timed path produced against the plain reference, and print the
result line.

Everything that belongs to one cell, configuration or metric sits in a
file of its own, found by name:

* ``BENCHMARK.json`` names the cell's configuration and its metrics;
* ``bench/configs/<config>.json`` holds the configuration as it is run;
* ``bench/workloads/<cell>.json`` holds the traffic, the layout that
  builds the system (``bench/cells/<layout>.py``) and the limits of the
  numbers that decide `correct`;
* ``bench/metrics/<metric>.py`` reads one metric from the window: its
  ``read(w)`` returns a number, or None where it finds nothing to read.
"""

import contextlib
import importlib
import importlib.util
import json
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

import devtrace
import flops

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench"
POLL_S = 0.01
TRACE_S = 4.0              # the traced part of a --trace 1 window
TAIL_S = 1.0               # the run outlasts the window by this much


class SetupError(RuntimeError):
    """The run cannot measure: no result is printed, and the process exits
    with ``code`` (2: no chip, or too few; 3: the cell never reached its
    window)."""

    def __init__(self, message, code=3):
        super().__init__(message)
        self.code = code


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell's entry in BENCHMARK.json with its configuration, its
    traffic and the names of its end-to-end and per-layer metrics."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json")
    config = next(c for c in spec["configs"] if c["name"] == entry["config"])

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "entry": entry,
        "config": json.loads((root / config["file"]).read_text()),
        "traffic": json.loads(
            (root / "bench" / "workloads" / f"{name}.json").read_text()),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
    }


@dataclass
class Window:
    """What a metric reader sees: the counters at the window's edges, the
    actor samples and the reduced trace."""
    seconds: float
    setup_s: float
    c0: dict
    c1: dict
    chips: int
    peak: dict
    actor: dict = field(default_factory=dict)
    trace: dict = None

    def d(self, key):
        """The counter's growth over the window, or None."""
        if key not in self.c0 or key not in self.c1:
            return None
        return self.c1[key] - self.c0[key]


def actor_window(lanes, t0, t1, lanes_per_actor):
    """Iteration gaps and env time of the steps that started in [t0, t1],
    from per-lane [start, end] samples."""
    gaps, env_s, steps = [], 0.0, 0
    for a in lanes:
        if not len(a):
            continue
        inside = a[(a[:, 0] >= t0) & (a[:, 0] <= t1)]
        steps += len(inside)
        env_s += float(np.sum(inside[:, 1] - inside[:, 0]))
        if len(inside) > 1:
            gaps.append(np.diff(inside[:, 0]))
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"gaps_s": gaps, "steps": steps, "env_s": env_s,
            "iterations": steps / lanes_per_actor}


class RunClock:
    """Stands in for the `time` module of `repro.core.system`, so that the
    sleep with which `SeedSystem.run` waits out its duration ends when the
    benchmark's window closes: the run is started long enough to cover any
    fill, and stopped as soon as the window is read. Short sleeps (polls)
    and every other function are the real ones."""

    def __init__(self, real):
        self._real = real
        self.release = threading.Event()

    def sleep(self, seconds):
        if seconds < 0.5:
            self._real.sleep(seconds)
        else:
            self.release.wait(seconds)

    def __getattr__(self, name):
        return getattr(self._real, name)


def install_run_clock() -> RunClock:
    import repro.core.system as system

    clock = RunClock(time)
    system.time = clock
    return clock


class CompileCounter:
    """Counts programs lowered (compiled, or loaded from the persistent
    cache) after `arm()`."""
    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if self.armed and name == self.EVENT:
            self.count += 1

    def arm(self, on: bool):
        self.armed = on


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def run(args, t_start: float, *, cell=None, require_chip: bool = True,
        fault=None, controls=False) -> dict:
    """One run; returns the result dict (the last line's object) with the
    checks under ``checks``. Tests pass ``cell`` (from `load_cell`, cut
    down), ``require_chip=False`` and a ``fault`` to plant in the timed
    path; `bench/control.py` asks for the ``controls`` readings, which
    come back under ``_readings``."""
    cell = cell or load_cell(args.workload)
    chips = cell["entry"]["chips"]
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise SetupError(f"no TPU: JAX found platform "
                         f"{devices[0].platform!r} ({len(devices)} devices)",
                         code=2)
    if len(devices) < chips:
        raise SetupError(f"{args.workload} needs {chips} chips, JAX found "
                         f"{len(devices)}", code=2)
    used = devices[:chips]
    kind = used[0].device_kind
    peak = flops.peak(kind) if require_chip else {"bf16_flops_per_s": 1.0}
    compiles = CompileCounter()

    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir(parents=True, exist_ok=True)
    annotate = None
    if args.trace:
        annotate = jax.profiler.TraceAnnotation
    layout = importlib.import_module(f"cells.{cell['traffic']['layout']}")
    c = layout.Cell(cell["config"], cell["traffic"], seed=args.seed,
                     out_dir=str(OUT), annotate=annotate, fault=fault)
    allowance = float(cell["traffic"]["fill_allowance_s"])
    clock = install_run_clock()
    c.start(allowance + args.seconds + TAIL_S)
    deadline = time.perf_counter() + allowance
    while not c.ready():
        err = c.error()
        if err or time.perf_counter() > deadline:
            clock.release.set()
            c.join()
            raise SetupError(err or f"not in steady state after "
                             f"{allowance} s: {c.progress()}")
        time.sleep(POLL_S)

    t_open = time.perf_counter()
    compiles.arm(True)
    c0 = c.counters()
    trace = None
    if args.trace:
        trace_s = min(TRACE_S, args.seconds / 2)
        lead = (args.seconds - trace_s) / 2
        time.sleep(lead)
        log_dir = str(OUT / "trace")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench/trace_window"):
            time.sleep(trace_s)
        jax.profiler.stop_trace()
        time.sleep(max(t_open + args.seconds - time.perf_counter(), 0.0))
    else:
        time.sleep(args.seconds)
    c1 = c.counters()
    t_close = c1["t"]
    compiles.arm(False)
    mem = peak_bytes(used)
    clock.release.set()
    stats = c.join()
    errors = c.errors(stats)
    if compiles.count:
        errors.append(f"{compiles.count} programs lowered inside the window")
    if args.trace:
        trace = devtrace.reduce(devtrace.extract(log_dir),
                                list(range(chips)))

    lanes = c.actor_samples()
    actor = (actor_window(lanes, t_open, t_close, c.lanes_per_actor)
             if lanes is not None else {})
    w = Window(seconds=t_close - t_open, setup_s=t_open - t_start, c0=c0,
               c1=c1, chips=chips, peak=peak, actor=actor, trace=trace)
    metric_defs = cell["per_layer"] if args.trace else cell["end_to_end"]
    metrics = {}
    for m in metric_defs:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name']}")
        v = reader.read(w)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    readings = c.check(controls)        # frees the program's state first
    limits = cell["traffic"]["limits"]
    checks = {k: v for k, v in readings["program"].items() if k in limits}
    errors += [f"no reading of {k}" for k in limits if k not in checks]
    shutil.rmtree(OUT, ignore_errors=True)
    correct = not errors and bool(checks) and all(
        v <= limits[k] for k, (v, _) in checks.items())
    result = {
        "correct": correct,
        "attempted": int(w.d("learner_steps")),
        "failed": len(errors),
        "metrics": metrics,
        "device": {"platform": used[0].platform, "kind": kind,
                   "count": len(devices), "memory_peak_bytes": mem},
    }
    if trace is not None:
        result["device"]["busy_s"] = trace["busy_s"]
        result["device"]["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": limits[k], "at": at}
                        for k, (v, at) in checks.items()}
    result["_errors"] = errors
    result["_readings"] = {k: v for k, v in readings.items()
                           if k != "reference"}
    result["_compiles_in_window"] = compiles.count
    result["_actor_samples"] = len(actor.get("gaps_s", []))
    return result


def report(result: dict, out=sys.stdout, err=sys.stderr):
    """Print the notes, then the checks as the last lines of standard
    error, then the result as the last line of standard output."""
    result.pop("_readings")
    for e in result.pop("_errors"):
        print(f"error: {e}", file=err)
    print(f"compiles in window: {result.pop('_compiles_in_window')}; "
          f"actor iterations sampled: {result.pop('_actor_samples')}",
          file=err)
    for k, v in result["checks"].items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r}, "
              f"worst at {v['at']})", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)


@contextlib.contextmanager
def annotated(annotate, name):
    if annotate is None:
        yield
    else:
        with annotate(name):
            yield


def wrap(fn, annotate, name):
    """``fn`` inside a host span of the profiler's trace, when tracing."""
    if annotate is None:
        return fn

    def wrapped(*a, **kw):
        with annotate(name):
            return fn(*a, **kw)
    return wrapped


def run_in_thread(target):
    """Start ``target`` in a thread; returns (thread, box) where box gets
    'result' or 'error'."""
    box = {}

    def body():
        try:
            box["result"] = target()
        except BaseException as e:     # surfaced by the caller
            box["error"] = repr(e)
    t = threading.Thread(target=body, name="bench-system-run", daemon=True)
    t.start()
    return t, box


def host_array(tree):
    import jax
    return jax.tree.map(lambda x: np.asarray(x), tree)
