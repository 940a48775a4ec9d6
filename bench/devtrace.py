"""From a JAX profiler trace to the device's busy time, its idle share, the
heaviest device operations and the longest idle gaps.

`extract` reads the ``.xplane.pb`` that `jax.profiler` wrote and keeps a
small neutral form: the operation events of each TPU's ``XLA Ops`` line
and the benchmark's own host spans (``bench/...``, from
`jax.profiler.TraceAnnotation`). `reduce` works on that form only, so a
recorded trace checks it without a chip (``bench/testdata``).

Busy is the union of the intervals in which an operation ran on the
device, clipped to the traced window; the idle share is one less busy over
the window. An idle gap is named after the benchmark span that covers most
of it on the host, or ``host:other`` where none does.
"""

import glob
import os
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/trace_window"
TOP = 10


def extract(log_dir: str) -> dict:
    import jax

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            chip = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[chip] = [[op_name(e.name), e.start_ns,
                                      e.duration_ns] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        host.append([e.name, e.start_ns, e.duration_ns])
    return {"devices": {str(k): v for k, v in devices.items()},
            "host": host}


_OP = re.compile(r"^(%?[\w.-]+) = .*? ([a-z][\w-]*)\(")


def op_name(hlo: str) -> str:
    """'%fusion.12 fusion' from the event's HLO text."""
    m = _OP.match(hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:80]


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _label(gap, spans):
    """The host span with the largest overlap of ``gap``."""
    best, best_overlap = "host:other", 0.0
    for name, s, e in spans:
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def reduce(trace: dict, chips, window_ns=None) -> dict:
    """Busy and idle of ``chips`` over the window, with the breakdown.

    ``window_ns`` is (start, end) on the trace's clock; by default it is
    the host span ``bench/trace_window``, or where there is none, from the
    first to the last event of any kind. Busy seconds are the
    mean over ``chips``; operations and gaps are those of the first chip.
    A chip with no operation events reads as not traced: ``None``.
    """
    ops = {c: trace["devices"].get(str(c)) for c in chips}
    if any(not v for v in ops.values()):
        return None
    marks = [h for h in trace["host"] if h[0] == WINDOW_SPAN]
    if window_ns is None and marks:
        window_ns = (marks[0][1], marks[0][1] + marks[0][2])
    if window_ns is None:
        starts = [e[1] for v in ops.values() for e in v]
        ends = [e[1] + e[2] for v in ops.values() for e in v]
        starts += [h[1] for h in trace["host"]]
        ends += [h[1] + h[2] for h in trace["host"]]
        window_ns = (min(starts), max(ends))
    w0, w1 = window_ns
    window_s = (w1 - w0) * 1e-9
    busy = {}
    merged0 = None
    for c, events in ops.items():
        clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in events
                   if s + d > w0 and s < w1]
        merged = _union(clipped)
        busy[c] = sum(e - s for s, e in merged) * 1e-9
        if merged0 is None:
            merged0 = merged
    busy_s = sum(busy.values()) / len(busy)

    per_op = defaultdict(float)
    for name, s, d in ops[chips[0]]:
        if s + d > w0 and s < w1:
            per_op[name] += (min(s + d, w1) - max(s, w0)) * 1e-9
    device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]

    spans = [(n, s, s + d) for n, s, d in trace["host"] if n != WINDOW_SPAN]
    edges = [w0] + [x for iv in merged0 for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle_gaps = [[_label(g, spans), (g[1] - g[0]) * 1e-9]
                 for g in gaps[:TOP]]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_pct": 100.0 * (1.0 - busy_s / window_s),
            "device_ops": [[n, s] for n, s in device_ops],
            "idle_gaps": idle_gaps}
