"""TelemetrySink: write the run's observables to disk.

Two artifacts per run directory:

- ``trace.json`` — Chrome trace-event JSON (load in Perfetto or
  chrome://tracing): every span from every traced process, plus flow
  arrows stitching each wire round-trip across process tracks.
- ``metrics.jsonl`` — one JSON object per sampler tick: schema version,
  monotonic tick index, wall-clock ts, per-process cpu cores, and a full
  registry snapshot (counters, gauges, histograms with p50/p95/p99).

Both artifacts are written ATOMICALLY: content goes to a same-directory
temp file first, then `os.replace` publishes it — a crash mid-dump (the
flight recorder triggering while a dump is in flight, a SIGKILL'd CI
job) can never leave a truncated trace.json that Perfetto rejects or a
half-line in metrics.jsonl. Readers either see the previous complete
artifact or the new complete one.
"""

import json
import os
from typing import Callable, Dict, List, Optional

__all__ = ["TelemetrySink", "METRICS_SCHEMA_VERSION"]

# bump when the shape of a metrics.jsonl line changes; consumers key
# their parsing on the per-line "schema" stamp
METRICS_SCHEMA_VERSION = 1


def _atomic_write(path: str, write_fn: Callable) -> None:
    """Write via temp file + `os.replace` (atomic on POSIX within one
    filesystem — the temp lives next to the target to guarantee that)."""
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            write_fn(f)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):          # only on a failed write
            try:
                os.remove(tmp)
            except OSError:
                pass


class TelemetrySink:
    def __init__(self, out_dir: str = "."):
        self.out_dir = out_dir

    def dump(self, trace_events: List[dict], metric_lines: List[dict],
             out_dir: Optional[str] = None) -> Dict[str, str]:
        out = out_dir or self.out_dir
        os.makedirs(out, exist_ok=True)
        trace_path = os.path.join(out, "trace.json")
        _atomic_write(trace_path, lambda f: json.dump(
            {"traceEvents": trace_events, "displayTimeUnit": "ms"}, f))
        metrics_path = os.path.join(out, "metrics.jsonl")

        def _write_lines(f):
            for i, line in enumerate(metric_lines):
                stamped = {"schema": METRICS_SCHEMA_VERSION, "tick": i}
                stamped.update(line)
                f.write(json.dumps(stamped) + "\n")

        _atomic_write(metrics_path, _write_lines)
        return {"trace": trace_path, "metrics": metrics_path}
