"""Share of the traced part of the window in which no operation ran on
the device: one less the union of the operation intervals of the profiler
trace over the traced window. On several chips, chip 0, where the learner
runs."""

def read(w):
    return None if w.trace is None else w.trace["idle_pct"]
