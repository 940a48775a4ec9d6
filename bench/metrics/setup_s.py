"""Seconds from process start to the window's opening: imports, weights,
compile or cache load, and the fill (replay to min_replay, or the queue's
first batches) with the learner's first steps."""

def read(w):
    return w.setup_s
