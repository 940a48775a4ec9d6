"""Learner wait per step: the replay sample or queue pop and the batch
assembly, from the learner's counters."""

def read(w):
    steps = w.d("learner_steps")
    if not steps:
        return None
    return 1e3 * w.d("learner_wait_s") / steps
