"""Learner step time: the host clock around a train step that ends in
block_until_ready, from the learner's counters."""

def read(w):
    steps = w.d("learner_steps")
    if not steps:
        return None
    return 1e3 * w.d("learner_train_s") / steps
