"""95th percentile, over every actor iteration that started in the window,
of the iteration's wall time: the gap between successive step() starts of
one env lane (the env steps of all the actor's lanes plus the inference
round trip), from the benchmark's env wrapper."""

import numpy as np


def read(w):
    gaps = w.actor.get("gaps_s") if w.actor else None
    if gaps is None or len(gaps) < 20:
        return None
    return 1e3 * float(np.percentile(gaps, 95))
