"""Mean wait of a lane from enqueue to the start of its batch's forward,
from the inference server's lane-weighted counters."""

def read(w):
    lanes = w.d("infer_lanes")
    if not lanes:
        return None
    return 1e3 * w.d("infer_queue_wait_s") / lanes
