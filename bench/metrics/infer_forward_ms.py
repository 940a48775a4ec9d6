"""Host time of one batched policy step of the inference server, from its
counters: compute seconds over batches. It holds the slot gather and
scatter and the sync back to the host."""

def read(w):
    batches = w.d("infer_batches")
    if not batches:
        return None
    return 1e3 * w.d("infer_compute_s") / batches
