"""Share of the frames generated in the window that the on-policy queue
dropped (stale, evicted on overflow), from the frame ledger."""

def read(w):
    generated = w.d("frames_generated")
    if not generated:
        return None
    return 100.0 * w.d("frames_dropped") / generated
