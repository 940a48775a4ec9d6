"""Model FLOPs of the window (learner steps x FLOPs per step, plus the
policy forwards of inference or of the scans, from the configuration's
shapes in bench/flops.py) over window x chips x the chip's bf16 peak."""

def read(w):
    flops = w.d("model_flops")
    if not flops:
        return None
    return 100.0 * flops / (w.seconds * w.chips * w.peak["bf16_flops_per_s"])
