"""Frames that entered a learner update in the window, per second. R2D2:
learner steps x batch x (burn-in + unroll), a replayed frame counting each
time it is sampled. V-trace: the frame ledger's frames_trained."""

def read(w):
    frames = w.d("frames_trained")
    return None if frames is None else frames / w.seconds
