"""Frames the fused device scans generated in the window, per second."""

def read(w):
    frames = w.d("rollout_frames")
    return None if not frames else frames / w.seconds
