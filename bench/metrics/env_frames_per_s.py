"""Env frames the host actors stepped in the window, per second: the
paper's FPS. Only cells with host actors have it."""

def read(w):
    frames = w.d("env_frames")
    if frames is None and w.actor:
        frames = w.actor["steps"]
    return None if not frames else frames / w.seconds
