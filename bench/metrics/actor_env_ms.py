"""Mean env time of one actor iteration in the window (the env steps of
all the actor's lanes), from the benchmark's env wrapper."""

def read(w):
    if not w.actor or not w.actor["iterations"]:
        return None
    return 1e3 * w.actor["env_s"] / w.actor["iterations"]
