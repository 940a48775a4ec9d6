"""Bytes the rollout workers brought to the host per frame the fused scans
generated: frames, actions, rewards, dones and behaviour logprobs, and per
unroll the recorded core and step 0's inputs."""

def read(w):
    frames = w.d("rollout_frames")
    nbytes = w.d("traj_bytes")
    if not frames or nbytes is None:
        return None
    return nbytes / frames
