"""Device-resident rollouts: the third design point on the paper's axis.

The paper's CPU/GPU-ratio analysis says env interaction on host CPUs is
the performance and power limiter of distributed RL; this package is the
end state of moving it off the host. Three design points coexist in this
repo, all behind `SeedSystem`:

  1. **per-step host** (`backend="host"`, E=1): one env step per inference
     round-trip — the SEED baseline. Cost per frame: t_env (CPU) + t_inf
     (round-trip). Throughput saturates at H/t_env host threads.
  2. **vectorized host** (`backend="host"`, E>1): each actor steps E lanes
     (`SyncVectorEnv` / `JaxVectorEnv`) per round-trip, amortizing t_inf
     and the Python dispatch over E — CuLE-style batching, PR 1.
  3. **device-resident** (`backend="device"`): `DeviceRolloutEngine` fuses
     env step and policy forward into one jitted `lax.scan` over T x E, so
     the host round-trip disappears entirely — ONE transfer per unroll
     (the trajectory), not one per step. The bound is scan throughput on
     the accelerator, not host threads (CuLE / Isaac Gym end state;
     `provisioning.SystemModel.with_device` models it).
  4. **engine-sharded device** (`backend="device"`, `engine_shards=K`):
     `ShardedRolloutEngine` partitions the lanes into K
     `DeviceRolloutEngine`s placed round-robin over `jax.devices()` with
     `jax.device_put` — when one scan saturates a device, K scans run
     data-parallel across devices (one per engine carry). CPU-only hosts
     fall back to K serial scans on the single device.

`RolloutWorker` threads drive repeated scans, refresh params from the
learner between scans (with an on-policy lag counter), and feed the same
replay sink as the host actors.
"""

from repro.rollout.engine import (DeviceRolloutEngine,  # noqa: F401
                                  ShardedRolloutEngine, StepInputs,
                                  action_key, as_jax_env)
from repro.rollout.worker import RolloutWorker  # noqa: F401
