"""V-trace batch assembly: per-lane unrolls -> (B, T) learner batches.

The three ingress routes — host `Actor` sinks, device `RolloutWorker`
scans, and wire ``TRAJ`` frames — all emit the same per-lane unroll schema
(`core.actor.flush_lane_unrolls`): 1-D time arrays per field, plus the
on-policy extras ``behavior_logprobs`` (stamped per step by the sampling
policy) and ``param_version`` (stamped per unroll by the generator). The
batcher stacks B of them into the exact field set `core.vtrace` consumes:
obs, actions, rewards, discounts (= gamma * (1 - done), 0 at terminals),
and behavior_logprobs, all (B, T) with time as the second axis.

Unrolls of a recurrent device policy also carry the core each lane held
before step 0 and that step's inputs (``core``, ``prev_action``,
``prev_reward``, ``first``; see `rollout.engine`). Their batch gains
``core`` (B, ...) and every step's ``prev_action``, ``prev_reward`` and
``first`` (B, T): step 0's as recorded, each later step's from the step
before it. A stateless policy's batch has none of these keys.
"""

from typing import Dict, List, Optional

import jax
import numpy as np

from repro.core.learner import BatchSourceClosed
from repro.onpolicy.queue import Closed, TrajectoryQueue
from repro.telemetry.tracer import maybe_span


def assemble_vtrace_batch(unrolls: List[Dict[str, np.ndarray]],
                          gamma: float) -> Dict[str, np.ndarray]:
    """Stack per-lane unrolls into a (B, T) V-trace batch.

    Raises KeyError if an unroll is missing ``behavior_logprobs`` — an
    on-policy system wired to a policy that doesn't report logprobs is a
    configuration error worth failing loudly on, not a NaN factory.
    """
    if not unrolls:
        raise ValueError("cannot assemble an empty batch")
    dones = np.stack([u["dones"] for u in unrolls]).astype(np.float32)
    batch = {
        "obs": np.stack([u["obs"] for u in unrolls]),
        "actions": np.stack([u["actions"] for u in unrolls]).astype(np.int32),
        "rewards": np.stack([u["rewards"] for u in unrolls]).astype(np.float32),
        "discounts": (gamma * (1.0 - dones)).astype(np.float32),
        "behavior_logprobs": np.stack(
            [u["behavior_logprobs"] for u in unrolls]).astype(np.float32),
    }
    # ALWAYS present (zeros when unstamped): a sometimes-there key would
    # change the batch pytree structure and force a train_step recompile
    # mid-run — the warmup batch must look exactly like the real ones
    batch["param_version"] = np.asarray(
        [int(np.asarray(u.get("param_version", 0)).reshape(()))
         for u in unrolls], np.int64)
    if "core" in unrolls[0]:
        batch.update(_recurrent_fields(unrolls, batch, dones))
    return batch


def _recurrent_fields(unrolls, batch, dones):
    """The core before step 0 and each step's previous action and reward
    and episode-start flag: recorded for step 0, else the step before's
    (0 where that step ended its episode)."""
    def step0(key, dtype):
        return np.asarray([u[key] for u in unrolls], dtype)[:, None]

    ended = dones[:, :-1] > 0
    return {
        "core": jax.tree.map(lambda *xs: np.stack(xs),
                             *[u["core"] for u in unrolls]),
        "prev_action": np.concatenate(
            [step0("prev_action", np.int32),
             np.where(ended, 0, batch["actions"][:, :-1])], 1
        ).astype(np.int32),
        "prev_reward": np.concatenate(
            [step0("prev_reward", np.float32),
             np.where(ended, 0.0, batch["rewards"][:, :-1])], 1
        ).astype(np.float32),
        "first": np.concatenate([step0("first", bool), ended], 1),
    }


class VTraceBatcher:
    """`Learner`-shaped batch source over a `TrajectoryQueue`.

    ``batcher() -> (batch, None)`` blocks until `batch_size` unrolls are
    available; a closed queue surfaces as `BatchSourceClosed`, which
    `Learner._loop` treats as a clean shutdown (the poison seam — see
    `Learner.stop`). With a tracer, the pop and the assembly are the
    ``onpolicy/pop_batch`` and ``onpolicy/assemble`` spans.
    """

    def __init__(self, queue: TrajectoryQueue, batch_size: int,
                 gamma: float = 0.99,
                 poll_timeout_s: Optional[float] = 0.5, tracer=None):
        self.queue = queue
        self.batch_size = batch_size
        self.gamma = gamma
        self.poll_timeout_s = poll_timeout_s
        self._tracer = tracer

    def __call__(self):
        tr = self._tracer
        while True:
            try:
                with maybe_span(tr, "onpolicy/pop_batch"):
                    unrolls = self.queue.pop_batch(
                        self.batch_size, timeout=self.poll_timeout_s)
                with maybe_span(tr, "onpolicy/assemble"):
                    return assemble_vtrace_batch(unrolls, self.gamma), None
            except Closed:
                raise BatchSourceClosed("trajectory queue closed") from None
            except TimeoutError:
                continue
