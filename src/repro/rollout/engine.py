"""Device-resident rollout engine: fused env+policy `lax.scan` unrolls.

The host-backed actor loop (`repro.core.actor`) pays one host<->device
round-trip per vector step: observations come down, actions go up, T times
per unroll. `DeviceRolloutEngine` fuses the pure-JAX env's `step` and the
policy forward into ONE jitted `lax.scan` over the unroll length, vmapped
over E lanes — the env-state batch, recurrent core state, observations and
PRNG key never leave the accelerator. The host sees exactly one transfer
per unroll: the stacked `(T, E, ...)` trajectory pytree.

Determinism contract (what the parity tests pin down):
  * lane i's env is seeded with `split(PRNGKey(seed), E)[i]` — the same
    derivation as `JaxVectorEnv`, so a host loop over the same keys
    produces bit-identical trajectories;
  * the per-step action key stream is `fold_in(PRNGKey(seed), 1)` split
    once per scan step (see `action_key`), so stochastic policies are
    reproducible against a host reference following the same stream.

Every device policy has one signature, ``policy_apply(params, core,
inputs, key)``: ``inputs`` is a `StepInputs` of each lane's observation,
previous action and reward, and episode-start flag. A recurrent policy
(one built with ``init_core``) zeroes its core where ``first`` is set,
and each trajectory then also records, under ``start``, the core every
lane held before the unroll's first step and that step's inputs, which
is what a learner needs to replay the unroll.
"""

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.envs.vector import _is_jax_env, as_env_instance
from repro.telemetry.tracer import maybe_span


class StepInputs(NamedTuple):
    """What a device policy sees of each lane at one step. At an episode's
    first step (``first`` set) there is no previous step: ``prev_action``
    and ``prev_reward`` are 0."""
    obs: jax.Array
    prev_action: jax.Array     # (E,) int32
    prev_reward: jax.Array     # (E,) float32
    first: jax.Array           # (E,) bool


def first_inputs(obs) -> StepInputs:
    """The inputs of every lane's first step after a reset."""
    n = obs.shape[0]
    return StepInputs(obs, jnp.zeros((n,), jnp.int32),
                      jnp.zeros((n,), jnp.float32), jnp.ones((n,), bool))


def next_inputs(nobs, actions, rewards, dones) -> StepInputs:
    """The inputs of the step after one that took ``actions`` and got
    ``rewards`` and ``dones``; an env that auto-resets hands back the next
    episode's first observation where ``dones`` is set."""
    dones = dones.astype(bool)
    return StepInputs(nobs, jnp.where(dones, 0, actions).astype(jnp.int32),
                      jnp.where(dones, 0.0, rewards).astype(jnp.float32),
                      dones)


def as_jax_env(env):
    """Normalize (factory | class | instance) into a pure-JAX env instance.

    The device engine requires a stateless keyed env (`reset(key)`,
    `step(state, action)`); host envs cannot ride a `lax.scan`.
    """
    instance, _ = as_env_instance(env)
    if not _is_jax_env(instance):
        raise ValueError(
            f"backend='device' requires a pure-JAX env (reset(key) -> "
            f"(state, obs)); got {type(instance).__name__}, a host env. "
            f"Use the host backend, or port the env to JAX.")
    return instance


def action_key(seed: int) -> jax.Array:
    """Initial key of the engine's per-step action stream (parity hook)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), 1)


class DeviceRolloutEngine:
    """Fused env+policy unrolls for one batch of E lanes.

    policy_apply: (params, core, inputs: StepInputs, key) -> (actions[E],
    core) — a pure function; `core` is any pytree of per-lane recurrent
    state (or None for feed-forward policies). One `rollout(params)` call
    advances all lanes T steps on-device and returns the host-side
    trajectory dict {obs (T,E,...), actions (T,E) i32, rewards (T,E) f32,
    dones (T,E) bool}; with ``init_core`` also ``start``: {core (E, ...),
    prev_action, prev_reward, first (E,)} as they stood before step 0.
    """

    def __init__(self, env, policy_apply: Callable, num_envs: int,
                 unroll: int, *, init_core: Optional[Callable] = None,
                 seed: int = 0, device=None, with_logprobs: bool = False):
        self.env = as_jax_env(env)
        self.num_envs = num_envs
        self.unroll = unroll
        self.num_actions = self.env.num_actions
        self.obs_shape = tuple(getattr(self.env, "obs_shape", ()))
        self._init_core = init_core       # init_core(num_envs) -> core pytree
        self._seed = seed
        # on-policy rollouts: policy_apply returns (actions, logprobs, core)
        # and the trajectory pytree gains behavior_logprobs (T, E) f32 —
        # V-trace's denominator rides the scan instead of a second forward
        self.with_logprobs = with_logprobs
        # optional explicit placement (engine sharding): the carry is
        # committed to `device` at reset, params are committed per call,
        # and jit then executes the whole fused scan there. None keeps the
        # historical default-device behavior bit-for-bit.
        self.device = device
        self._reset = jax.jit(jax.vmap(self.env.reset))
        self._unroll_fn = jax.jit(self._build(policy_apply, unroll))
        self._carry = None
        self.scans = 0                    # device round-trips (one per unroll)
        self.frames = 0                   # = scans * T * E

    def _build(self, policy_apply, T):
        vstep = jax.vmap(self.env.step)

        def unroll_fn(params, carry):
            def one_step(c, _):
                env_state, core, inputs, key = c
                key, sub = jax.random.split(key)
                if self.with_logprobs:
                    actions, logprobs, core = policy_apply(params, core,
                                                           inputs, sub)
                else:
                    actions, core = policy_apply(params, core, inputs, sub)
                actions = actions.astype(jnp.int32)
                env_state, nobs, rewards, dones = vstep(env_state, actions)
                out = {"obs": inputs.obs, "actions": actions,
                       "rewards": rewards.astype(jnp.float32),
                       "dones": dones}
                if self.with_logprobs:
                    out["behavior_logprobs"] = logprobs.astype(jnp.float32)
                return (env_state, core, next_inputs(nobs, actions, rewards,
                                                     dones), key), out

            _, core0, inputs0, _ = carry
            carry, traj = jax.lax.scan(one_step, carry, None, length=T)
            if self._init_core is not None:
                traj["start"] = {"core": core0,
                                 "prev_action": inputs0.prev_action,
                                 "prev_reward": inputs0.prev_reward,
                                 "first": inputs0.first}
            return carry, traj

        return unroll_fn

    def _place(self, tree):
        """Commit a pytree to this engine's device (no-op when unplaced)."""
        return tree if self.device is None else jax.device_put(tree,
                                                               self.device)

    def reset(self) -> np.ndarray:
        """(Re)seed all lanes; returns the initial obs batch (E, ...)."""
        keys = jax.random.split(jax.random.PRNGKey(self._seed), self.num_envs)
        env_state, obs = self._reset(keys)
        core = self._init_core(self.num_envs) if self._init_core else None
        self._carry = self._place(
            (env_state, core, first_inputs(obs), action_key(self._seed)))
        return np.asarray(obs)

    def warmup(self, params):
        """Compile the fused scan without advancing lane state or counters."""
        if self._carry is None:
            self.reset()
        carry, traj = self._unroll_fn(self._place(params), self._carry)
        jax.block_until_ready(traj["actions"])

    def dispatch(self, params):
        """Launch one unroll asynchronously: advances the carry and the
        counters, returns the ON-DEVICE trajectory pytree (no host
        transfer yet). `ShardedRolloutEngine` uses this to get all K
        engines' scans in flight before the first blocking device_get, so
        multi-device hosts overlap their scans."""
        if self._carry is None:
            self.reset()
        self._carry, traj = self._unroll_fn(self._place(params), self._carry)
        self.scans += 1
        self.frames += self.unroll * self.num_envs
        return traj

    def rollout(self, params, tracer=None) -> dict:
        """Advance all lanes T steps in one device call; ONE host transfer.
        With a ``tracer``, the launch is a ``rollout/dispatch`` span and
        the wait for the trajectory on the host a ``rollout/fetch``."""
        with maybe_span(tracer, "rollout/dispatch"):
            traj = self.dispatch(params)
        with maybe_span(tracer, "rollout/fetch"):
            # the single per-unroll transfer
            return jax.tree.map(np.asarray, jax.device_get(traj))


class ShardedRolloutEngine:
    """K device-sharded `DeviceRolloutEngine`s presenting as one engine.

    The `DeviceRolloutEngine` is one-device-one-carry by construction, so
    sharding the scan across accelerators is pure *placement*: lanes are
    partitioned contiguously into K shards, shard k's engine is committed
    to ``devices[k % len(devices)]`` with `jax.device_put`, and one
    `rollout()` dispatches ALL K fused scans before the first blocking
    host transfer — on a multi-device host the scans overlap, on a
    CPU-only host the round-robin degenerates to K serial scans on the one
    device (correct, just unaccelerated). Frame/scan accounting is summed
    across engines; the trajectory comes back as one (T, E_total, ...)
    batch, so `RolloutWorker` and the replay schema are unchanged.

    Seeding: shard k of an engine seeded `s` uses ``s * K + k`` — distinct
    per shard, and disjoint across workers as long as every worker uses
    the same K (which `SeedSystem` does).
    """

    def __init__(self, env, policy_apply: Callable, num_envs: int,
                 unroll: int, *, num_shards: int,
                 init_core: Optional[Callable] = None, seed: int = 0,
                 devices=None, with_logprobs: bool = False):
        if not isinstance(num_shards, int) or num_shards < 1:
            raise ValueError(
                f"num_shards must be a positive int, got {num_shards!r}")
        if num_shards > num_envs:
            raise ValueError(
                f"num_shards={num_shards} exceeds num_envs={num_envs}: "
                f"each engine shard needs at least one lane")
        devices = list(devices) if devices is not None else jax.devices()
        if not devices:
            raise ValueError("no devices available to place engine shards")
        self.num_envs = num_envs
        self.unroll = unroll
        self.num_shards = num_shards
        base, extra = divmod(num_envs, num_shards)
        self.engines = []
        for k in range(num_shards):
            lanes = base + (1 if k < extra else 0)
            self.engines.append(DeviceRolloutEngine(
                env, policy_apply, lanes, unroll, init_core=init_core,
                seed=seed * num_shards + k,
                device=devices[k % len(devices)],
                with_logprobs=with_logprobs))
        self.num_actions = self.engines[0].num_actions
        self.obs_shape = self.engines[0].obs_shape
        self.devices = [e.device for e in self.engines]
        self.scans = 0                    # sharded rollouts driven

    @property
    def frames(self) -> int:
        """Env frames supplied, summed across engine shards."""
        return sum(e.frames for e in self.engines)

    @property
    def shard_scans(self) -> int:
        """Per-engine scan total (= scans * num_shards once started)."""
        return sum(e.scans for e in self.engines)

    def reset(self) -> np.ndarray:
        return np.concatenate([e.reset() for e in self.engines])

    def warmup(self, params):
        for e in self.engines:
            e.warmup(params)

    def rollout(self, params, tracer=None) -> dict:
        """Advance all lanes T steps: K device calls dispatched before any
        host transfer, then ONE gather per shard, concatenated on the lane
        axis into the (T, E_total, ...) unroll schema (``start``'s fields
        are lane-major). Spans as `DeviceRolloutEngine.rollout`."""
        with maybe_span(tracer, "rollout/dispatch"):
            trajs = [e.dispatch(params) for e in self.engines]
        with maybe_span(tracer, "rollout/fetch"):
            hosts = [jax.device_get(t) for t in trajs]
            self.scans += 1
            out = {k: np.concatenate([np.asarray(h[k]) for h in hosts],
                                     axis=1)
                   for k in hosts[0] if k != "start"}
            if "start" in hosts[0]:
                out["start"] = jax.tree.map(
                    lambda *xs: np.concatenate(xs, axis=0),
                    *[h["start"] for h in hosts])
            return out
