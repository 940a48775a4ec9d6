"""V-trace learner: the on-policy train_step and the sampling policies
that generate its data.

`make_vtrace_train_step` builds the jittable ``train_step(state, batch)``
the generic `core.learner.Learner` loop drives — the same publish/version
seam R2D2 uses, different math: V-trace corrected targets
(`core.vtrace`) over the staleness-stamped batches a `VTraceBatcher`
assembles. The last unroll step is the bootstrap anchor (its value
estimate closes the return), so a T-step unroll trains T-1 positions.

Data generation needs the policy to report the behavior logprob of every
sampled action (V-trace's denominator). Two adapters cover the backends:

  * `SamplingPolicy` — a host-side ``policy_step`` for the central
    `InferenceServer`: samples from the latest *published* params (the
    learner pushes them via its publish seam) and returns the
    ``(N, 2) float32 [action, logprob]`` convention on-policy actors
    decode (`core.actor.Actor(with_logprobs=True)`); it also carries the
    param version the system stamps unrolls with.
  * `make_device_sampling_policy` — the device-backend counterpart: a
    pure ``policy_apply`` returning (actions, logprobs, core) for the
    fused scan (`DeviceRolloutEngine(with_logprobs=True)`).

Every policy is ``apply_fn(params, core, inputs) -> (logits, values,
core)`` over (B, T) inputs. A stateless one (`mlp_actor_critic`) reads
``inputs["obs"]`` alone and carries a ``None`` core; a recurrent one
(`models.impala`) also reads ``prev_action``, ``prev_reward`` and
``first`` and unrolls from the core before step 0. A recurrent policy
trains only on the device backend, whose scans record each unroll's
starting core.
"""

import threading
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.vtrace import vtrace, vtrace_losses
from repro.optim.adamw import apply_updates
from repro.telemetry.tracer import maybe_span


def mlp_actor_critic(obs_dim: int, num_actions: int, hidden: int = 64):
    """Tiny shared-torso actor-critic: returns (init_fn, apply_fn) with
    ``apply_fn(params, core, {"obs": obs[..., obs_dim]}) -> (logits[...,
    A], value[...], core)``, stateless (the core passes through) and
    rank-polymorphic, so the same function serves (N,) inference batches
    and (B, T) learner batches."""

    def init_fn(key):
        k1, k2, k3 = jax.random.split(key, 3)
        s = 1.0 / np.sqrt(obs_dim)
        return {
            "w1": jax.random.normal(k1, (obs_dim, hidden)) * s,
            "b1": jnp.zeros((hidden,)),
            "wp": jax.random.normal(k2, (hidden, num_actions)) * 0.01,
            "bp": jnp.zeros((num_actions,)),
            "wv": jax.random.normal(k3, (hidden, 1)) * 0.01,
            "bv": jnp.zeros((1,)),
        }

    def apply_fn(params, core, inputs):
        h = jax.nn.relu(inputs["obs"] @ params["w1"] + params["b1"])
        logits = h @ params["wp"] + params["bp"]
        value = (h @ params["wv"] + params["bv"])[..., 0]
        return logits, value, core

    return init_fn, apply_fn


def make_vtrace_train_step(apply_fn: Callable, optimizer, *,
                           rho_bar: float = 1.0, c_bar: float = 1.0,
                           value_coef: float = 0.5,
                           entropy_coef: float = 0.01):
    """train_step(state, batch) -> (state, metrics) over V-trace batches.

    ``apply_fn(params, core, batch) -> (logits[B, T, A], values[B, T],
    core)`` reads its inputs from the batch and starts from
    ``batch["core"]``, which only a recurrent policy's batch carries (the
    core recorded before step 0; ``first`` resets it). Batch fields are
    the `assemble_vtrace_batch` schema. The state dict is the standard
    {params, opt_state, step} pytree, so checkpointing and the `Learner`
    publish seam work unchanged.
    """

    def loss_fn(params, batch):
        logits, values, _ = apply_fn(params, batch.get("core"), batch)
        logp = jax.nn.log_softmax(logits)
        taken = jnp.take_along_axis(
            logp, batch["actions"][..., None], axis=-1)[..., 0]
        entropy = -jnp.sum(jax.nn.softmax(logits) * logp, axis=-1)

        # step T-1 only bootstraps: train positions 0..T-2
        tlp = taken[:, :-1]
        vtr = vtrace(tlp, batch["behavior_logprobs"][:, :-1],
                     batch["rewards"][:, :-1], batch["discounts"][:, :-1],
                     values[:, :-1], values[:, -1],
                     rho_bar=rho_bar, c_bar=c_bar)
        mask = jnp.ones_like(tlp)
        pg, vl, en = vtrace_losses(tlp, entropy[:, :-1], vtr, values[:, :-1],
                                   mask, value_coef=value_coef,
                                   entropy_coef=entropy_coef)
        loss = pg + vl + en
        return loss, {"loss": loss, "pg_loss": pg, "value_loss": vl,
                      "entropy_loss": en, "mean_rho": vtr.rhos.mean()}

    def train_step(state, batch):
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (_, metrics), grads = grad_fn(state["params"], batch)
        updates, opt_state, om = optimizer.update(
            grads, state["opt_state"], state["params"], state["step"])
        params = apply_updates(state["params"], updates)
        metrics.update(om)
        return {"params": params, "opt_state": opt_state,
                "step": state["step"] + 1}, metrics

    return train_step


class VTraceLearner:
    """The on-policy learner bundle for one actor-critic policy: the
    jitted V-trace `train_step` (what `SeedSystem(algo="vtrace")` drives
    through the generic `Learner` loop), fresh train state, the two
    sampling adapters, and a warmup that pre-compiles the step at the
    system's batch shape. `assemble_vtrace_batch` keeps the batch pytree
    structure fixed, so ONE warmup covers the whole run — without it the
    first real batch compiles inside the measured window (observed 3.2 s
    vs the 80 ms steady step on a 2-core host). A recurrent policy also
    gives ``init_core(n)``, the zero core of n lanes: the warmup batch
    carries it, and the host-backend sampling policy is refused."""

    def __init__(self, apply_fn: Callable, optimizer, *,
                 init_core: Optional[Callable] = None,
                 rho_bar: float = 1.0, c_bar: float = 1.0,
                 value_coef: float = 0.5, entropy_coef: float = 0.01):
        self.apply_fn = apply_fn
        self.optimizer = optimizer
        self.init_core = init_core
        self.train_step = jax.jit(make_vtrace_train_step(
            apply_fn, optimizer, rho_bar=rho_bar, c_bar=c_bar,
            value_coef=value_coef, entropy_coef=entropy_coef))

    def init_state(self, params) -> dict:
        """Standard {params, opt_state, step} train-state pytree."""
        return {"params": params, "opt_state": self.optimizer.init(params),
                "step": jnp.zeros((), jnp.int32)}

    def warmup(self, state, *, batch_size: int, unroll: int,
               obs_shape: Tuple[int, ...], obs_dtype=np.float32):
        """Compile the train step on a structurally-identical dummy batch
        (state is NOT advanced)."""
        from repro.onpolicy.batcher import assemble_vtrace_batch
        dummy = {"obs": np.zeros((unroll,) + tuple(obs_shape), obs_dtype),
                 "actions": np.zeros((unroll,), np.int32),
                 "rewards": np.zeros((unroll,), np.float32),
                 "dones": np.zeros((unroll,), np.float32),
                 "behavior_logprobs": np.zeros((unroll,), np.float32)}
        if self.init_core is not None:
            dummy.update(core=jax.tree.map(lambda x: np.asarray(x)[0],
                                           self.init_core(1)),
                         prev_action=np.int32(0), prev_reward=np.float32(0),
                         first=True)
        self.train_step(state, assemble_vtrace_batch([dummy] * batch_size,
                                                     gamma=0.99))

    def sampling_policy(self, params, seed: int = 0) -> "SamplingPolicy":
        """Host-backend `policy_step` (wire `.publish` via
        `SeedSystem(policy_publish=...)`); stateless policies only."""
        if self.init_core is not None:
            raise ValueError(
                "a recurrent V-trace policy has no host-backend sampling "
                "policy: central inference keeps no per-slot V-trace core "
                "and its requests carry no reward or done; use "
                "backend='device'")
        return SamplingPolicy(self.apply_fn, params, seed=seed)

    def device_policy_apply(self) -> Callable:
        """Device-backend `policy_apply` for the fused scan."""
        return make_device_sampling_policy(self.apply_fn)


def _sample(logits, key):
    actions = jax.random.categorical(key, logits)
    lp = jnp.take_along_axis(jax.nn.log_softmax(logits),
                             actions[..., None], axis=-1)[..., 0]
    return actions, lp


def _sample_with_logprobs(apply_fn):
    def fn(params, obs, key):
        logits, _, _ = apply_fn(params, None, {"obs": obs})
        return _sample(logits, key)
    return fn


class SamplingPolicy:
    """Host-backend ``policy_step`` that reports behavior logprobs.

    Returns ``(N, 2) float32`` rows of [action, behavior_logprob] — the
    reply convention `Actor(with_logprobs=True)` decodes. Params swap in
    via `publish` (wire it as `SeedSystem(policy_publish=...)`), under a
    lock because inference replicas may call concurrently with the
    learner's publish; `version` mirrors the publish step so callers can
    expose it (the gateway stamps it onto wire replies). A `SeedSystem`
    with telemetry sets ``tracer`` to its own, and each call is then
    traced as ``policy/key``, ``policy/dispatch`` and ``policy/fetch``.
    """

    def __init__(self, apply_fn: Callable, params, seed: int = 0):
        self._sample = jax.jit(_sample_with_logprobs(apply_fn))
        self._lock = threading.Lock()
        self._params = params
        self._base_key = jax.random.PRNGKey(seed)
        self._calls = 0
        self.tracer = None
        self.version = 0

    def publish(self, params, step: int):
        with self._lock:
            self._params = params
            self.version = int(step)

    def __call__(self, obs: np.ndarray, slot_ids) -> np.ndarray:
        tr = self.tracer
        with maybe_span(tr, "policy/key"):
            with self._lock:
                params = self._params
                self._calls += 1
                key = jax.random.fold_in(self._base_key, self._calls)
        with maybe_span(tr, "policy/dispatch"):
            actions, lp = self._sample(params, jnp.asarray(obs), key)
        with maybe_span(tr, "policy/fetch"):
            out = np.empty((np.asarray(obs).shape[0], 2), np.float32)
            out[:, 0] = np.asarray(actions)
            out[:, 1] = np.asarray(lp)
        return out


def make_device_sampling_policy(apply_fn: Callable):
    """Device-backend counterpart of `SamplingPolicy`: a pure
    ``policy_apply(params, core, inputs, key) -> (actions, logprobs,
    core)`` for `DeviceRolloutEngine(with_logprobs=True)` — the logprob
    rides the fused scan and comes back inside the trajectory pytree. It
    is the learner's apply at T = 1; an engine built without
    ``init_core`` hands it a ``None`` core."""

    def policy_apply(params, core, inputs, key):
        step = {k: v[:, None] for k, v in inputs._asdict().items()}
        logits, _, core = apply_fn(params, core, step)
        actions, lp = _sample(logits[:, 0], key)
        return actions, lp, core

    return policy_apply
