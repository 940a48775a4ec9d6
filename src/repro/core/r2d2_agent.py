"""The paper's R2D2 agent wired into `SeedSystem`: central inference that
owns per-lane LSTM state (SEED's key design), prioritized replay, and the
recurrent double-Q learner that publishes params back to inference.

`build_r2d2_system` is the one place this wiring lives; the example
(`examples/train_atari_r2d2.py`, reduced widths) and `chip_smoke.py` (the
published `AtariConfig()` widths) both call it.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.losses import init_train_state, make_train_step
from repro.core.system import SeedSystem
from repro.models.atari import make_atari
from repro.nn.recurrent import lstm_state_init
from repro.optim import adamw
from repro.telemetry.tracer import maybe_span

LR = 5e-4
EPSILON = 0.2          # share of actions drawn uniformly at random
DEADLINE_MS = 4.0      # inference batching deadline
SEED = 0


def build_r2d2_system(acfg, env_factory, *, num_actors: int,
                      envs_per_actor: int, learner_batch: int,
                      replay_capacity: int, min_replay: int,
                      telemetry=None) -> SeedSystem:
    """A host-backend, in-process `SeedSystem` training R2D2 on `acfg`.

    Both jitted paths are compiled before this returns, so a `run()`
    window is steady state: the policy at every lane count the server can
    batch (whole actors: ``k * envs_per_actor`` for k = 1..``num_actors``)
    and the train step at ``learner_batch`` sequences of ``burn_in +
    unroll`` steps. The compile leaves the train and LSTM state untouched.

    ``telemetry`` (a `repro.telemetry.Telemetry`) goes to the system; with
    it enabled, each policy step is traced as its ``policy/slot_gather``,
    ``policy/dispatch``, ``policy/fetch`` and ``policy/slot_scatter``, and
    the batch's conversion to device arrays inside each train step as
    ``learner/input``.
    """
    tr = (telemetry.tracer
          if telemetry is not None and telemetry.enabled else None)
    bundle = make_atari(acfg)
    opt = adamw(LR)
    state = init_train_state(bundle, opt, jax.random.PRNGKey(SEED),
                             with_target=True)
    # no donation: the inference thread reads live["params"] while the
    # learner steps, so the old buffers must stay alive
    train_step = jax.jit(make_train_step(bundle, opt, algo="r2d2", acfg=acfg))

    # the server hands policy_step dense (actor, env) slot ids, so the LSTM
    # state table has one row per lane
    lanes = num_actors * envs_per_actor
    params_lock = threading.Lock()
    live = {"params": state["params"]}
    core = {"h": np.zeros((lanes, acfg.core_dim), np.float32),
            "c": np.zeros((lanes, acfg.core_dim), np.float32)}
    rng = np.random.default_rng(SEED)

    @jax.jit
    def _policy(params, obs, h, c):
        q, (h2, c2) = bundle.decode_step(params, obs, (h, c))
        return jnp.argmax(q, -1), h2, c2

    def policy_step(obs, ids):
        n = len(ids)
        with params_lock:
            p = live["params"]
        with maybe_span(tr, "policy/slot_gather"):
            h, c = core["h"][ids], core["c"][ids]
        with maybe_span(tr, "policy/dispatch"):
            a, h2, c2 = _policy(p, obs, h, c)
        with maybe_span(tr, "policy/fetch"):
            a, h2, c2 = np.asarray(a), np.asarray(h2), np.asarray(c2)
        with maybe_span(tr, "policy/slot_scatter"):
            core["h"][ids] = h2
            core["c"][ids] = c2
        explore = rng.random(n) < EPSILON
        return np.where(explore, rng.integers(0, acfg.num_actions, n), a)

    def learner_step(st, batch):
        b = batch["obs"].shape[0]
        with maybe_span(tr, "learner/input"):
            jb = {
                "obs": jnp.asarray(batch["obs"]),
                "actions": jnp.asarray(batch["actions"], jnp.int32),
                "rewards": jnp.asarray(batch["rewards"]),
                "dones": jnp.asarray(batch["dones"]),
                "core": lstm_state_init(b, acfg.core_dim),
            }
        st, metrics = train_step(st, jb)
        with params_lock:
            live["params"] = st["params"]
        return st, metrics

    obs = np.asarray(env_factory().reset())
    for k in range(1, num_actors + 1):
        n = k * envs_per_actor
        jax.block_until_ready(_policy(
            state["params"], obs[None].repeat(n, 0), core["h"][:n],
            core["c"][:n]))
    seq_len = acfg.burn_in + acfg.unroll
    dummy = {
        "obs": jnp.zeros((learner_batch, seq_len) + obs.shape, obs.dtype),
        "actions": jnp.zeros((learner_batch, seq_len), jnp.int32),
        "rewards": jnp.zeros((learner_batch, seq_len), jnp.float32),
        "dones": jnp.zeros((learner_batch, seq_len), jnp.float32),
        "core": lstm_state_init(learner_batch, acfg.core_dim),
    }
    jax.block_until_ready(train_step(state, dummy))

    return SeedSystem(
        env_factory=env_factory, policy_step=policy_step,
        num_actors=num_actors, unroll=seq_len, envs_per_actor=envs_per_actor,
        train_step=learner_step, state=state, learner_batch=learner_batch,
        replay_capacity=replay_capacity, min_replay=min_replay,
        deadline_ms=DEADLINE_MS, telemetry=telemetry)
