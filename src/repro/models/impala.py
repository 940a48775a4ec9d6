"""IMPALA's deep ResNet-LSTM actor-critic (Espeholt et al. 2018, Fig. 3
right, the "large" network), for V-trace on the device backend.

Each conv stack is a 3x3 conv (stride 1, SAME), a 3x3 max-pool (stride 2,
SAME) and two residual blocks (ReLU, 3x3 conv, ReLU, 3x3 conv, plus the
skip). After the stacks: ReLU, a dense layer, ReLU. The LSTM reads that
output concatenated with the clipped previous reward and the one-hot
previous action, and is zeroed at every step that starts an episode. A
linear policy head and a linear baseline head read the LSTM output.

`impala_actor_critic(cfg)` returns ``(init_fn, apply_fn, init_core)``:

* ``apply_fn(params, core, inputs) -> (logits[B, T, A], values[B, T],
  core)``, where ``inputs`` maps ``obs`` (B, T, H, W, C), ``prev_action``
  (B, T) int, ``prev_reward`` (B, T) and ``first`` (B, T) bool; ``core``
  is the (B, 2, core_dim) LSTM state (h, c) before step 0. The policy
  step of a rollout is the T = 1 case.
* ``init_core(batch)`` is the zero state.

Weights are float32 and come from the key in declaration order (conv
stacks, dense, LSTM, policy, baseline; each weight before its bias) with
fan-in scaling; no head is scaled towards a uniform policy. Computation
is in the parameters' dtype at the backend's default matmul precision.
"""

import jax
import jax.numpy as jnp

from repro.configs.impala_atari import ImpalaConfig
from repro.models.atari import init_conv
from repro.nn import init as inits
from repro.nn.recurrent import init_lstm, lstm_step
from repro.sharding.param import ArrayMaker


def _dense(mk, name, d_in, d_out):
    return {"w": mk(f"{name}.w", (d_in, d_out), (None, None), inits.fan_in()),
            "b": mk(f"{name}.b", (d_out,), (None,), inits.zeros)}


def _torso_dim(cfg: ImpalaConfig) -> int:
    """Width of the flattened conv output: each stack's pool halves the
    side, rounding up."""
    hw = cfg.obs_size
    for _ in cfg.channels:
        hw = -(-hw // 2)
    return hw * hw * cfg.channels[-1]


def _build(cfg: ImpalaConfig, mk):
    p = {}
    cin = cfg.obs_channels
    for s, ch in enumerate(cfg.channels):
        stack = {"conv": init_conv(mk, f"stack{s}.conv", cin, ch, 3)}
        for r in range(cfg.res_blocks):
            stack[f"res{r}"] = {
                f"conv{j}": init_conv(mk, f"stack{s}.res{r}.conv{j}", ch, ch,
                                      3)
                for j in range(2)}
        p[f"stack{s}"] = stack
        cin = ch
    p["fc"] = _dense(mk, "fc", _torso_dim(cfg), cfg.fc_dim)
    p["lstm"] = init_lstm(mk, cfg.fc_dim + 1 + cfg.num_actions, cfg.core_dim)
    p["policy"] = _dense(mk, "policy", cfg.core_dim, cfg.num_actions)
    p["baseline"] = _dense(mk, "baseline", cfg.core_dim, 1)
    return p


def _conv(p, x):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def _max_pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1), "SAME")


def _torso(cfg, params, obs):
    """obs (N, H, W, C) uint8 -> (N, fc_dim)."""
    dt = params["fc"]["w"].dtype
    x = obs.astype(dt) / 255.0
    for s in range(len(cfg.channels)):
        stack = params[f"stack{s}"]
        x = _max_pool(_conv(stack["conv"], x))
        for r in range(cfg.res_blocks):
            res = stack[f"res{r}"]
            y = _conv(res["conv0"], jax.nn.relu(x))
            x = x + _conv(res["conv1"], jax.nn.relu(y))
    x = jax.nn.relu(x).reshape(x.shape[0], -1)
    return jax.nn.relu(x @ params["fc"]["w"] + params["fc"]["b"])


def impala_actor_critic(cfg: ImpalaConfig = ImpalaConfig()):
    def init_fn(key):
        return _build(cfg, ArrayMaker(key, jnp.float32))

    def init_core(batch):
        return jnp.zeros((batch, 2, cfg.core_dim), jnp.float32)

    def apply_fn(params, core, inputs):
        obs = inputs["obs"]
        b, t = obs.shape[:2]
        dt = params["fc"]["w"].dtype
        e = _torso(cfg, params, obs.reshape((b * t,) + obs.shape[2:]))
        reward = jnp.clip(inputs["prev_reward"], -1.0, 1.0).astype(dt)
        action = jax.nn.one_hot(inputs["prev_action"], cfg.num_actions,
                                dtype=dt)
        x = jnp.concatenate([e.reshape(b, t, -1), reward[..., None], action],
                            -1)

        def step(core, xs):
            x_t, first_t = xs
            core = jnp.where(first_t[:, None, None], 0.0, core)
            h, (h, c) = lstm_step(params["lstm"], x_t,
                                  (core[:, 0], core[:, 1]))
            return jnp.stack([h, c], 1), h

        steps = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(inputs["first"], 1, 0))
        core, hs = jax.lax.scan(step, core.astype(dt), steps)
        hs = jnp.moveaxis(hs, 0, 1)
        logits = hs @ params["policy"]["w"] + params["policy"]["b"]
        values = (hs @ params["baseline"]["w"]
                  + params["baseline"]["b"])[..., 0]
        return logits, values, core

    return init_fn, apply_fn, init_core
