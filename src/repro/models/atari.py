"""The paper's exact workload: R2D2 conv-LSTM agent (Kapturowski et al. '19)
for ALE — Nature-DQN conv torso, LSTM core, dueling Q heads.

This network is small enough to actually *train on CPU* in examples/, which
anchors the paper-faithful reproduction (Fig 3's actor sweep runs it live).
"""

from functools import partial

import jax
import jax.numpy as jnp

from repro.nn import init as inits
from repro.nn.recurrent import init_lstm, lstm_scan, lstm_step, lstm_state_init
from repro.models.common import ModelBundle, ModelOutputs
from repro.sharding.param import ArrayMaker, SpecMaker

CONVS = ((32, 8, 4), (64, 4, 2), (64, 3, 1))   # (features, kernel, stride)


def _conv_out_hw(hw, kernel, stride):
    return (hw - kernel) // stride + 1


def init_conv(mk, name, cin, cout, k):
    return {
        "w": mk(f"{name}.w", (k, k, cin, cout), (None, None, None, None),
                inits.fan_in(in_axes=(0, 1, 2))),
        "b": mk(f"{name}.b", (cout,), (None,), inits.zeros),
    }


def _apply_conv(p, x, stride):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(stride, stride), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jax.nn.relu(y + p["b"])


def _torso_dims(cfg):
    h = w = cfg.obs_size
    cin = cfg.obs_channels
    for feats, k, s in CONVS:
        h, w = _conv_out_hw(h, k, s), _conv_out_hw(w, k, s)
        cin = feats
    return h * w * cin


def _build(cfg, mk):
    p = {}
    cin = cfg.obs_channels
    for i, (feats, k, s) in enumerate(CONVS):
        p[f"conv{i}"] = init_conv(mk, f"conv{i}", cin, feats, k)
        cin = feats
    flat = _torso_dims(cfg)
    p["torso_out"] = {
        "w": mk("torso_out.w", (flat, cfg.core_dim), (None, None), inits.fan_in()),
        "b": mk("torso_out.b", (cfg.core_dim,), (None,), inits.zeros)}
    p["lstm"] = init_lstm(mk, cfg.core_dim, cfg.core_dim)
    p["adv"] = {"w": mk("adv.w", (cfg.core_dim, cfg.num_actions), (None, None),
                        inits.fan_in()),
                "b": mk("adv.b", (cfg.num_actions,), (None,), inits.zeros)}
    p["val"] = {"w": mk("val.w", (cfg.core_dim, 1), (None, None), inits.fan_in()),
                "b": mk("val.b", (1,), (None,), inits.zeros)}
    return p


def _torso(cfg, p, obs):
    """obs (N, H, W, C) uint8/float -> (N, core_dim)."""
    x = obs.astype(jnp.float32) / 255.0 if obs.dtype == jnp.uint8 else obs.astype(jnp.float32)
    for i, (_, _, s) in enumerate(CONVS):
        x = _apply_conv(p[f"conv{i}"], x, s)
    x = x.reshape(x.shape[0], -1)
    return jax.nn.relu(x @ p["torso_out"]["w"] + p["torso_out"]["b"])


def _duel(p, h):
    adv = h @ p["adv"]["w"] + p["adv"]["b"]
    val = h @ p["val"]["w"] + p["val"]["b"]
    return val + adv - adv.mean(axis=-1, keepdims=True)


def atari_forward(cfg, params, batch):
    """batch['obs'] (B,T,H,W,C); optional batch['core'] initial LSTM state.
    Returns q-values (B,T,A) as .logits."""
    obs = batch["obs"]
    b, t = obs.shape[:2]
    e = _torso(cfg, params, obs.reshape((b * t,) + obs.shape[2:]))
    e = e.reshape(b, t, -1)
    state = batch.get("core")
    if state is None:
        state = lstm_state_init(b, cfg.core_dim)
    hs, state = lstm_scan(params["lstm"], e, state)
    q = _duel(params, hs)
    return ModelOutputs(logits=q, value=q.max(-1), aux_loss=0.0), state


def atari_step(cfg, params, obs_t, state):
    """Single env step for actor inference: obs (B,H,W,C) -> (q (B,A), state)."""
    e = _torso(cfg, params, obs_t)
    h, state = lstm_step(params["lstm"], e, state)
    return _duel(params, h), state


def make_atari(cfg) -> ModelBundle:
    return ModelBundle(
        cfg=cfg,
        init=lambda rng: _build(cfg, ArrayMaker(rng, jnp.float32)),
        logical_axes=lambda: _build(cfg, SpecMaker("axes")),
        forward=lambda params, batch: atari_forward(cfg, params, batch)[0],
        init_cache=lambda batch, max_len=None, dtype=None:
            lstm_state_init(batch, cfg.core_dim),
        prefill=None,
        decode_step=lambda params, obs_t, state: atari_step(cfg, params, obs_t, state),
    )
