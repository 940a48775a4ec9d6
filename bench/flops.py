"""Model FLOPs from a configuration's shapes, and the table of chip peaks.

A multiply-add is two FLOPs. Work the program recomputes does not count,
and neither does work no one needs: the gradient with respect to the raw
observation (the first layer's input) is never computed. Each function
takes the configuration file's dict (``bench/configs/<name>.json``).
"""

import json
from pathlib import Path

PEAKS = json.loads((Path(__file__).parent / "peaks.json").read_text())

R2D2_CONVS = ((32, 8, 4), (64, 4, 2), (64, 3, 1))   # features, kernel, stride


def peak(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown chip is an
    error, never a default."""
    if device_kind not in PEAKS["chips"]:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; known: "
                       f"{sorted(PEAKS['chips'])}")
    return PEAKS["chips"][device_kind]


def r2d2_layer_macs(model: dict) -> dict:
    """Multiply-adds per frame of one forward pass, by layer."""
    hw, cin = model["obs_size"], model["obs_channels"]
    macs = {}
    for i, (feats, k, s) in enumerate(R2D2_CONVS):
        hw = (hw - k) // s + 1
        macs[f"conv{i}"] = hw * hw * feats * k * k * cin
        cin = feats
    d = model["core_dim"]
    macs["torso"] = hw * hw * cin * d
    macs["lstm"] = 2 * d * 4 * d
    macs["heads"] = d * model["num_actions"] + d
    return macs


def r2d2_forward_flops(model: dict) -> float:
    """One policy step of one lane: the forward pass for one frame."""
    return 2.0 * sum(r2d2_layer_macs(model).values())


def r2d2_step_flops(model: dict, batch: int) -> float:
    """One learner step as `core/losses.py` writes the loss: the online
    and the target forward over every frame of the burn-in plus unroll,
    and the backward of the online pass over the same frames (the burn-in
    is not stopped from taking gradients). The backward costs two forwards
    per layer, less the first conv's gradient with respect to its input."""
    macs = r2d2_layer_macs(model)
    fwd = sum(macs.values())
    bwd = 2 * fwd - macs["conv0"]
    frames = batch * (model["burn_in"] + model["unroll"])
    return 2.0 * frames * (2 * fwd + bwd)


def mlp_layer_macs(model: dict, obs_dim: int, num_actions: int) -> dict:
    h = model["hidden"]
    return {"hidden": obs_dim * h, "policy": h * num_actions, "value": h}


def mlp_policy_flops(model: dict, obs_dim: int, num_actions: int) -> float:
    """One sampled action of one lane: the hidden layer and the policy
    head (the value head's output is unused there)."""
    m = mlp_layer_macs(model, obs_dim, num_actions)
    return 2.0 * (m["hidden"] + m["policy"])


def mlp_step_flops(model: dict, obs_dim: int, num_actions: int,
                   batch: int) -> float:
    """One V-trace learner step over ``batch`` unrolls of ``unroll``
    frames: forward of both heads, and a backward that takes no gradient
    with respect to the observation."""
    m = mlp_layer_macs(model, obs_dim, num_actions)
    fwd = sum(m.values())
    bwd = 2 * fwd - m["hidden"]
    return 2.0 * batch * model["unroll"] * (fwd + bwd)
