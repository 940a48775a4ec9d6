"""Model FLOPs of IMPALA's deep ResNet-LSTM (Espeholt et al. 2018, Fig. 3
right) from its configuration's shapes (``bench/configs/
impala_deep_atari.json``'s ``model``).

A multiply-add is two FLOPs. Work the program recomputes does not count,
and neither does work no one needs: the gradient with respect to the raw
frames (the first conv's input), and the backward pass of an unroll's
last step, which only bootstraps (V-trace stops its gradient), are never
needed. Pools, ReLUs, the skip additions and the LSTM's gate
nonlinearities are left out, as elementwise work.
"""


def layer_macs(model: dict) -> dict:
    """Multiply-adds per frame of one forward pass, by layer."""
    hw, cin = model["obs_size"], model["obs_channels"]
    macs = {}
    for s, ch in enumerate(model["channels"]):
        macs[f"stack{s}.conv"] = hw * hw * 9 * cin * ch
        hw = (hw + 1) // 2                  # 3x3 max-pool, stride 2, SAME
        macs[f"stack{s}.res"] = (2 * model["res_blocks"]
                                 * hw * hw * 9 * ch * ch)
        cin = ch
    d, h, a = model["fc_dim"], model["core_dim"], model["num_actions"]
    macs["fc"] = hw * hw * cin * d
    macs["lstm"] = (d + 1 + a + h) * 4 * h
    macs["policy"] = h * a
    macs["baseline"] = h
    return macs


def policy_flops(model: dict) -> float:
    """One sampled action of one lane in a scan: the forward pass without
    the baseline head, whose output the scan does not use."""
    m = layer_macs(model)
    return 2.0 * (sum(m.values()) - m["baseline"])


def step_flops(model: dict, batch: int) -> float:
    """One V-trace learner step over ``batch`` unrolls of ``unroll``
    frames: the forward pass over every frame, and the backward pass,
    two forwards per layer less the first conv's input gradient, over
    every frame but each unroll's last."""
    m = layer_macs(model)
    fwd = sum(m.values())
    bwd = 2 * fwd - m["stack0.conv"]
    t = model["unroll"]
    return 2.0 * batch * (t * fwd + (t - 1) * bwd)
