"""Plain reference of IMPALA's deep ResNet-LSTM actor-critic (Espeholt et
al. 2018, Fig. 3 right) under V-trace, written out from the configuration
file with nothing taken from the program.

Weights: parameter ``i`` in declaration order draws from
``fold_in(PRNGKey(seed), i)``: a normal truncated to two standard
deviations, scaled by one over the square root of its fan-in; biases start
at zero. The order: for each conv stack its conv, then for each residual
block its two convs; the dense layer; the LSTM's input matrix, recurrent
matrix and bias; the policy head; the baseline head; each weight before
its bias.

Forward: frames scaled to [0, 1]. Each stack: a 3x3 conv (stride 1,
SAME), a 3x3 max-pool (stride 2, SAME), then residual blocks x + conv(
relu(conv(relu(x)))). A conv is written as one matmul over the nine
shifted copies of the zero-padded input: at ``highest`` precision the
TPU's compiler takes minutes over the gradient of a native convolution,
and seconds over this. Then ReLU, the dense layer, ReLU. The LSTM's input
is that output, the previous reward clipped to [-1, 1] and the one-hot
previous action, in that order; its state (h, c) is zeroed at each step
whose ``first`` is set, and its forget gate carries a +1 bias (gates
ordered input, forget, cell, output). Linear policy and baseline heads
read h.

Loss: as `vtrace_mlp`'s, over the logits and values the network unrolls
from the core recorded before each unroll's step 0: the last step only
bootstraps; with rho = min(rho_bar, pi/mu) and c = min(c_bar, pi/mu),
v_s - V(x_s) = sum of discounted products of c times delta_t = rho_t (r_t
+ gamma_t V(x_{t+1}) - V(x_t)); the policy gradient uses rho_s (r_s +
gamma_s v_{s+1} - V(x_s)). Loss = policy term + baseline_cost * 0.5 * mean
(v_s - V)^2 - entropy_cost * mean entropy. It is computed in blocks of
``BLOCK`` unrolls, each recomputed in the backward pass, so that the
float32 reference fits beside the program; every term is a mean over
equal blocks, so the loss is the mean of the blocks' losses.
"""

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 8                  # unrolls per block of the loss
BATCH_KEYS = ("obs", "actions", "rewards", "discounts", "behavior_logprobs",
              "core", "prev_action", "prev_reward", "first")


def shapes(model: dict):
    """[(path, shape, fan_in or None for a zero bias)] in declaration
    order."""
    out = []

    def conv(path, cin, cout):
        out.append((path + ("w",), (3, 3, cin, cout), 9 * cin))
        out.append((path + ("b",), (cout,), None))

    def dense(name, d_in, d_out):
        out.append(((name, "w"), (d_in, d_out), d_in))
        out.append(((name, "b"), (d_out,), None))

    cin, hw = model["obs_channels"], model["obs_size"]
    for s, ch in enumerate(model["channels"]):
        conv(("stack%d" % s, "conv"), cin, ch)
        for r in range(model["res_blocks"]):
            for j in range(2):
                conv(("stack%d" % s, "res%d" % r, "conv%d" % j), ch, ch)
        cin, hw = ch, (hw + 1) // 2
    d, a, h = model["fc_dim"], model["num_actions"], model["core_dim"]
    dense("fc", hw * hw * cin, d)
    x = d + 1 + a
    out += [(("lstm", "wi"), (x, 4 * h), x), (("lstm", "wh"), (h, 4 * h), h),
            (("lstm", "b"), (4 * h,), None)]
    dense("policy", h, a)
    dense("baseline", h, 1)
    return out


def init_params(model: dict, seed: int):
    root = jax.random.PRNGKey(seed)
    params = {}
    for i, (path, shape, fan) in enumerate(shapes(model)):
        if fan is None:
            x = jnp.zeros(shape, jnp.float32)
        else:
            x = (jax.random.truncated_normal(jax.random.fold_in(root, i),
                                             -2.0, 2.0, shape)
                 * (1.0 / np.sqrt(fan))).astype(jnp.float32)
        node = params
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = x
    return params


def _conv(p, x):
    """3x3 conv, stride 1, SAME, as one matmul over the nine shifted
    copies of the zero-padded input (NHWC, weights HWIO)."""
    n, h, w, c = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    patches = jnp.concatenate([xp[:, i:i + h, j:j + w]
                               for i in range(3) for j in range(3)], -1)
    return patches @ p["w"].reshape(9 * c, -1) + p["b"]


def forward(model: dict, params, batch):
    """Logits (B, T, A) and values (B, T) from ``batch``'s core (B, 2, H),
    obs (B, T, H, W, C), prev_action, prev_reward and first (B, T), in the
    parameters' dtype."""
    dt = params["fc"]["w"].dtype
    obs = batch["obs"]
    b, t = obs.shape[:2]
    x = obs.reshape((b * t,) + obs.shape[2:]).astype(dt) / 255.0
    for s in range(len(model["channels"])):
        stack = params["stack%d" % s]
        x = _conv(stack["conv"], x)
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), "SAME")
        for r in range(model["res_blocks"]):
            res = stack["res%d" % r]
            x = x + _conv(res["conv1"],
                          jax.nn.relu(_conv(res["conv0"], jax.nn.relu(x))))
    x = jax.nn.relu(x).reshape(b * t, -1)
    x = jax.nn.relu(x @ params["fc"]["w"] + params["fc"]["b"])
    x = jnp.concatenate(
        [x.reshape(b, t, -1),
         jnp.clip(batch["prev_reward"].astype(dt), -1.0, 1.0)[..., None],
         jax.nn.one_hot(batch["prev_action"], model["num_actions"],
                        dtype=dt)], -1)
    lstm = params["lstm"]

    def cell(hc, step):
        x_t, first = step
        keep = jnp.logical_not(first)[:, None].astype(dt)
        h, c = hc[0] * keep, hc[1] * keep
        gates = x_t @ lstm["wi"] + h @ lstm["wh"] + lstm["b"]
        gi, gf, gg, go = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(gf + 1.0) * c + jax.nn.sigmoid(gi) * jnp.tanh(gg)
        h = jax.nn.sigmoid(go) * jnp.tanh(c)
        return (h, c), h

    core = batch["core"].astype(dt)
    _, hs = jax.lax.scan(cell, (core[:, 0], core[:, 1]),
                         (jnp.swapaxes(x, 0, 1),
                          jnp.swapaxes(batch["first"], 0, 1)))
    hs = jnp.swapaxes(hs, 0, 1)
    logits = hs @ params["policy"]["w"] + params["policy"]["b"]
    values = (hs @ params["baseline"]["w"] + params["baseline"]["b"])[..., 0]
    return logits, values


def logprob_of(model: dict, params, batch):
    """(B, T) log-probability of each recorded action under ``params``,
    the network unrolled from each unroll's recorded core."""
    logits, _ = forward(model, params, batch)
    logp = jax.nn.log_softmax(logits)
    return jnp.take_along_axis(logp, batch["actions"][..., None], -1)[..., 0]


def _vtrace_loss(model, logits, values, batch, dt):
    logp = jax.nn.log_softmax(logits)
    taken = jnp.take_along_axis(logp, batch["actions"][..., None], -1)[..., 0]
    entropy = -jnp.sum(jax.nn.softmax(logits) * logp, -1)
    tlp, v = taken[:, :-1], values[:, :-1]
    boot = values[:, -1]
    ratio = jnp.exp(tlp - batch["behavior_logprobs"][:, :-1].astype(dt))
    rho = jnp.minimum(model["rho_bar"], ratio)
    c = jnp.minimum(model["c_bar"], ratio)
    r = batch["rewards"][:, :-1].astype(dt)
    disc = batch["discounts"][:, :-1].astype(dt)
    v_next = jnp.concatenate([v[:, 1:], boot[:, None]], 1)
    delta = rho * (r + disc * v_next - v)
    acc = jnp.zeros_like(boot)
    rows = [None] * v.shape[1]
    for s in reversed(range(v.shape[1])):
        acc = delta[:, s] + disc[:, s] * c[:, s] * acc
        rows[s] = acc
    vs = jax.lax.stop_gradient(v + jnp.stack(rows, 1))
    vs_next = jnp.concatenate([vs[:, 1:], boot[:, None]], 1)
    adv = jax.lax.stop_gradient(rho * (r + disc * vs_next - v))
    pg = -jnp.mean(tlp * adv)
    baseline = 0.5 * jnp.mean(jnp.square(vs - v))
    return (pg + model["baseline_cost"] * baseline
            - model["entropy_cost"] * jnp.mean(entropy[:, :-1]))


def make_loss(model: dict):
    def block_loss(params, block):
        dt = params["fc"]["w"].dtype
        logits, values = forward(model, params, block)
        return _vtrace_loss(model, logits, values, block, dt)

    def loss_fn(params, batch):
        batch = {k: batch[k] for k in BATCH_KEYS}
        b = batch["obs"].shape[0]
        size = min(BLOCK, b)
        if b % size:
            raise ValueError(f"batch of {b} unrolls is not a whole number "
                             f"of blocks of {size}")
        blocks = jax.tree.map(
            lambda x: x.reshape((b // size, size) + x.shape[1:]), batch)
        losses = jax.lax.map(
            lambda blk: jax.checkpoint(block_loss)(params, blk), blocks)
        return jnp.mean(losses)

    return loss_fn
