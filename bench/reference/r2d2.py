"""Plain reference of the R2D2 conv-LSTM agent (Kapturowski et al. 2019):
its initial weights, forward pass and loss, written out from the
configuration file with nothing taken from the program.

Weights: parameter ``i`` in declaration order (conv0..2, torso, LSTM input
and recurrent matrices and bias, advantage head, value head; each weight
before its bias) draws from ``fold_in(PRNGKey(seed), i)``: a normal
truncated to two standard deviations, scaled by one over the square root
of its fan-in; biases start at zero.

Forward: frames scaled to [0, 1], three VALID convolutions with ReLU
(NHWC), a dense ReLU layer, an LSTM whose forget gate carries a +1 bias
(gates ordered input, forget, cell, output), and dueling heads
(value + advantage - mean advantage).

Loss (as the program writes it): online and target nets unroll the whole
burn-in plus training sequence from a zero state; on the training part,
double-Q n-step targets with value rescaling h(x) = sign(x)(sqrt(|x|+1)-1)
+ 1e-3 x, cut at episode ends, and half the mean squared TD error over the
first ``unroll - n_step`` positions. The replay's importance weights are
not applied.
"""

import jax
import jax.numpy as jnp
import numpy as np

CONVS = ((32, 8, 4), (64, 4, 2), (64, 3, 1))
EPS = 1e-3


def shapes(model: dict):
    """[(path, shape, fan_in or None for a zero bias)] in declaration
    order."""
    out = []
    cin, hw = model["obs_channels"], model["obs_size"]
    for i, (feats, k, s) in enumerate(CONVS):
        out.append((("conv%d" % i, "w"), (k, k, cin, feats), k * k * cin))
        out.append((("conv%d" % i, "b"), (feats,), None))
        cin, hw = feats, (hw - k) // s + 1
    d, a = model["core_dim"], model["num_actions"]
    flat = hw * hw * cin
    out += [(("torso_out", "w"), (flat, d), flat),
            (("torso_out", "b"), (d,), None),
            (("lstm", "wi"), (d, 4 * d), d),
            (("lstm", "wh"), (d, 4 * d), d),
            (("lstm", "b"), (4 * d,), None),
            (("adv", "w"), (d, a), d), (("adv", "b"), (a,), None),
            (("val", "w"), (d, 1), d), (("val", "b"), (1,), None)]
    return out


def init_params(model: dict, seed: int):
    root = jax.random.PRNGKey(seed)
    params = {}
    for i, ((group, leaf), shape, fan) in enumerate(shapes(model)):
        if fan is None:
            x = jnp.zeros(shape, jnp.float32)
        else:
            x = (jax.random.truncated_normal(jax.random.fold_in(root, i),
                                             -2.0, 2.0, shape)
                 * (1.0 / np.sqrt(fan))).astype(jnp.float32)
        params.setdefault(group, {})[leaf] = x
    return params


def forward(params, obs, carry_map=None):
    """obs (B, T, H, W, C) uint8 -> q (B, T, A), from a zero LSTM state,
    computed in the parameters' dtype. ``carry_map`` is applied to the
    (h, c) carried into each step: the check plants a slot-table fault
    there."""
    dt = params["torso_out"]["w"].dtype
    b, t = obs.shape[:2]
    x = obs.reshape((b * t,) + obs.shape[2:]).astype(dt) / 255.0
    for i, (_, _, s) in enumerate(CONVS):
        p = params["conv%d" % i]
        x = jax.lax.conv_general_dilated(
            x, p["w"], (s, s), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(x + p["b"])
    x = x.reshape(b * t, -1)
    e = jax.nn.relu(x @ params["torso_out"]["w"] + params["torso_out"]["b"])
    e = e.reshape(b, t, -1)
    lstm = params["lstm"]
    d = lstm["wh"].shape[0]

    def cell(carry, x_t):
        h, c = carry if carry_map is None else carry_map(carry)
        z = x_t @ lstm["wi"] + h @ lstm["wh"] + lstm["b"]
        i, f, g, o = z[:, :d], z[:, d:2 * d], z[:, 2 * d:3 * d], z[:, 3 * d:]
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    zero = jnp.zeros((b, d), dt)
    _, hs = jax.lax.scan(cell, (zero, zero), jnp.swapaxes(e, 0, 1))
    hs = jnp.swapaxes(hs, 0, 1)
    adv = hs @ params["adv"]["w"] + params["adv"]["b"]
    val = hs @ params["val"]["w"] + params["val"]["b"]
    return val + adv - adv.mean(-1, keepdims=True)


def lane_q(params, obs, carry_map=None, block=16):
    """q (L, T, A) of every lane at every step of its observations
    (L, T, H, W, C), each lane from a zero state, in blocks of ``block``
    lanes (L a multiple of it)."""
    fn = jax.jit(lambda p, o: forward(p, o, carry_map))
    return np.concatenate([np.asarray(fn(params, obs[i:i + block]),
                                      np.float32)
                           for i in range(0, len(obs), block)])


def h(x):
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + EPS * x


def h_inv(x):
    n = jnp.sqrt(1.0 + 4.0 * EPS * (jnp.abs(x) + 1.0 + EPS)) - 1.0
    return jnp.sign(x) * (jnp.square(n / (2.0 * EPS)) - 1.0)


def make_loss(model: dict):
    """loss(params, batch); the batch carries the target net's parameters
    under ``target``."""
    burn, n, gamma = model["burn_in"], model["n_step"], model["gamma"]

    def loss_fn(params, batch):
        q = forward(params, batch["obs"])[:, burn:]
        qt = jax.lax.stop_gradient(
            forward(batch["target"], batch["obs"]))[:, burn:]
        a = batch["actions"][:, burn:]
        r = batch["rewards"][:, burn:].astype(q.dtype)
        d = batch["dones"][:, burn:].astype(q.dtype)
        t = q.shape[1]
        best = jnp.argmax(q, -1)
        q_next = h_inv(jnp.take_along_axis(qt, best[..., None], -1)[..., 0])
        m = t - n
        ret = jnp.zeros_like(r[:, :m])
        alive = jnp.ones_like(ret)
        disc = 1.0
        for i in range(n):
            ret = ret + disc * alive * r[:, i:i + m]
            alive = alive * (1.0 - d[:, i:i + m])
            disc = disc * gamma
        target = jax.lax.stop_gradient(
            h(ret + disc * alive * q_next[:, n:n + m]))
        q_a = jnp.take_along_axis(q, a[..., None], -1)[..., 0][:, :m]
        return 0.5 * jnp.mean(jnp.square(target - q_a))

    return loss_fn
