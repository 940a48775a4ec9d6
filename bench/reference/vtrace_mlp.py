"""Plain reference of the V-trace actor-critic (Espeholt et al. 2018) with
a one-hidden-layer MLP policy, written out from the configuration file.

Weights (made by the benchmark from the seed and handed to the program):
``w1`` normal over sqrt(obs_dim), ``wp`` and ``wv`` normal times 0.01,
biases zero, each from its own split of ``PRNGKey(seed)``.

Loss: logits and values for every step of each unroll; the last step only
bootstraps. With rho = min(rho_bar, pi/mu) and c = min(c_bar, pi/mu),
v_s - V(x_s) = sum_t gamma-discounted products of c times
delta_t = rho_t (r_t + gamma_t V(x_{t+1}) - V(x_t)); the policy gradient
uses rho_s (r_s + gamma_s v_{s+1} - V(x_s)). Loss = policy term
+ baseline_cost * 0.5 * mean (v_s - V)^2 - entropy_cost * mean entropy.
"""

import jax
import jax.numpy as jnp
import numpy as np


def init_params(model: dict, obs_dim: int, num_actions: int, seed: int):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    hdim = model["hidden"]
    return {
        "w1": jax.random.normal(k1, (obs_dim, hdim)) / np.sqrt(obs_dim),
        "b1": jnp.zeros((hdim,)),
        "wp": jax.random.normal(k2, (hdim, num_actions)) * 0.01,
        "bp": jnp.zeros((num_actions,)),
        "wv": jax.random.normal(k3, (hdim, 1)) * 0.01,
        "bv": jnp.zeros((1,)),
    }


def forward(params, obs):
    x = jax.nn.relu(obs @ params["w1"] + params["b1"])
    return x @ params["wp"] + params["bp"], (x @ params["wv"]
                                             + params["bv"])[..., 0]


def logprob_of(params, obs, actions):
    logits, _ = forward(params, obs)
    logp = jax.nn.log_softmax(logits)
    return jnp.take_along_axis(logp, actions[..., None], -1)[..., 0]


def make_loss(model: dict):
    rho_bar, c_bar = model["rho_bar"], model["c_bar"]

    def loss_fn(params, batch):
        dt = params["w1"].dtype
        logits, values = forward(params, batch["obs"].astype(dt))
        logp = jax.nn.log_softmax(logits)
        taken = jnp.take_along_axis(
            logp, batch["actions"][..., None], -1)[..., 0]
        entropy = -jnp.sum(jax.nn.softmax(logits) * logp, -1)
        tlp, v = taken[:, :-1], values[:, :-1]
        boot = values[:, -1]
        ratio = jnp.exp(tlp - batch["behavior_logprobs"][:, :-1].astype(dt))
        rho, c = jnp.minimum(rho_bar, ratio), jnp.minimum(c_bar, ratio)
        r = batch["rewards"][:, :-1].astype(dt)
        disc = batch["discounts"][:, :-1].astype(dt)
        v_next = jnp.concatenate([v[:, 1:], boot[:, None]], 1)
        delta = rho * (r + disc * v_next - v)
        t = v.shape[1]
        acc = jnp.zeros_like(boot)
        rows = [None] * t
        for s in reversed(range(t)):
            acc = delta[:, s] + disc[:, s] * c[:, s] * acc
            rows[s] = acc
        vs = jax.lax.stop_gradient(v + jnp.stack(rows, 1))
        vs_next = jnp.concatenate([vs[:, 1:], boot[:, None]], 1)
        adv = jax.lax.stop_gradient(rho * (r + disc * vs_next - v))
        pg = -jnp.mean(tlp * adv)
        baseline = 0.5 * jnp.mean(jnp.square(vs - v))
        return (pg + model["baseline_cost"] * baseline
                - model["entropy_cost"] * jnp.mean(entropy[:, :-1]))

    return loss_fn
