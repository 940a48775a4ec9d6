"""What the plain references share: Adam with global-norm clipping, three
training steps, and the precision they compute in.

A reference computes in float32 with every matmul and convolution at
``highest`` precision, so on a TPU nothing drops to bfloat16 passes. The
control computes the same steps with its parameters and inputs in
bfloat16 (the master copy and Adam stay in float32), the step below the
configuration's float32 that a later change could be tempted to take.
"""

import jax
import jax.numpy as jnp

F32 = "float32"
BF16 = "bfloat16"


def cast_floats(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


def adam(opt: dict):
    """init(params) and update(grads, m, v, params, step) -> (params, m, v,
    clipped grads), as ``opt`` states: learning_rate, adam_b1, adam_b2,
    adam_epsilon, max_grad_norm."""
    lr, b1, b2 = opt["learning_rate"], opt["adam_b1"], opt["adam_b2"]
    eps, clip = opt["adam_epsilon"], opt["max_grad_norm"]

    def init(params):
        z = jax.tree.map(jnp.zeros_like, params)
        return z, z

    def update(grads, m, v, params, step):
        leaves = jax.tree.leaves(grads)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
        scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
        grads = jax.tree.map(lambda g: g * scale, grads)
        t = step + 1.0
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
        params = jax.tree.map(
            lambda p, m_, v_: p - lr * (m_ / (1 - b1 ** t))
            / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps), params, m, v)
        return params, m, v, grads

    return init, update


def train(loss_fn, params, batches, opt: dict, dtype: str = F32) -> dict:
    """Follow the program's first ``len(batches)`` steps from ``params``.

    ``loss_fn(params, batch) -> loss`` is traced at highest precision; with
    ``dtype="bfloat16"`` its parameters and float inputs are cast down
    first and it runs at the backend's default precision. Returns the loss
    of each step, the clipped gradient of the first step and the
    parameters after each step, all in float32.
    """
    init, update = adam(opt)

    def lossf(p, batch):
        if dtype == F32:
            return loss_fn(p, batch)
        return loss_fn(cast_floats(p, jnp.bfloat16),
                       cast_floats(batch, jnp.bfloat16)).astype(jnp.float32)

    @jax.jit
    def step(p, m, v, batch, i):
        loss, grads = jax.value_and_grad(lossf)(p, batch)
        grads = cast_floats(grads, jnp.float32)
        p, m, v, clipped = update(grads, m, v, p, i)
        return p, m, v, loss, clipped

    precision = "highest" if dtype == F32 else "default"
    m, v = init(params)
    out = {"losses": [], "params": [params]}
    for i, batch in enumerate(batches):
        with jax.default_matmul_precision(precision):
            params, m, v, loss, clipped = step(params, m, v, batch, float(i))
        out["losses"].append(float(loss))
        out["params"].append(params)
        if i == 0:
            out["grads"] = clipped
    return out
