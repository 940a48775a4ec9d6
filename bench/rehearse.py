"""Compile each cell's device programs at their real sizes for a described
TPU v5e, with no chip attached, and print what the compiler says of their
memory. Run it on a CPU-only machine before sending a cell to the chip:

    JAX_PLATFORMS=cpu python3 bench/rehearse.py

It compiles the program's R2D2 train step at the cell's batch, its policy
step at every batch the inference server can form, the reference's train
step (float32 at highest precision, as the check runs it), and the
V-trace train step and fused rollout scan. Nothing runs, so it gives no
time.
"""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import harness  # noqa: E402
from reference import r2d2 as ref_r2d2  # noqa: E402
from reference import vtrace_mlp as ref_vtrace  # noqa: E402


def shaped(tree, sharding):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        np.shape(x), jnp.asarray(x).dtype if not hasattr(x, "dtype")
        else x.dtype, sharding=sharding), tree)


def report(name, fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    m = compiled.memory_analysis()
    row = {"program": name,
           "argument_bytes": m.argument_size_in_bytes,
           "output_bytes": m.output_size_in_bytes,
           "temp_bytes": m.temp_size_in_bytes,
           "generated_code_bytes": m.generated_code_size_in_bytes}
    print(json.dumps(row), flush=True)
    return row


def r2d2(one):
    from repro.configs.r2d2_atari import AtariConfig
    from repro.core.losses import init_train_state, make_train_step
    from repro.models.atari import make_atari
    from repro.optim import adamw

    cell = harness.load_cell("r2d2_atari.inproc")
    cfg, tr = cell["config"], cell["traffic"]
    acfg = AtariConfig(**cfg["model"])
    bundle = make_atari(acfg)
    opt = adamw(cfg["learning_rate"])
    state = jax.eval_shape(lambda: init_train_state(
        bundle, opt, jax.random.PRNGKey(0), with_target=True))
    state = shaped(state, one)
    b, t = cfg["learner_batch"], acfg.burn_in + acfg.unroll
    hw = (acfg.obs_size, acfg.obs_size, acfg.obs_channels)
    batch = {
        "obs": jax.ShapeDtypeStruct((b, t) + hw, jnp.uint8, sharding=one),
        "actions": jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=one),
        "rewards": jax.ShapeDtypeStruct((b, t), jnp.float32, sharding=one),
        "dones": jax.ShapeDtypeStruct((b, t), jnp.float32, sharding=one),
        "core": (jax.ShapeDtypeStruct((b, acfg.core_dim), jnp.float32,
                                      sharding=one),) * 2,
    }
    report("r2d2 train step", make_train_step(bundle, opt, algo="r2d2",
                                              acfg=acfg), state, batch)
    for k in range(1, tr["num_actors"] + 1):
        n = k * tr["envs_per_actor"]
        obs = jax.ShapeDtypeStruct((n,) + hw, jnp.uint8, sharding=one)
        core = jax.ShapeDtypeStruct((n, acfg.core_dim), jnp.float32,
                                    sharding=one)

        def policy(params, obs, h, c):
            q, (h2, c2) = bundle.decode_step(params, obs, (h, c))
            return jnp.argmax(q, -1), h2, c2
        report(f"r2d2 policy step, {n} lanes", policy, state["params"], obs,
               core, core)

    params = shaped(jax.eval_shape(
        lambda: ref_r2d2.init_params(cfg["model"], 0)), one)
    loss = ref_r2d2.make_loss(cfg["model"])
    rb = {k: batch[k] for k in ("obs", "actions", "rewards", "dones")}
    rb["target"] = params

    def ref_step(p, rb):
        return jax.value_and_grad(loss)(p, rb)
    with jax.default_matmul_precision("highest"):
        report("r2d2 reference step (f32, highest)", ref_step, params, rb)


def vtrace(one):
    from repro.envs.catch import CatchEnv
    from repro.onpolicy import VTraceLearner, mlp_actor_critic
    from repro.optim import adamw
    from repro.rollout import DeviceRolloutEngine

    cell = harness.load_cell("vtrace_mlp.device")
    cfg, tr = cell["config"], cell["traffic"]
    env = CatchEnv()
    obs_dim, a = env.obs_shape[0], env.num_actions
    init_fn, apply_fn = mlp_actor_critic(obs_dim, a, cfg["model"]["hidden"])
    vl = VTraceLearner(apply_fn, adamw(cfg["learning_rate"]))
    params = jax.eval_shape(lambda: ref_vtrace.init_params(
        cfg["model"], obs_dim, a, 0))
    state = shaped(jax.eval_shape(lambda: vl.init_state(params)), one)
    b, t = cfg["learner_batch"], cfg["model"]["unroll"]

    def f(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)
    batch = {"obs": f((b, t, obs_dim), jnp.float32),
             "actions": f((b, t), jnp.int32),
             "rewards": f((b, t), jnp.float32),
             "discounts": f((b, t), jnp.float32),
             "behavior_logprobs": f((b, t), jnp.float32),
             "param_version": f((b,), jnp.int32)}
    report("vtrace train step", vl.train_step, state, batch)
    eng = DeviceRolloutEngine(CatchEnv, vl.device_policy_apply(),
                              tr["envs_per_worker"], t, with_logprobs=True)
    carry = jax.eval_shape(lambda: (
        eng._reset(jax.random.split(jax.random.PRNGKey(0),
                                    tr["envs_per_worker"]))[0], None,
        jnp.zeros((tr["envs_per_worker"], obs_dim)),
        jax.random.PRNGKey(0)))
    report("vtrace fused rollout scan",
           eng._build(vl.device_policy_apply(), t), state["params"],
           shaped(carry, one))
    loss = ref_vtrace.make_loss(cfg["model"])
    rb = {k: batch[k] for k in ("obs", "actions", "rewards", "discounts",
                                "behavior_logprobs")}
    with jax.default_matmul_precision("highest"):
        report("vtrace reference step (f32, highest)",
               lambda p, rb: jax.value_and_grad(loss)(p, rb),
               state["params"], rb)


def main():
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    r2d2(one)
    vtrace(one)


if __name__ == "__main__":
    main()
