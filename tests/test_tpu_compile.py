"""Compile for a described TPU v5e, with no chip attached.

The TPU compiler is installed with jaxlib and compiles for a topology that
is described, not present: it refuses what the chip would refuse (block
shapes off the tiling, lowerings Mosaic lacks, programs that overflow the
device), which interpret mode never sees. Nothing runs, so these tests say
nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.r2d2_atari import AtariConfig
from repro.core.losses import init_train_state, make_train_step
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.ssd_scan import ssd_scan
from repro.models.atari import make_atari
from repro.nn.recurrent import lstm_state_init
from repro.optim import adamw


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


KERNELS = {
    # (BH, S, D) prefill attention, 128-wide blocks
    "flash_attention": (
        flash_attention,
        [((32, 2048, 128), jnp.bfloat16)] * 3),
    # one query token per row against a 4k-token cache, lengths in SMEM
    "decode_attention": (
        decode_attention,
        [((8, 8, 128), jnp.bfloat16), ((8, 4096, 8, 128), jnp.bfloat16),
         ((8, 4096, 8, 128), jnp.bfloat16), ((8,), jnp.int32)]),
    # Mamba2-style heads: P=64, N=128, 256-step chunks, A in SMEM
    "ssd_scan": (
        lambda x, dt, a, b, c: ssd_scan(x, dt, a, b, c, chunk=256),
        [((64, 2048, 64), jnp.float32), ((64, 2048), jnp.float32),
         ((64,), jnp.float32), ((64, 2048, 128), jnp.float32),
         ((64, 2048, 128), jnp.float32)]),
    # RecurrentGemma-2B width (2560) over 2k steps
    "rglru_scan": (
        rglru_scan,
        [((8, 2048, 2560), jnp.float32)] * 2),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_pallas_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    _, hlo = _compile(fn, *args)
    assert "tpu_custom_call" in hlo, f"{name}: no Mosaic kernel in the HLO"


def test_r2d2_full_width_train_step_compiles_for_v5e(one_chip):
    acfg = AtariConfig()
    bundle = make_atari(acfg)
    opt = adamw(5e-4)
    state = jax.eval_shape(
        lambda k: init_train_state(bundle, opt, k, with_target=True),
        jax.random.PRNGKey(0))
    b, t = 8, acfg.burn_in + acfg.unroll
    frame = (acfg.obs_size, acfg.obs_size, acfg.obs_channels)
    batch = {
        "obs": jax.ShapeDtypeStruct((b, t) + frame, jnp.uint8),
        "actions": jax.ShapeDtypeStruct((b, t), jnp.int32),
        "rewards": jax.ShapeDtypeStruct((b, t), jnp.float32),
        "dones": jax.ShapeDtypeStruct((b, t), jnp.float32),
        "core": jax.eval_shape(lambda: lstm_state_init(b, acfg.core_dim)),
    }
    step = make_train_step(bundle, opt, algo="r2d2", acfg=acfg)
    compiled, _ = _compile(step, _shapes(state, one_chip),
                           _shapes(batch, one_chip))
    mem = compiled.memory_analysis()
    # the step and its temporaries fit the 16 GB of one v5e with room left
    assert mem.temp_size_in_bytes < 4 * 2 ** 30, mem


def test_r2d2_policy_step_compiles_for_v5e_at_256_lanes(one_chip):
    acfg = AtariConfig()
    bundle = make_atari(acfg)
    params = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    lanes = 256
    obs = jax.ShapeDtypeStruct(
        (lanes, acfg.obs_size, acfg.obs_size, acfg.obs_channels), jnp.uint8)
    core = jax.eval_shape(lambda: lstm_state_init(lanes, acfg.core_dim))

    def policy(p, o, h, c):
        q, state = bundle.decode_step(p, o, (h, c))
        return jnp.argmax(q, -1), state

    _compile(policy, _shapes(params, one_chip), _shapes(obs, one_chip),
             *_shapes(core, one_chip))
