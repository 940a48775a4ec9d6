"""Pallas TPU kernel for the RG-LRU linear recurrence h_t = a_t h_{t-1} + b_t.

Grid (B, n_width_blocks, n_seq_blocks), seq innermost/sequential; the
hidden state (one row of width-block lanes) is carried in VMEM scratch.
Inside a block the time loop is a lax.fori_loop over rows — sequential in
time (the recurrence is inherently serial) but fully vectorized across the
width lanes, which is how the TPU VPU wants it.

Oracle: repro.kernels.ref.rglru_ref.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, y_ref, h_ref, *, block_s):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def body(t, h):
        # one (1, W) row per step, read and written through the refs:
        # Mosaic lowers a dynamic ref slice but not a dynamic row index
        # into a loaded value
        row = pl.ds(t, 1)
        h = (a_ref[0, row, :].astype(jnp.float32) * h
             + b_ref[0, row, :].astype(jnp.float32))
        y_ref[0, row, :] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_s, body, h_ref[...])
    h_ref[...] = h


def rglru_scan(a, b, *, block_s=256, block_w=None, interpret=False):
    """a, b (B, S, W) -> h sequence (B, S, W)."""
    bsz, s, w = a.shape
    block_s = min(block_s, s)
    block_w = block_w or w
    assert s % block_s == 0 and w % block_w == 0
    kernel = functools.partial(_rglru_kernel, block_s=block_s)
    return pl.pallas_call(
        kernel,
        grid=(bsz, w // block_w, s // block_s),
        in_specs=[
            pl.BlockSpec((1, block_s, block_w), lambda b_, wi, si: (b_, si, wi)),
            pl.BlockSpec((1, block_s, block_w), lambda b_, wi, si: (b_, si, wi)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_w),
                               lambda b_, wi, si: (b_, si, wi)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, w), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        interpret=interpret,
    )(a, b)
