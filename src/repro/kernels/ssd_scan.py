"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid (batch*heads, n_chunks) with the chunk axis innermost/sequential: the
(P, N) SSM state lives in VMEM scratch and is carried across chunk steps,
so the inter-chunk recurrence costs no HBM round-trips. Within a chunk the
work is three MXU matmuls (C·Bᵀ, M·X, Xᵀ·(w⊙B)) over an (L, L) tile —
exactly the SSD insight (quadratic-attention duality) mapped to the MXU.

Oracle: repro.kernels.ref.ssd_ref (sequential recurrence).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk):
    bi = pl.program_id(0)
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    a = a_ref[bi]                                    # scalar A (negative)
    x = x_ref[0, 0].astype(jnp.float32)              # (L, P)
    dt = dt_ref[0, 0].astype(jnp.float32)            # (1, L) row
    b = b_ref[0, 0].astype(jnp.float32)              # (L, N)
    c = c_ref[0, 0].astype(jnp.float32)              # (L, N)

    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = rows >= cols                            # [t, s]: s <= t
    # inclusive cumsum of dt*a as a matmul against the causal mask, at
    # full f32 precision: the sums are exponentiated below
    da = dt * a                                      # (1, L)
    cs_row = jax.lax.dot_general(da, causal.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST)  # (1, L)
    cs_col = cs_row.reshape(chunk, 1)                # (L, 1)
    total = jnp.sum(da)                              # cs at the last step
    # intra-chunk: M[t,s] = (C_t.B_s) * exp(cs_t - cs_s) * dt_s,  s <= t
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())))   # (L, L)
    decay = cs_col - cs_row
    m = scores * jnp.exp(jnp.where(causal, decay, -jnp.inf)) * dt
    y = jax.lax.dot(m, x)                            # (L, P)

    # inter-chunk: y += exp(cs_t) * C_t . S_prev
    state = state_ref[...]                           # (P, N)
    y += jnp.exp(cs_col) * jax.lax.dot_general(
        c, state, (((1,), (1,)), ((), ())))          # (L, P)

    # state update: S = exp(sum da) * S + X^T (w ⊙ B), w_s = exp(cs_L - cs_s) dt_s
    w = jnp.exp(total - cs_row) * dt                 # (1, L)
    upd = jax.lax.dot_general(x, w.reshape(chunk, 1) * b,
                              (((0,), (0,)), ((), ())))
    state_ref[...] = jnp.exp(total) * state + upd
    y_ref[0, 0, ...] = y.astype(y_ref.dtype)


def ssd_scan(x, dt, a, b, c, *, chunk=256, interpret=False):
    """x (BH, S, P); dt (BH, S); a (BH,); b,c (BH, S, N). Groups/heads are
    pre-expanded and folded into the leading dim. Returns y (BH, S, P)."""
    bh, s, p = x.shape
    n = b.shape[-1]
    assert s % chunk == 0
    nc = s // chunk
    xc = x.reshape(bh, nc, chunk, p)
    dtc = dt.reshape(bh, nc, 1, chunk)
    bc = b.reshape(bh, nc, chunk, n)
    cc = c.reshape(bh, nc, chunk, n)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            # whole (BH,) vector in SMEM: a (1,) block is not tile-aligned
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, p), lambda b_, ci: (b_, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b_, ci: (b_, ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda b_, ci: (b_, ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda b_, ci: (b_, ci, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p), lambda b_, ci: (b_, ci, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, nc, chunk, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(a, xc, dtc, bc, cc)
    return y.reshape(bh, s, p)
