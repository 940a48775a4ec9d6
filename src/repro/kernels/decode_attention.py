"""Pallas TPU decode attention (one query token vs. a long KV cache).

Decode attention is HBM-bandwidth-bound: the kernel streams KV blocks
through VMEM once, carrying the online-softmax state in scratch. Grid:
(B, H_blocks, S_blocks) with the S axis innermost/sequential. Handles
variable valid length (cache fill level) via masking.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale, block_s, block_h, n_s):
    bi = pl.program_id(0)
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)               # (bh, d)
    k = k_ref[0].astype(jnp.float32)               # (bs, bh, d)
    v = v_ref[0].astype(jnp.float32)
    # one query row per head: multiply-reduce on the VPU. The head axis sits
    # between S and D in the cache layout, and Mosaic only lowers batched
    # dots whose batch axis leads both operands
    s = jnp.sum(q[None] * k, axis=-1) * scale      # (bs, bh)
    valid = (si * block_s + jax.lax.broadcasted_iota(jnp.int32, (block_s, block_h), 0)
             ) < len_ref[bi]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=0))
    p = jnp.exp(s - m_new[None, :])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=0)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.sum(p[:, :, None] * v, axis=0)
    m_ref[...] = m_new

    @pl.when(si == n_s - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(q, k, v, lengths, *, scale=None, block_s=512,
                     block_h=None, interpret=False):
    """q (B,H,D); k,v (B,S,H,D) head-expanded cache; lengths (B,) int32."""
    b, h, d = q.shape
    s = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    block_s = min(block_s, s)
    block_h = block_h or h
    assert s % block_s == 0 and h % block_h == 0
    n_s, n_h = s // block_s, h // block_h

    kernel = functools.partial(_decode_kernel, scale=scale, block_s=block_s,
                               block_h=block_h, n_s=n_s)
    return pl.pallas_call(
        kernel,
        grid=(b, n_h, n_s),
        in_specs=[
            # the whole (B,) lengths vector sits in SMEM; a (1,) block per
            # batch row is not tile-aligned and Mosaic refuses it
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_h, d), lambda b_, hi, si: (b_, hi, 0)),
            pl.BlockSpec((1, block_s, block_h, d),
                         lambda b_, hi, si: (b_, si, hi, 0)),
            pl.BlockSpec((1, block_s, block_h, d),
                         lambda b_, hi, si: (b_, si, hi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_h, d), lambda b_, hi, si: (b_, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_h,), jnp.float32),
            pltpu.VMEM((block_h,), jnp.float32),
            pltpu.VMEM((block_h, d), jnp.float32),
        ],
        interpret=interpret,
    )(lengths, q, k, v)
