"""Jitted public wrappers for the Pallas kernels.

The kernels compile for the TPU. `interpret=True` runs the kernel body in
Python per grid step instead — bit-accurate for validating against the
oracles on a CPU, slow for big shapes (tests use small sweeps). Nothing
picks interpret mode on the caller's behalf. The pure-jnp paths in
repro.nn remain the default for CPU execution and for the dry-run cost
accounting.
"""

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.ssd_scan import ssd_scan as _ssd
from repro.kernels.rglru_scan import rglru_scan as _rglru


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=None,
                    block_q=128, block_k=128, interpret=False):
    """(B,S,H,D) attention; KV heads must equal Q heads (expand first)."""
    b, s, h, d = q.shape
    fold = lambda x: jnp.moveaxis(x, 2, 1).reshape(b * h, s, d)
    out = _flash(fold(q), fold(k), fold(v), causal=causal, window=window,
                 softcap=softcap, block_q=block_q, block_k=block_k,
                 interpret=interpret)
    return jnp.moveaxis(out.reshape(b, h, s, d), 1, 2)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(q, k, v, lengths, *, block_s=512, interpret=False):
    """q (B,H,D); k,v (B,S,H,D); lengths (B,)."""
    return _decode(q, k, v, lengths, block_s=block_s, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, b, c, *, chunk=128, interpret=False):
    """x (B,S,H,P); dt (B,S,H); a (H,); b,c (B,S,H,N) head-expanded."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    fold = lambda t: jnp.moveaxis(t, 2, 1).reshape(bsz * h, s, t.shape[-1])
    xf = fold(x)
    dtf = jnp.moveaxis(dt, 2, 1).reshape(bsz * h, s)
    af = jnp.tile(a, bsz)
    y = _ssd(xf, dtf, af, fold(b), fold(c), chunk=chunk,
             interpret=interpret)
    return jnp.moveaxis(y.reshape(bsz, h, s, p), 1, 2)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def rglru_scan(a, b, *, block_s=256, interpret=False):
    return _rglru(a, b, block_s=block_s, interpret=interpret)
