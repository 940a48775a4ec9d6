"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode.

That they compile for the chip is `tests/test_tpu_compile.py`'s job."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as R

K = jax.random.PRNGKey(7)


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape).astype(dtype)


def _fold(x):
    b, s, h, d = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(b * h, s, d)


def _unfold(x, b, h):
    bh, s, d = x.shape
    return jnp.moveaxis(x.reshape(b, h, s, d), 1, 2)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("s,d,dtype", [(128, 64, jnp.float32),
                                       (256, 128, jnp.float32),
                                       (128, 64, jnp.bfloat16)])
@pytest.mark.parametrize("window,softcap", [(0, None), (64, None), (0, 30.0)])
def test_flash_attention(s, d, dtype, window, softcap):
    b, h = 2, 2
    q = _rand(K, (b, s, h, d), dtype)
    k = _rand(jax.random.fold_in(K, 1), (b, s, h, d), dtype)
    v = _rand(jax.random.fold_in(K, 2), (b, s, h, d), dtype)
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              softcap=softcap, block_q=64, block_k=64,
                              interpret=True)
    ref = _unfold(R.attention_ref(_fold(q), _fold(k), _fold(v), causal=True,
                                  window=window, softcap=softcap), b, h)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("s,dtype", [(256, jnp.float32), (512, jnp.bfloat16)])
def test_decode_attention(s, dtype):
    b, h, d = 3, 4, 64
    q = _rand(K, (b, h, d), dtype)
    k = _rand(jax.random.fold_in(K, 1), (b, s, h, d), dtype)
    v = _rand(jax.random.fold_in(K, 2), (b, s, h, d), dtype)
    lens = jnp.array([s // 4, s // 2, s], jnp.int32)
    out = ops.decode_attention(q, k, v, lens, block_s=128, interpret=True)
    ref = R.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("s,p,n,chunk", [(128, 16, 32, 32), (256, 32, 16, 64)])
def test_ssd_scan(s, p, n, chunk):
    b, h = 2, 3
    x = _rand(K, (b, s, h, p), jnp.float32) * 0.5
    dt = jax.nn.softplus(_rand(jax.random.fold_in(K, 1), (b, s, h), jnp.float32))
    a = -jnp.exp(_rand(jax.random.fold_in(K, 2), (h,), jnp.float32) * 0.3)
    bb = _rand(jax.random.fold_in(K, 3), (b, s, h, n), jnp.float32) * 0.3
    cc = _rand(jax.random.fold_in(K, 4), (b, s, h, n), jnp.float32) * 0.3
    y = ops.ssd_scan(x, dt, a, bb, cc, chunk=chunk, interpret=True)
    yref, _ = R.ssd_ref(x, dt, a, bb, cc)
    scale = float(jnp.abs(yref).max())
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                               atol=3e-5 * max(scale, 1.0), rtol=1e-4)


@pytest.mark.parametrize("s,w,block_s", [(128, 64, 32), (256, 128, 64)])
def test_rglru_scan(s, w, block_s):
    b = 2
    a = jax.nn.sigmoid(_rand(K, (b, s, w), jnp.float32))
    bb = _rand(jax.random.fold_in(K, 1), (b, s, w), jnp.float32) * 0.1
    h = ops.rglru_scan(a, bb, block_s=block_s, interpret=True)
    href, _ = R.rglru_ref(a, bb)
    np.testing.assert_allclose(np.asarray(h), np.asarray(href),
                               atol=1e-5, rtol=1e-5)


def test_nn_chunked_attention_matches_ref():
    """The jnp chunked path (dry-run default) equals the full-scores ref."""
    from repro.nn.attention import attend_chunked, attend_ref
    b, s, hq, hk, d = 2, 96, 4, 2, 32
    q = _rand(K, (b, s, hq, d), jnp.float32)
    k = _rand(jax.random.fold_in(K, 1), (b, s, hk, d), jnp.float32)
    v = _rand(jax.random.fold_in(K, 2), (b, s, hk, d), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    out = attend_chunked(q, k, v, pos, pos, scale=0.2, chunk=32)
    ke = jnp.repeat(k, 2, axis=2)
    ve = jnp.repeat(v, 2, axis=2)
    ref = attend_ref(q, ke, ve, pos, pos, scale=0.2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_nn_ssd_chunked_matches_ref():
    from repro.nn.ssd import ssd_chunked
    b, s, h, p, n = 2, 64, 2, 8, 16
    x = _rand(K, (b, s, h, p), jnp.float32) * 0.5
    dt = jax.nn.softplus(_rand(jax.random.fold_in(K, 1), (b, s, h), jnp.float32))
    a = -jnp.exp(_rand(jax.random.fold_in(K, 2), (h,), jnp.float32) * 0.3)
    bb = _rand(jax.random.fold_in(K, 3), (b, s, 1, n), jnp.float32) * 0.3
    cc = _rand(jax.random.fold_in(K, 4), (b, s, 1, n), jnp.float32) * 0.3
    y, st = ssd_chunked(x, dt, a, bb, cc, chunk=16)
    yref, stref = R.ssd_ref(x, dt, a, jnp.repeat(bb, h, 2), jnp.repeat(cc, h, 2))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref), atol=3e-5,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(stref), atol=3e-5,
                               rtol=1e-4)
