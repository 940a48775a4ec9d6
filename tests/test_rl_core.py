"""V-trace / R2D2 / replay correctness, incl. hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.r2d2 import inv_rescale, n_step_targets, rescale
from repro.core.replay import PrioritizedReplay
from repro.core.vtrace import vtrace

K = jax.random.PRNGKey(3)


# ------------------------------- V-trace -----------------------------------

def _naive_vtrace(tlp, blp, r, d, v, boot, rho_bar=1.0, c_bar=1.0):
    """Direct recursive definition (Espeholt et al. eq. 1)."""
    b, t = r.shape
    rho = np.minimum(rho_bar, np.exp(tlp - blp))
    c = np.minimum(c_bar, np.exp(tlp - blp))
    v_tp1 = np.concatenate([v[:, 1:], boot[:, None]], 1)
    vs = np.zeros((b, t + 1))
    vs[:, t] = boot
    for i in reversed(range(t)):
        delta = rho[:, i] * (r[:, i] + d[:, i] * v_tp1[:, i] - v[:, i])
        vs[:, i] = v[:, i] + delta + d[:, i] * c[:, i] * (
            vs[:, i + 1] - v_tp1[:, i])
    return vs[:, :t]


@settings(deadline=None, max_examples=25)
@given(st.integers(1, 6), st.integers(2, 12), st.integers(0, 2 ** 31 - 1))
def test_vtrace_matches_naive_recursion(b, t, seed):
    rng = np.random.default_rng(seed)
    tlp = rng.normal(size=(b, t)) * 0.3
    blp = rng.normal(size=(b, t)) * 0.3
    r = rng.normal(size=(b, t))
    d = rng.uniform(0.8, 1.0, size=(b, t)) * (rng.random((b, t)) > 0.1)
    v = rng.normal(size=(b, t))
    boot = rng.normal(size=(b,))
    out = vtrace(*map(jnp.asarray, (tlp, blp, r, d, v, boot)))
    expected = _naive_vtrace(tlp, blp, r, d, v, boot)
    np.testing.assert_allclose(np.asarray(out.vs), expected, atol=1e-4)


def test_vtrace_on_policy_reduces_to_nstep_return():
    """On-policy (target == behavior), rho = c = 1: vs_t is the discounted
    Monte-Carlo return bootstrapped at the end."""
    b, t = 2, 8
    lp = jnp.zeros((b, t)) - 0.5
    r = jax.random.normal(K, (b, t))
    gamma = 0.9
    d = jnp.full((b, t), gamma)
    v = jnp.zeros((b, t))
    boot = jnp.zeros((b,))
    out = vtrace(lp, lp, r, d, v, boot)
    expected = np.zeros((b, t))
    acc = np.zeros(b)
    rn = np.asarray(r)
    for i in reversed(range(t)):
        acc = rn[:, i] + gamma * acc
        expected[:, i] = acc
    np.testing.assert_allclose(np.asarray(out.vs), expected, atol=1e-4)


# -------------------------------- R2D2 --------------------------------------

@settings(deadline=None, max_examples=50)
@given(st.floats(-1e4, 1e4))
def test_rescale_invertible(x):
    xr = float(inv_rescale(rescale(jnp.float32(x))))
    assert abs(xr - x) < 1e-2 + 1e-3 * abs(x)


def test_n_step_targets_match_naive():
    b, t, a, n, gamma = 2, 9, 4, 3, 0.9
    q_t = jax.random.normal(K, (b, t, a))
    q_o = jax.random.normal(jax.random.fold_in(K, 1), (b, t, a))
    actions = jax.random.randint(jax.random.fold_in(K, 2), (b, t), 0, a)
    rewards = jax.random.normal(jax.random.fold_in(K, 3), (b, t))
    dones = (jax.random.uniform(jax.random.fold_in(K, 4), (b, t)) < 0.15
             ).astype(jnp.float32)
    tgt = n_step_targets(q_t, q_o, actions, rewards, dones, n_step=n,
                         gamma=gamma)
    qo, qt, rn, dn = map(np.asarray, (q_o, q_t, rewards, dones))
    best = qo.argmax(-1)
    qnext = inv_rescale(np.take_along_axis(qt, best[..., None], -1)[..., 0])
    expected = np.zeros((b, t - n))
    for bi in range(b):
        for ti in range(t - n):
            ret, disc, alive = 0.0, 1.0, 1.0
            for i in range(n):
                ret += disc * alive * rn[bi, ti + i]
                alive *= 1.0 - dn[bi, ti + i]
                disc *= gamma
            ret += disc * alive * qnext[bi, ti + n]
            expected[bi, ti] = rescale(ret)
    np.testing.assert_allclose(np.asarray(tgt), expected, atol=1e-4)


# ------------------------------- replay -------------------------------------

def test_replay_ring_overwrite_and_sampling():
    buf = PrioritizedReplay(capacity=8, alpha=1.0, seed=0)
    for i in range(12):
        buf.add({"x": np.full((3,), i, np.float32)}, priority=1.0)
    assert len(buf) == 8
    batch, idx, w = buf.sample(16, beta=0.5)
    assert batch["x"].shape == (16, 3)
    assert batch["x"].min() >= 4  # first 4 were overwritten
    assert w.shape == (16,) and w.max() <= 1.0 + 1e-6


@settings(deadline=None, max_examples=10)
@given(st.lists(st.floats(0.01, 100.0), min_size=2, max_size=16))
def test_replay_priority_proportionality(priorities):
    buf = PrioritizedReplay(capacity=32, alpha=1.0, seed=1)
    for i, p in enumerate(priorities):
        buf.add({"x": np.float32([i])}, priority=p)
    _, idx, _ = buf.sample(4000, beta=0.0)
    counts = np.bincount(idx, minlength=len(priorities)).astype(float)
    emp = counts / counts.sum()
    expect = np.array(priorities) / np.sum(priorities)
    # loose statistical check on the high-priority items
    top = int(np.argmax(expect))
    assert abs(emp[top] - expect[top]) < 0.12


def test_replay_update_priorities():
    buf = PrioritizedReplay(capacity=4, alpha=1.0, seed=2)
    for i in range(4):
        buf.add({"x": np.float32([i])}, priority=0.001)
    buf.update_priorities(np.array([2]), np.array([1000.0]))
    _, idx, _ = buf.sample(100)
    assert (idx == 2).mean() > 0.9


def _seq(i, frame=6):
    """A sequence whose every key carries ``i``, in the dtypes actors give."""
    return {"obs": np.full((5, frame, frame, 2), i % 256, np.uint8),
            "actions": np.full((5,), i, np.int32),
            "rewards": np.full((5,), i, np.float32),
            "dones": np.full((5,), i % 2, np.bool_)}


class _NumpyRing:
    """The plain host replay: numpy rows, the same priority draw."""

    def __init__(self, capacity, alpha, seed):
        self.capacity, self.alpha = capacity, alpha
        self.rows, self.p = {}, np.zeros((capacity,), np.float64)
        self.next = self.size = 0
        self.rng = np.random.default_rng(seed)

    def add(self, seq, priority):
        for k, v in seq.items():
            self.rows.setdefault(
                k, np.zeros((self.capacity,) + v.shape, v.dtype))[self.next] = v
        self.p[self.next] = max(float(priority), 1e-6) ** self.alpha
        self.next = (self.next + 1) % self.capacity
        self.size = min(self.size + 1, self.capacity)

    def sample(self, batch, beta):
        probs = self.p[:self.size] / self.p[:self.size].sum()
        idx = self.rng.choice(self.size, size=batch, p=probs)
        w = (self.size * probs[idx]) ** (-beta)
        return ({k: v[idx] for k, v in self.rows.items()}, idx,
                (w / w.max()).astype(np.float32))


def test_replay_sample_is_device_arrays_keeping_each_dtype():
    buf = PrioritizedReplay(capacity=4, seed=0)
    for i in range(3):
        buf.add(_seq(i), priority=1.0)
    batch, idx, w = buf.sample(5)
    want = _seq(0)
    assert set(batch) == set(want)
    for k, v in batch.items():
        assert isinstance(v, jax.Array), k
        assert v.devices() == {jax.devices()[0]}, k
        assert v.dtype == want[k].dtype, k
        assert v.shape == (5,) + want[k].shape, k
    assert isinstance(idx, np.ndarray) and isinstance(w, np.ndarray)


def test_replay_matches_a_numpy_ring_after_the_ring_overwrites():
    buf = PrioritizedReplay(capacity=8, alpha=0.9, seed=7)
    ref = _NumpyRing(8, 0.9, seed=7)
    for i in range(13):
        buf.add(_seq(i), priority=1.0 + i % 5)
        ref.add(_seq(i), priority=1.0 + i % 5)
    for _ in range(3):
        batch, idx, w = buf.sample(16, beta=0.6)
        want, want_idx, want_w = ref.sample(16, beta=0.6)
        np.testing.assert_array_equal(idx, want_idx)
        np.testing.assert_array_equal(w, want_w)
        for k in want:
            np.testing.assert_array_equal(np.asarray(batch[k]), want[k])


def test_replay_sample_is_a_snapshot_under_later_adds():
    """A batch left unread keeps the rows it was sampled from after adds
    overwrite every slot."""
    row = 1 << 17                                  # 512 KiB of float32
    buf = PrioritizedReplay(capacity=4, seed=0)
    for i in range(4):
        buf.add({"x": np.full((row,), i, np.float32),
                 "y": np.full((2,), i, np.int32)}, priority=1.0)
    batch, idx, _ = buf.sample(128)
    for i in range(4):
        buf.add({"x": np.full((row,), 100 + i, np.float32),
                 "y": np.full((2,), 100 + i, np.int32)}, priority=1.0)
    x, y = np.asarray(batch["x"]), np.asarray(batch["y"])
    np.testing.assert_array_equal(x, np.broadcast_to(idx[:, None], x.shape))
    np.testing.assert_array_equal(y, np.broadcast_to(idx[:, None], y.shape))
    later, later_idx, _ = buf.sample(4)
    np.testing.assert_array_equal(np.asarray(later["y"])[:, 0],
                                  100 + later_idx)


def test_replay_compiles_nothing_to_add_and_its_gather_once():
    """Adds at every slot, twice, then samples at one batch size: adds
    compile no program and the gather compiles one (row shapes no other
    test uses)."""
    compiled = []

    def listen(event, duration_s, fun_name=None, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(fun_name)

    buf = PrioritizedReplay(capacity=6, seed=0)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for i in range(2 * buf.capacity):          # every slot, twice
            buf.add(_seq(i, frame=7), priority=1.0)
        adds = list(compiled)
        for _ in range(4):
            buf.sample(3)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert adds == [], adds
    assert compiled == ["jit(_stack_rows)"], compiled


def test_replay_batch_reuses_a_step_compiled_on_fresh_arrays():
    """A sampled batch is placed as `jnp` makes arrays, so a step warmed
    up on zeros of its shapes runs it without compiling again."""
    step = jax.jit(lambda b: jax.tree.map(lambda v: v.sum(), b))
    want = _seq(0)
    step({k: jnp.zeros((3,) + v.shape, v.dtype) for k, v in want.items()})
    buf = PrioritizedReplay(capacity=4, seed=0)
    for i in range(4):
        buf.add(_seq(i), priority=1.0)
    for _ in range(2):
        step(buf.sample(3)[0])
    assert step._cache_size() == 1


def test_replay_refuses_a_capacity_the_device_cannot_hold():
    from repro.core.replay import _check_fits

    class Device:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    rows = {"obs": np.zeros((10, 10), np.uint8),
            "rewards": np.zeros((10,), np.float32)}    # 140 bytes a row
    with pytest.raises(ValueError, match="14000 bytes"):
        _check_fits(Device({"bytes_limit": 20000, "bytes_in_use": 6001}),
                    100, rows)
    _check_fits(Device({"bytes_limit": 20000, "bytes_in_use": 6000}),
                100, rows)
    _check_fits(Device(None), 10 ** 9, rows)       # no limit: the CPU
