"""Prioritized sequence replay buffer (R2D2-style), rows on the device.

A ring of fixed-length sequences kept on the default device, where the
learner's jitted step reads them; proportional prioritization p_i^alpha
with importance-sampling weights, drawn on the host. Thread-safe: actors
add() while the learner sample()s — the paper's replay-management task,
which competes with actors for the same host CPU threads. An add puts its
row on the device before it takes the lock and then only files the row
in its slot; a sample draws indices on the host and dispatches one jitted
stack of the drawn rows, so the lock is held for a dispatch at most and
no row is copied on the host. Counters (always on) time every sample,
every add and each add's wait for the lock; with a tracer the same parts
are ``replay/*`` spans.

Each slot holds its row as the array the add put on the device, in the
layout the device gives a row, so a sample copies whole rows into a batch
in the layout the device gives a batch. One (capacity, ...) array per key
would take the device's default layout for that shape, which can make
the slot its minor dimension (a TPU does for R2D2's uint8 frames at 256
slots): on a v5e a row's write into it took 93 ms and a 64-row gather 181
ms. A layout chosen for it by hand is lost when JAX's persistent
compilation cache hands back the programs that write and read it.
"""

import contextlib
import threading
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.telemetry.tracer import maybe_span


@jax.jit
def _stack_rows(rows):
    """{key: [row, ...]} -> {key: batch}, each batch in the device's layout
    for its shape; one program per batch size and row shapes."""
    return {k: jnp.stack(v) for k, v in rows.items()}


def _check_fits(device, capacity: int, rows: dict):
    """Raise if ``capacity`` rows of every key do not fit in what the
    device has free; a device that reports no limit (the CPU) passes."""
    stats = device.memory_stats() or {}
    limit = stats.get("bytes_limit")
    if limit is None:
        return
    need = capacity * sum(r.nbytes for r in rows.values())
    free = limit - stats.get("bytes_in_use", 0)
    if need > free:
        raise ValueError(
            f"replay of {capacity} rows needs {need} bytes on {device}, "
            f"which has {free} of {limit} free")


class PrioritizedReplay:
    def __init__(self, capacity: int, alpha: float = 0.9, seed: int = 0,
                 tracer=None):
        self.capacity = capacity
        self.alpha = alpha
        self._rows: Dict[str, List[jax.Array]] = {}
        self._priorities = np.zeros((capacity,), np.float64)
        self._next = 0
        self._size = 0
        self._lock = threading.Lock()
        self._rng = np.random.default_rng(seed)
        self._tracer = tracer
        # counted under the lock: the seconds of every sample(), its lock
        # wait included; of every add(), its put and lock wait included;
        # and each add()'s wait for the lock
        self.samples = 0
        self.sample_time_s = 0.0
        self.adds = 0
        self.add_time_s = 0.0
        self.add_wait_s = 0.0

    def __len__(self):
        return self._size

    @contextlib.contextmanager
    def _locked(self):
        """Hold the lock; yields the seconds waited for it, the wait traced
        as ``replay/lock_wait``."""
        t0 = time.perf_counter()
        with maybe_span(self._tracer, "replay/lock_wait"):
            self._lock.acquire()
        try:
            yield time.perf_counter() - t0
        finally:
            self._lock.release()

    def add(self, seq: Dict[str, np.ndarray], priority: float):
        t0 = time.perf_counter()
        with maybe_span(self._tracer, "replay/add"):
            # the put happens before the lock, so it never serialises the
            # actors; a lane's view is made contiguous for it. Uncommitted,
            # as `jnp` makes arrays, so the steps that read the batches
            # compile for what their warm-up gave them
            rows = {k: jax.device_put(np.asarray(v, order="C"))
                    for k, v in seq.items()}
            with self._locked() as waited:
                i = self._next
                if not self._rows:
                    _check_fits(jax.devices()[0], self.capacity, rows)
                    self._rows = {k: [None] * self.capacity for k in rows}
                for k, r in rows.items():
                    self._rows[k][i] = r
                self._priorities[i] = max(float(priority), 1e-6) ** self.alpha
                self._next = (i + 1) % self.capacity
                self._size = min(self._size + 1, self.capacity)
                self.adds += 1
                self.add_wait_s += waited
                self.add_time_s += time.perf_counter() - t0

    def sample(self, batch: int, beta: float = 0.6):
        """(rows, idx, w): every key's rows at ``idx`` stacked into arrays
        on the device, a snapshot taken at this call that later adds do
        not change (the stack reads the row arrays it was handed, which
        stay alive until it has run); ``idx`` and the importance weights
        ``w`` as numpy."""
        tr = self._tracer
        t0 = time.perf_counter()
        with maybe_span(tr, "replay/sample"), self._locked():
            n = self._size
            assert n > 0, "empty replay"
            p = self._priorities[:n]
            probs = p / p.sum()
            idx = self._rng.choice(n, size=batch, p=probs)
            w = (n * probs[idx]) ** (-beta)
            w = w / w.max()
            with maybe_span(tr, "replay/gather"):
                out = _stack_rows({k: [rows[j] for j in idx]
                                   for k, rows in self._rows.items()})
            self.samples += 1
            self.sample_time_s += time.perf_counter() - t0
            return out, idx, w.astype(np.float32)

    def update_priorities(self, idx: np.ndarray, priorities: np.ndarray):
        with self._lock:
            self._priorities[idx] = np.maximum(priorities, 1e-6) ** self.alpha
