"""Prioritized sequence replay buffer (R2D2-style), host-side.

Numpy ring buffer storing fixed-length sequences; proportional
prioritization p_i^alpha with importance-sampling weights. Thread-safe:
actors add() while the learner sample()s — the paper's replay-management
task, which competes with actors for the same host CPU threads. Counters
(always on) time every sample and every add's wait for the lock; with a
tracer the same parts are ``replay/*`` spans.
"""

import contextlib
import threading
import time
from typing import Dict

import numpy as np

from repro.telemetry.tracer import maybe_span


class PrioritizedReplay:
    def __init__(self, capacity: int, alpha: float = 0.9, seed: int = 0,
                 tracer=None):
        self.capacity = capacity
        self.alpha = alpha
        self._storage: Dict[str, np.ndarray] = {}
        self._priorities = np.zeros((capacity,), np.float64)
        self._next = 0
        self._size = 0
        self._lock = threading.Lock()
        self._rng = np.random.default_rng(seed)
        self._tracer = tracer
        # counted under the lock: the seconds of every sample(), its lock
        # wait included, and each add()'s wait for the lock
        self.samples = 0
        self.sample_time_s = 0.0
        self.adds = 0
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
        with maybe_span(self._tracer, "replay/add"), \
                self._locked() as waited:
            self.adds += 1
            self.add_wait_s += waited
            i = self._next
            if not self._storage:
                for k, v in seq.items():
                    v = np.asarray(v)
                    self._storage[k] = np.zeros((self.capacity,) + v.shape, v.dtype)
            for k, v in seq.items():
                self._storage[k][i] = v
            self._priorities[i] = max(float(priority), 1e-6) ** self.alpha
            self._next = (i + 1) % self.capacity
            self._size = min(self._size + 1, self.capacity)

    def sample(self, batch: int, beta: float = 0.6):
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
                out = {k: v[idx].copy() for k, v in self._storage.items()}
            self.samples += 1
            self.sample_time_s += time.perf_counter() - t0
            return out, idx, w.astype(np.float32)

    def update_priorities(self, idx: np.ndarray, priorities: np.ndarray):
        with self._lock:
            self._priorities[idx] = np.maximum(priorities, 1e-6) ** self.alpha
