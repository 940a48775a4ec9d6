"""Environment wrappers the benchmark hands to the system as its env factory.

`TimedEnv` wraps a host env. It mixes the run's seed into every lane's
seed, and records the start and end of each `step()` on the monotonic
clock, which all processes of one machine share. The gap between two
successive step starts of one lane is one actor iteration: the env steps of
all the actor's lanes plus its inference round trip. In a spawned actor
host the samples go to files under ``sample_dir``; in-process they stay in
`LOCAL`.

`SeededJaxEnv` wraps a pure-JAX env for the fused device scans: it folds
the run's seed into each lane's reset key, so the episodes follow the seed.

Both are module-level classes with picklable arguments, so a spawned actor
host can rebuild them.
"""

import contextlib
import importlib
import os
import time

import numpy as np

LOCAL = []                 # in-process TimedEnv instances
FLUSH_EVERY_S = 0.25
_ANNOTATE = [None]         # jax.profiler.TraceAnnotation, when tracing


def resolve(target: str):
    """'package.module:Name' -> the object."""
    module, name = target.split(":")
    return getattr(importlib.import_module(module), name)


def mix_seed(*parts) -> int:
    """A 31-bit seed from any whole numbers (the run's seed may exceed 32
    bits, which JAX's PRNGKey would silently wrap)."""
    return int(np.random.SeedSequence([int(p) for p in parts])
               .generate_state(1)[0] >> 1)


def set_annotation(annotate):
    """Wrap each env step in ``annotate("bench/env_step")`` from now on."""
    _ANNOTATE[0] = annotate


class TimedEnv:
    def __init__(self, target: str, kwargs: dict, seed: int,
                 sample_dir=None):
        self.inner = resolve(target)(**kwargs)
        self.seed = seed
        self.num_actions = self.inner.num_actions
        self.obs_shape = self.inner.obs_shape
        self.auto_resets = getattr(self.inner, "auto_resets", False)
        self.sample_dir = sample_dir
        self._starts, self._ends = [], []
        self._last_flush = time.perf_counter()
        self._file = None
        self.reseed(kwargs.get("seed", 0))
        if sample_dir is None:
            LOCAL.append(self)

    def reseed(self, lane_seed: int):
        self.inner.reseed(mix_seed(self.seed, lane_seed))

    def reset(self):
        return self.inner.reset()

    def step(self, action):
        t0 = time.perf_counter()
        ann = _ANNOTATE[0]
        with (ann("bench/env_step") if ann else contextlib.nullcontext()):
            out = self.inner.step(action)
        t1 = time.perf_counter()
        self._starts.append(t0)
        self._ends.append(t1)
        if self.sample_dir is not None and t1 - self._last_flush \
                > FLUSH_EVERY_S:
            self._flush(t1)
        return out

    def _flush(self, now):
        if self._file is None:
            os.makedirs(self.sample_dir, exist_ok=True)
            self._file = open(os.path.join(
                self.sample_dir, f"{os.getpid()}-{id(self)}.f64"), "ab")
        np.asarray([self._starts, self._ends], np.float64).T.tofile(
            self._file)
        self._file.flush()
        self._starts, self._ends = [], []
        self._last_flush = now

    def samples(self):
        """(n, 2) array of [start, end] of the steps held in memory."""
        return np.asarray([self._starts, self._ends], np.float64).T


def read_samples(sample_dir=None):
    """Per-lane (n, 2) [start, end] arrays: from the files a spawned host
    wrote under ``sample_dir``, or from the in-process instances."""
    if sample_dir is None:
        return [e.samples() for e in LOCAL]
    out = []
    if os.path.isdir(sample_dir):
        for name in sorted(os.listdir(sample_dir)):
            a = np.fromfile(os.path.join(sample_dir, name), np.float64)
            out.append(a.reshape(-1, 2))
    return out


class SeededJaxEnv:
    """A pure-JAX env whose reset key has the run's seed folded in."""

    def __init__(self, target: str, kwargs: dict, seed: int):
        self.inner = resolve(target)(**kwargs)
        self.seed = mix_seed(seed)
        self.num_actions = self.inner.num_actions
        self.obs_shape = self.inner.obs_shape

    def reset(self, key):
        import jax
        return self.inner.reset(jax.random.fold_in(key, self.seed))

    def step(self, state, action):
        return self.inner.step(state, action)
