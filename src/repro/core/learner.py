"""Learner loop: sample -> train_step -> publish params.

The learner is the accelerator-resident half of SEED: it consumes
trajectory batches (prioritized replay for R2D2, on-policy queue for
V-trace), runs the jitted/pjitted train_step, and publishes fresh params
to the inference server under a version counter. Periodic checkpointing
and restart-on-failure live here (see repro.checkpoint). Always-on
counters time each step's parts; with telemetry each part is also a
``learner/*`` span. A step's scalar metrics stay on the device: they
cross to the host once, when something reads `Learner.metrics`."""

import queue
import threading
import time
import traceback
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from repro.telemetry.tracer import maybe_span


class BatchSourceClosed(Exception):
    """Raised by a batch_fn whose source was poisoned by `Learner.stop()`
    (e.g. a closed on-policy trajectory queue); `_loop` treats it as a
    clean shutdown, not an error."""


class Learner:
    def __init__(self, train_step: Callable, state, batch_fn: Callable,
                 publish: Optional[Callable] = None,
                 checkpoint_manager=None, checkpoint_every: int = 0,
                 checkpoint_every_s: float = 0.0,
                 priority_update: Optional[Callable] = None,
                 poison: Optional[Callable] = None,
                 telemetry=None):
        """batch_fn() -> (batch, info) blocking; publish(params, step).

        ``poison()`` is called from `stop()` to unblock a batch_fn that is
        waiting on an empty source (the batch_fn should then raise
        `BatchSourceClosed`); without it a blocking source would hang the
        learner thread past `join`'s timeout forever. Polling batch_fns
        can instead watch `stopped` and raise `BatchSourceClosed`
        themselves.
        """
        self.train_step = train_step
        self.state = state
        self.batch_fn = batch_fn
        self.publish = publish
        self.ckpt = checkpoint_manager
        self.checkpoint_every = checkpoint_every
        # wall-clock checkpoint cadence (0 disables): the live-loop fault
        # tolerance knob — step-based cadence stalls when steps stall,
        # which is exactly when a crash costs the most un-checkpointed work
        self.checkpoint_every_s = checkpoint_every_s
        self._last_ckpt_t = time.perf_counter()
        self.priority_update = priority_update
        self.poison = poison
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.steps = 0
        # the last step's scalar metrics, left on the device; `metrics`
        # pulls them once per step when read (`metrics_pulls` counts that)
        self._step_metrics: Dict[str, Any] = {}
        self._pulled: tuple = (self._step_metrics, {})
        self._pull_lock = threading.Lock()
        self.metrics_pulls = 0
        # always-on counters: seconds waiting for a batch, training on it
        # (its transfer to the device, inside the train step's call,
        # included) and after the step (see _post_step)
        self.wait_time_s = 0.0
        self.train_time_s = 0.0
        self.post_time_s = 0.0
        self.error: Optional[str] = None     # traceback of a fatal loop error
        # timings are already taken in _one_step; telemetry adds the
        # distribution (p50/p95/p99) view and the spans of each step
        self._tracer = (telemetry.tracer
                        if telemetry is not None and telemetry.enabled
                        else None)
        if telemetry is not None:
            self._h_train = telemetry.metrics.histogram("learner/train_s")
            self._h_wait = telemetry.metrics.histogram("learner/wait_s")
        else:
            self._h_train = None
            self._h_wait = None
        self._health = getattr(telemetry, "health", None)

    @property
    def metrics(self) -> Dict[str, float]:
        """The last step's scalar metrics as floats: one transfer of the
        whole dict at the first read after a step, cached until the next."""
        with self._pull_lock:           # readers only; the loop never waits
            dev = self._step_metrics
            held, values = self._pulled
            if held is not dev:
                with maybe_span(self._tracer, "learner/metrics_pull"):
                    values = {k: float(v)
                              for k, v in jax.device_get(dev).items()}
                self.metrics_pulls += 1
                self._pulled = (dev, values)
            return values

    @property
    def stopped(self) -> bool:
        """True once stop() was called (or the loop died); batch_fns that
        poll-and-sleep must check this so stop() can interrupt the wait."""
        return self._stop.is_set()

    def start(self):
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self):
        self._stop.set()
        if self.poison is not None:
            self.poison()

    def join(self, timeout=30.0):
        if self._thread:
            self._thread.join(timeout=timeout)

    def run_steps(self, n: int):
        for _ in range(n):
            self._one_step()

    def _one_step(self):
        tr = self._tracer
        with maybe_span(tr, "learner/step"):
            t0 = time.perf_counter()
            with maybe_span(tr, "learner/batch"):
                batch, info = self.batch_fn()
            t1 = time.perf_counter()
            with maybe_span(tr, "learner/train"):
                self.state, metrics = self.train_step(self.state, batch)
                jax.block_until_ready(self.state["step"])
            t2 = time.perf_counter()
            self.wait_time_s += t1 - t0
            self.train_time_s += t2 - t1
            self.steps += 1
            if self._h_train is not None:
                self._h_wait.record(t1 - t0)
                self._h_train.record(t2 - t1)
            with maybe_span(tr, "learner/post"):
                self._post_step(tr, metrics, info)
                # the step's last references: freeing them is part of
                # the iteration, not of the time between two of them
                del batch, metrics, info
            self.post_time_s += time.perf_counter() - t2

    def _post_step(self, tr, metrics, info):
        """What follows a train step: the scalar metrics kept (on the
        device, by reference), the replay priorities, the publish and the
        checkpoint."""
        self._step_metrics = {k: v for k, v in metrics.items()
                              if np.ndim(v) == 0}
        if self.priority_update and "priorities" in metrics:
            with maybe_span(tr, "learner/priority_update"):
                self.priority_update(info, np.asarray(metrics["priorities"]))
        if self.publish:
            with maybe_span(tr, "learner/publish"):
                self.publish(self.state["params"], self.steps)
        if self.ckpt:
            with maybe_span(tr, "learner/checkpoint"):
                self._maybe_checkpoint()

    def _maybe_checkpoint(self):
        if self.checkpoint_every and self.steps % self.checkpoint_every == 0:
            self.ckpt.save(self.state, self.steps)
        elif self.checkpoint_every_s and time.perf_counter() \
                - self._last_ckpt_t >= self.checkpoint_every_s:
            # async: hands off a host snapshot and keeps training — the
            # save must not stall the accelerator (see CheckpointManager)
            self.ckpt.save(self.state, self.steps)
            self._last_ckpt_t = time.perf_counter()

    def _loop(self):
        # A bare `except queue.Empty` would let any other exception kill the
        # thread silently; record it so the system can surface the death.
        hb = self._health
        if hb is not None:
            # generous deadline: the first train_step pays jit compile
            # (seconds), and an empty trajectory queue legitimately
            # blocks batch_fn — only a truly wedged learner should flag
            hb.register("learner", stale_after_s=30.0)
        try:
            while not self._stop.is_set():
                if hb is not None:
                    hb.beat("learner")
                try:
                    self._one_step()
                except queue.Empty:
                    continue
                except BatchSourceClosed:
                    break             # poisoned batch source: clean shutdown
                except Exception:
                    self.error = traceback.format_exc()
                    self._stop.set()
                    break
        finally:
            if hb is not None:
                hb.unregister("learner")
