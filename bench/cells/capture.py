"""Keeps the learner's first steps and the policy's first calls for the
check.

`StepCapture` takes the learner's place as its train step: every step
still goes through the program's own call on the batch its own feed made;
the first ``n`` batches, losses and resulting states are kept. ``fault``
plants a fault underneath, for the tests that must see `correct` fail:
``state_unchanged`` returns the state it was given, ``half_batch`` leaves
the second half of the batch's rows out.

`PolicyCapture` takes the inference server's place as its policy step, and
keeps every call made on the initial weights: those that start before the
learner's first step does and end before it has published. Its fault:
``token_altered`` serves each action plus one.
"""

import threading

import numpy as np

import harness

FAULTS = ("state_unchanged", "half_batch")
POLICY_FAULTS = ("token_altered",)


class StepCapture:
    def __init__(self, learner, n, annotate, fault=None):
        if fault not in (None,) + FAULTS + POLICY_FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.inner = learner.train_step
        learner.train_step = self
        self.n, self.annotate = n, annotate
        self.fault = fault if fault in FAULTS else None
        self.state0 = None
        self.started = threading.Event()
        self.published = threading.Event()
        self.batches, self.states, self.losses = [], [], []

    def __call__(self, state, batch):
        if self.state0 is None:
            self.state0 = state
        self.started.set()
        fed = batch
        if self.fault == "half_batch":
            fed = {k: v[: len(v) // 2] for k, v in batch.items()}
        with harness.annotated(self.annotate, "bench/train_step"):
            new, metrics = self.inner(state, fed)
        self.published.set()
        if self.fault == "state_unchanged":
            new = state
        if self.fault == "half_batch" and "priorities" in metrics:
            # replay still gets a priority for every row it handed out
            p = np.asarray(metrics["priorities"])
            metrics = dict(metrics, priorities=np.concatenate([p, p]))
        if len(self.batches) < self.n:
            self.batches.append(batch)
            self.states.append(new)
            self.losses.append(metrics["loss"])
        return new, metrics

    def program_side(self) -> dict:
        """Host copies of what the comparison reads from the program."""
        return {
            "losses": [float(x) for x in self.losses],
            "params0": harness.host_array(self.state0["params"]),
            "m1": harness.host_array(self.states[0]["opt_state"]["m"]),
            "params": harness.host_array(self.states[-1]["params"]),
        }


class PolicyCapture:
    def __init__(self, inner, steps: StepCapture, num_actions, fault=None):
        self.inner, self.steps = inner, steps
        self.num_actions = num_actions
        self.fault = fault if fault in POLICY_FAULTS else None
        self.calls = []
        self.closed = False

    def __call__(self, obs, ids):
        keep = not self.closed and not self.steps.started.is_set()
        actions = np.asarray(self.inner(obs, ids))
        if self.fault == "token_altered":
            actions = (actions + 1) % self.num_actions
        if keep and not self.steps.published.is_set():
            self.calls.append((np.array(ids), np.array(obs),
                               np.array(actions)))
        else:
            self.closed = True
        return actions
