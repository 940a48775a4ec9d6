"""R2D2 on the host backend, in-process: actor threads step `TimedEnv`
lanes, the central inference server batches their requests into the
conv-LSTM policy with its per-lane LSTM slot table, unrolls land in
prioritized replay, and the learner trains from it and publishes params.

The system is the program's own `build_r2d2_system`. The benchmark sets
its seed, hands it the env factory, and wraps three of its callables: the
learner's train step and the policy step (to keep their first steps and
calls for the check) and the learner's batch source (host spans in the
profiler's trace, with the other two).
"""

import functools
import gc
import time

import compare
import flops
import harness
import timed_env
from cells.capture import PolicyCapture, StepCapture
from reference import common, r2d2 as ref


class Cell:
    def __init__(self, config, traffic, *, seed, out_dir, annotate, fault):
        import repro.core.r2d2_agent as agent
        from repro.configs.r2d2_atari import AtariConfig

        self.config, self.traffic = config, traffic
        self.model = config["model"]
        self.batch = config["learner_batch"]
        self.seq_len = self.model["burn_in"] + self.model["unroll"]
        self.lanes_per_actor = traffic["envs_per_actor"]
        self.first_steps = traffic["first_steps"]
        self.prog_seed = timed_env.mix_seed(seed)
        self.step_flops = flops.r2d2_step_flops(self.model, self.batch)
        self.fwd_flops = flops.r2d2_forward_flops(self.model)
        timed_env.LOCAL.clear()
        timed_env.set_annotation(annotate)
        env = functools.partial(timed_env.TimedEnv, traffic["env"],
                                traffic["env_kwargs"], seed)
        agent.SEED = self.prog_seed
        self.system = agent.build_r2d2_system(
            AtariConfig(**self.model), env,
            num_actors=traffic["num_actors"],
            envs_per_actor=traffic["envs_per_actor"],
            learner_batch=self.batch,
            replay_capacity=config["replay_capacity"],
            min_replay=traffic["min_replay"])
        learner, server = self.system.learner, self.system.server
        self.capture = StepCapture(learner, self.first_steps, annotate,
                                   fault)
        learner.batch_fn = harness.wrap(learner.batch_fn, annotate,
                                        "bench/learner_batch")
        self.policy = PolicyCapture(server.policy_step, self.capture,
                                    self.model["num_actions"], fault)
        server.policy_step = harness.wrap(self.policy, annotate,
                                          "bench/policy_step")
        self._thread = self._box = None

    def start(self, seconds):
        self._thread, self._box = harness.run_in_thread(
            lambda: self.system.run(seconds=seconds))

    def ready(self):
        s = self.system
        return (s.learner.steps >= self.first_steps
                and all(a.iterations > 0 for a in s.actors))

    def progress(self):
        s = self.system
        return (f"replay {len(s.replay)}, learner steps {s.learner.steps}, "
                f"actor iterations {[a.iterations for a in s.actors]}")

    def error(self):
        s = self.system
        return (self._box.get("error") or s.learner.error or s.server.error
                or next((a.error for a in s.actors if a.error), None))

    def counters(self):
        s = self.system
        srv = s.server.stats
        steps = s.learner.steps
        lanes = srv["requests"]
        return {
            "t": time.perf_counter(),
            "learner_steps": steps,
            "learner_train_s": s.learner.train_time_s,
            "learner_wait_s": s.learner.wait_time_s,
            "frames_trained": steps * self.batch * self.seq_len,
            "env_frames": sum(a.frames for a in s.actors),
            "infer_batches": srv["batches"],
            "infer_lanes": lanes,
            "infer_queue_wait_s": srv["queue_wait_s"],
            "infer_compute_s": srv["compute_s"],
            "model_flops": steps * self.step_flops + lanes * self.fwd_flops,
        }

    def join(self):
        self._thread.join()
        return self._box.get("result")

    def errors(self, stats):
        out = [self._box["error"]] if "error" in self._box else []
        if stats:
            out += [stats[k] for k in ("learner_error", "inference_error")
                    if stats.get(k)]
        return out

    def actor_samples(self):
        return timed_env.read_samples()

    def check(self, controls=False):
        """Free the program, then follow its first steps with the plain
        reference, and replay each lane's policy calls made on the initial
        weights through the reference's LSTM from a zero state. Returns
        {"program": {name: (value, where)}}, and with ``controls`` the same
        numbers read from the bfloat16 control, from the reference fed half
        of each batch and from the reference with a slot-table fault, each
        put in the program's place."""
        prog = self.capture.program_side()
        batches = self.capture.batches
        served = compare.lane_histories(self.policy.calls)
        self.system = self.capture = self.policy = None
        timed_env.LOCAL.clear()
        gc.collect()

        def reference(batches, dtype=common.F32):
            return harness.host_array(run_reference(
                self.model, self.config, self.prog_seed, batches, dtype))
        out = compare.readings(prog, batches, reference,
                               self.config["adam_b1"], controls)
        params = ref.init_params(self.model, self.prog_seed)

        def q_fn(dtype=common.F32, carry=None):
            return policy_q(params, served["obs"], dtype, carry)
        policy = compare.policy_readings(served, q_fn, self.prog_seed,
                                         self.config["epsilon"], controls)
        for kind, numbers in policy.items():
            out.setdefault(kind, {}).update(numbers)
        return out


def reference_batches(batches, target):
    """The program's batches as the reference takes them; the target net
    stays at the initial weights over the first steps."""
    return [{"target": target, **{k: b[k] for k in
                                  ("obs", "actions", "rewards", "dones")}}
            for b in batches]


def run_reference(model, config, seed, batches, dtype=common.F32):
    params = ref.init_params(model, seed)
    return common.train(ref.make_loss(model), params,
                        reference_batches(batches, params), config, dtype)


def _shuffle(carry):
    """Each lane's state taken from its neighbour's slot row."""
    import jax.numpy as jnp
    return tuple(jnp.roll(x, 1, axis=0) for x in carry)


def _stale(carry):
    """The state never written back: every step starts from zero."""
    import jax.numpy as jnp
    return tuple(jnp.zeros_like(x) for x in carry)


def policy_q(params, obs, dtype=common.F32, carry=None):
    """The reference's Q at every lane and step, at ``highest`` precision
    in float32, or in bfloat16 at the default (the control); ``carry``
    plants a slot-table fault (``shuffle`` or ``stale``)."""
    import jax
    carry_map = {None: None, "shuffle": _shuffle, "stale": _stale}[carry]
    if dtype == common.F32:
        with jax.default_matmul_precision("highest"):
            return ref.lane_q(params, obs, carry_map)
    return ref.lane_q(common.cast_floats(params, dtype), obs, carry_map)
