"""V-trace with the MLP actor-critic, laid out as `SeedSystem` takes it:

* ``backend: device``: rollout workers drive fused env+policy scans over
  pure-JAX lanes (`SeededJaxEnv`), bypassing central inference and the
  wire; unrolls go through the on-policy queue to the learner.
* ``backend: host`` with ``transport: shm``: actors in spawned host
  processes step `TimedEnv` lanes and reach the central inference server
  (a `SamplingPolicy`) through gateways over shared-memory rings; their
  unrolls come back over the wire into the same queue. The hosts' env
  wrappers write their step times to files the harness reads.

The benchmark makes the weights from the seed (the same function the
reference starts from) and builds the learner bundle and the system the
way their callers in the program do. It wraps the learner's train step
(to keep its first steps) and its batch source, and each engine's
rollout (host spans in the profiler's trace).
"""

import functools
import gc
import os
import time

import numpy as np

import compare
import flops
import harness
import timed_env
from cells.capture import StepCapture
from reference import common, vtrace_mlp as ref


def make_params(model, obs_dim, num_actions, seed):
    import jax
    return jax.jit(functools.partial(ref.init_params, model, obs_dim,
                                     num_actions))(seed)


class Cell:
    def __init__(self, config, traffic, *, seed, out_dir, annotate, fault):
        from repro.core.system import SeedSystem
        from repro.onpolicy import VTraceLearner, mlp_actor_critic
        from repro.optim import adamw

        self.config, self.traffic = config, traffic
        self.model = model = config["model"]
        self.batch = config["learner_batch"]
        self.first_steps = traffic["first_steps"]
        self.prog_seed = timed_env.mix_seed(seed)
        self.device = traffic["backend"] == "device"
        if self.device:
            self.lanes_per_actor = None
            self.sample_dir = None
            env = functools.partial(timed_env.SeededJaxEnv, traffic["env"],
                                    traffic["env_kwargs"], seed)
        else:
            self.lanes_per_actor = traffic["envs_per_actor"]
            self.sample_dir = f"{out_dir}/samples"
            env = functools.partial(timed_env.TimedEnv, traffic["env"],
                                    traffic["env_kwargs"], seed,
                                    self.sample_dir)
        probe = env()
        self.obs_dim, self.num_actions = probe.obs_shape[0], probe.num_actions
        self.step_flops = flops.mlp_step_flops(model, self.obs_dim,
                                               self.num_actions, self.batch)
        self.policy_flops = flops.mlp_policy_flops(model, self.obs_dim,
                                                   self.num_actions)
        init_fn, apply_fn = mlp_actor_critic(self.obs_dim, self.num_actions,
                                             model["hidden"])
        vl = VTraceLearner(apply_fn, adamw(config["learning_rate"]),
                           rho_bar=model["rho_bar"], c_bar=model["c_bar"],
                           value_coef=model["baseline_cost"],
                           entropy_coef=model["entropy_cost"])
        state = vl.init_state(make_params(model, self.obs_dim,
                                          self.num_actions, self.prog_seed))
        vl.warmup(state, batch_size=self.batch, unroll=model["unroll"],
                  obs_shape=(self.obs_dim,))
        common_kw = dict(env_factory=env, unroll=model["unroll"],
                         algo="vtrace", train_step=vl.train_step,
                         state=state, learner_batch=self.batch,
                         gamma=model["gamma"],
                         queue_capacity=traffic["queue_capacity"])
        if self.device:
            self.system = SeedSystem(
                backend="device", policy_apply=vl.device_policy_apply(),
                num_actors=traffic["num_workers"],
                envs_per_actor=traffic["envs_per_worker"], **common_kw)
            self.system.warmup()
            for w in self.system.actors:
                w.engine.rollout = harness.wrap(w.engine.rollout, annotate,
                                                "bench/rollout")
        else:
            policy = vl.sampling_policy(state["params"], seed=self.prog_seed)
            actors, lanes = traffic["num_actors"], traffic["envs_per_actor"]
            # a replica batches whole requests of the actors routed to it:
            # warm every lane count it can form
            per_replica = -(-actors // traffic["num_replicas"])
            for k in range(1, per_replica + 1):
                policy(np.zeros((k * lanes, self.obs_dim), np.float32), None)
            self.system = SeedSystem(
                policy_step=policy, num_actors=actors,
                envs_per_actor=lanes, transport=traffic["transport"],
                num_actor_hosts=traffic["num_hosts"],
                num_gateways=traffic["num_gateways"],
                num_replicas=traffic["num_replicas"],
                policy_publish=policy.publish, **common_kw)
            self.system.server.policy_step = harness.wrap(
                self.system.server.policy_step, annotate,
                "bench/policy_step")
        learner = self.system.learner
        self.capture = StepCapture(learner, self.first_steps, annotate,
                                   fault)
        learner.batch_fn = harness.wrap(learner.batch_fn, annotate,
                                        "bench/learner_batch")
        self._thread = self._box = None

    def start(self, seconds):
        self._thread, self._box = harness.run_in_thread(
            lambda: self.system.run(seconds=seconds))

    def ready(self):
        if self.system.learner.steps < self.first_steps:
            return False
        if self.device:
            return True
        # every host has stepped its lanes (its env wrappers have flushed)
        pids = {n.split("-")[0] for n in _listdir(self.sample_dir)}
        return len(pids) >= self.traffic["num_hosts"]

    def progress(self):
        s = self.system
        return (f"learner steps {s.learner.steps}, "
                f"ledger {s.onpolicy_queue.stats()}")

    def error(self):
        s = self.system
        return (self._box.get("error") or s.learner.error
                or (s.server.error if s.server else None)
                or next((a.error for a in s.actors if a.error), None))

    def counters(self):
        s = self.system
        led = s.onpolicy_queue.stats()
        steps = s.learner.steps
        out = {
            "t": time.perf_counter(),
            "learner_steps": steps,
            "learner_train_s": s.learner.train_time_s,
            "learner_wait_s": s.learner.wait_time_s,
            "frames_trained": led["frames_trained"],
            "frames_generated": led["frames_generated"],
            "frames_dropped": led["frames_dropped"],
        }
        if self.device:
            forwards = out["rollout_frames"] = sum(a.frames
                                                   for a in s.actors)
        else:
            srv = s.server.stats
            forwards = out["infer_lanes"] = srv["requests"]
            out.update(infer_batches=srv["batches"],
                       infer_queue_wait_s=srv["queue_wait_s"],
                       infer_compute_s=srv["compute_s"])
        out["model_flops"] = (steps * self.step_flops
                              + forwards * self.policy_flops)
        return out

    def join(self):
        self._thread.join()
        stats = self._box.get("result")
        self.ledger = stats["onpolicy"] if stats else None
        return stats

    def errors(self, stats):
        out = [self._box["error"]] if "error" in self._box else []
        if stats:
            out += [stats[k] for k in ("learner_error", "inference_error")
                    if stats.get(k)]
            out += stats.get("host_errors", [])
        return out

    def actor_samples(self):
        return None if self.device else timed_env.read_samples(
            self.sample_dir)

    def check(self, controls=False):
        """Free the program, then follow its first steps with the plain
        reference (see `compare.readings`), check the scans' behaviour
        logprobs against the reference policy at the version each unroll
        was made with, and check that the frame ledger closed exactly."""
        prog = self.capture.program_side()
        batches = self.capture.batches
        self.system = self.capture = None
        gc.collect()

        def reference(batches, dtype=common.F32):
            return harness.host_array(run_reference(
                self.model, self.config, self.obs_dim, self.num_actions,
                self.prog_seed, batches, dtype))
        out = compare.readings(prog, batches, reference,
                               self.config["adam_b1"], controls)
        params = out["reference"]["params"]
        # a fused scan runs one version of the params throughout; a host
        # actor's unroll spans every publish made while it ran, so only
        # the first batch, made before any publish, has one version
        seen = batches if self.device else batches[:1]
        out["program"]["logprob_gap"] = logprob_gap(params, seen)
        if controls:
            out["control"]["logprob_gap"] = logprob_gap(
                params, seen, behaviour=common.BF16)
        led = self.ledger
        out["program"]["ledger_gap"] = (float(
            abs(led["frames_generated"] - led["frames_trained"]
                - led["frames_dropped"] - led["frames_pending"])
            + led["frames_pending"]), "after close")
        return out


def _listdir(path):
    return os.listdir(path) if os.path.isdir(path) else []


def reference_batches(batches):
    return [{k: b[k] for k in ("obs", "actions", "rewards", "discounts",
                               "behavior_logprobs")} for b in batches]


def run_reference(model, config, obs_dim, num_actions, seed, batches,
                  dtype=common.F32):
    import jax
    params = jax.tree.map(np.asarray, make_params(model, obs_dim,
                                                  num_actions, seed))
    return common.train(ref.make_loss(model), params,
                        reference_batches(batches), config, dtype)


def logprob_gap(ref_params, batches, behaviour=None):
    """Largest gap between a behaviour logprob the scan recorded and the
    reference policy's logprob of that action, under the reference's
    parameters of the version the unroll was stamped with. Unrolls made
    under versions the reference did not reach are skipped. With
    ``behaviour="bfloat16"`` the recorded logprobs are replaced by the
    reference's own in bfloat16: the control's reading."""
    import jax
    import jax.numpy as jnp
    worst, where = 0.0, "no unroll of a version the reference reached"
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            for v in np.unique(b["param_version"]):
                if v >= len(ref_params):
                    continue
                rows = b["param_version"] == v
                p, obs = ref_params[int(v)], b["obs"][rows]
                lp = np.asarray(ref.logprob_of(p, obs, b["actions"][rows]))
                if behaviour is None:
                    seen = b["behavior_logprobs"][rows]
                else:
                    # a bfloat16 policy hands back bfloat16 logprobs
                    seen = np.asarray(ref.logprob_of(
                        common.cast_floats(p, jnp.bfloat16),
                        obs.astype(jnp.bfloat16), b["actions"][rows])
                        .astype(jnp.bfloat16), np.float32)
                gap = float(np.max(np.abs(lp - seen)))
                if gap >= worst:
                    worst, where = gap, f"batch {i + 1}, version {int(v)}"
    return worst, where
