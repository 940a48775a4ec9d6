"""IMPALA's deep ResNet-LSTM under V-trace on the device backend, laid out
as `SeedSystem` takes it: rollout workers drive fused env+policy scans
over pure-JAX ALESim lanes (`SeededJaxEnv` around `ALESimJaxEnv`), each
scan recording the core every lane held before its first step; unrolls
go through the on-policy queue to the learner, which unrolls the network
from those cores. The scans and the learner share one chip: one Anakin
replica without its cross-core gradient all-reduce.

The benchmark makes the weights from the seed (the same function the
reference starts from) and builds the learner bundle and the system the
way their callers in the program do. It wraps the learner's train step
(to keep its first steps) and its batch source, and each engine's rollout
(host spans in the profiler's trace). Besides `cells.capture`'s faults it
plants ``core_zeroed``: every unroll's recorded core zeroed in the
batches the learner is fed.
"""

import functools
import gc
import time

import numpy as np

import compare
import flops_impala
import harness
import timed_env
from cells.capture import StepCapture
from reference import common, impala_deep as ref

CELL_FAULTS = ("core_zeroed",)


def make_params(model, seed):
    import jax
    return jax.jit(functools.partial(ref.init_params, model))(seed)


def network_config(model):
    from repro.configs.impala_atari import ImpalaConfig
    return ImpalaConfig(obs_size=model["obs_size"],
                        obs_channels=model["obs_channels"],
                        num_actions=model["num_actions"],
                        channels=tuple(model["channels"]),
                        res_blocks=model["res_blocks"],
                        fc_dim=model["fc_dim"], core_dim=model["core_dim"])


def zero_cores(batches):
    return [dict(b, core=np.zeros_like(b["core"])) for b in batches]


class Cell:
    def __init__(self, config, traffic, *, seed, out_dir, annotate, fault):
        from repro.core.system import SeedSystem
        from repro.models.impala import impala_actor_critic
        from repro.onpolicy import VTraceLearner
        from repro.optim import adamw

        self.config, self.traffic = config, traffic
        self.model = model = config["model"]
        self.batch = config["learner_batch"]
        self.first_steps = traffic["first_steps"]
        self.prog_seed = timed_env.mix_seed(seed)
        env = functools.partial(timed_env.SeededJaxEnv, traffic["env"],
                                traffic["env_kwargs"], seed)
        probe = env()
        shape = (model["obs_size"], model["obs_size"], model["obs_channels"])
        if tuple(probe.obs_shape) != shape or \
                probe.num_actions != model["num_actions"]:
            raise ValueError(f"env gives {probe.obs_shape} frames and "
                             f"{probe.num_actions} actions; the network "
                             f"takes {shape} and {model['num_actions']}")
        self.step_flops = flops_impala.step_flops(model, self.batch)
        self.policy_flops = flops_impala.policy_flops(model)
        _, apply_fn, init_core = impala_actor_critic(network_config(model))
        opt = adamw(config["learning_rate"], b1=config["adam_b1"],
                    b2=config["adam_b2"], eps=config["adam_epsilon"],
                    max_grad_norm=config["max_grad_norm"])
        vl = VTraceLearner(apply_fn, opt, init_core=init_core,
                           rho_bar=model["rho_bar"], c_bar=model["c_bar"],
                           value_coef=model["baseline_cost"],
                           entropy_coef=model["entropy_cost"])
        state = vl.init_state(make_params(model, self.prog_seed))
        vl.warmup(state, batch_size=self.batch, unroll=model["unroll"],
                  obs_shape=shape, obs_dtype=np.uint8)
        self.system = SeedSystem(
            env_factory=env, backend="device",
            policy_apply=vl.device_policy_apply(), init_core=init_core,
            num_actors=traffic["num_workers"],
            envs_per_actor=traffic["envs_per_worker"],
            unroll=model["unroll"], algo="vtrace", train_step=vl.train_step,
            state=state, learner_batch=self.batch, gamma=model["gamma"],
            queue_capacity=traffic["queue_capacity"])
        self.system.warmup()
        for w in self.system.actors:
            w.engine.rollout = harness.wrap(w.engine.rollout, annotate,
                                            "bench/rollout")
        learner = self.system.learner
        self.capture = StepCapture(
            learner, self.first_steps, annotate,
            None if fault in CELL_FAULTS else fault)
        inner = feed = learner.batch_fn
        if fault == "core_zeroed":
            def feed():
                batch, aux = inner()
                return zero_cores([batch])[0], aux
        learner.batch_fn = harness.wrap(feed, annotate,
                                        "bench/learner_batch")
        self._thread = self._box = None

    def start(self, seconds):
        self._thread, self._box = harness.run_in_thread(
            lambda: self.system.run(seconds=seconds))

    def ready(self):
        return self.system.learner.steps >= self.first_steps

    def progress(self):
        s = self.system
        return (f"learner steps {s.learner.steps}, "
                f"ledger {s.onpolicy_queue.stats()}")

    def error(self):
        s = self.system
        return (self._box.get("error") or s.learner.error
                or next((a.error for a in s.actors if a.error), None))

    def counters(self):
        s = self.system
        led = s.onpolicy_queue.stats()
        steps = s.learner.steps
        frames = sum(a.frames for a in s.actors)
        return {
            "t": time.perf_counter(),
            "learner_steps": steps,
            "learner_train_s": s.learner.train_time_s,
            "learner_wait_s": s.learner.wait_time_s,
            "frames_trained": led["frames_trained"],
            "frames_generated": led["frames_generated"],
            "frames_dropped": led["frames_dropped"],
            "rollout_frames": frames,
            "rollout_scans": sum(a.iterations for a in s.actors),
            "rollout_scan_s": sum(a.scan_time_s for a in s.actors),
            "traj_bytes": sum(a.fetch_bytes for a in s.actors),
            "model_flops": (steps * self.step_flops
                            + frames * self.policy_flops),
        }

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
        return out

    def actor_samples(self):
        return None

    def check(self, controls=False):
        """Free the program, then follow its first steps with the plain
        reference (see `compare.readings`), check the scans' behaviour
        logprobs against the reference network unrolled from each
        unroll's recorded core at the version the unroll was made with,
        and check that the frame ledger closed exactly. With
        ``controls``, also the logprobs read from zeroed cores (the
        ``core_zeroed`` fault)."""
        prog = self.capture.program_side()
        batches = self.capture.batches
        self.system = self.capture = None
        gc.collect()
        params0 = harness.host_array(make_params(self.model, self.prog_seed))
        loss = ref.make_loss(self.model)

        def reference(batches, dtype=common.F32):
            return harness.host_array(common.train(
                loss, params0, [{k: b[k] for k in ref.BATCH_KEYS}
                                for b in batches], self.config, dtype))
        out = compare.readings(prog, batches, reference,
                               self.config["adam_b1"], controls)
        params = out["reference"]["params"]
        out["program"]["logprob_gap"] = logprob_gap(self.model, params,
                                                    batches)
        if controls:
            out["control"]["logprob_gap"] = logprob_gap(
                self.model, params, batches, behaviour=common.BF16)
            out["core_zeroed"] = {"logprob_gap": logprob_gap(
                self.model, params, zero_cores(batches))}
        led = self.ledger
        out["program"]["ledger_gap"] = (float(
            abs(led["frames_generated"] - led["frames_trained"]
                - led["frames_dropped"] - led["frames_pending"])
            + led["frames_pending"]), "after close")
        return out


def logprob_gap(model, ref_params, batches, behaviour=None):
    """Largest gap between a behaviour logprob the scan recorded and the
    reference network's logprob of that action, unrolled from the
    unroll's recorded core under the reference's parameters of the
    version the unroll was stamped with. Unrolls made under versions the
    reference did not reach are skipped. With ``behaviour="bfloat16"``
    the recorded logprobs are replaced by the reference's own in
    bfloat16: the control's reading."""
    import jax
    import jax.numpy as jnp
    keys = ("obs", "actions", "core", "prev_action", "prev_reward", "first")
    lp_fn = jax.jit(functools.partial(ref.logprob_of, model))
    worst, where = 0.0, "no unroll of a version the reference reached"
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            rb = {k: b[k] for k in keys}
            for v in np.unique(b["param_version"]):
                if v >= len(ref_params):
                    continue
                rows = b["param_version"] == v
                p = ref_params[int(v)]
                lp = np.asarray(lp_fn(p, rb))[rows]
                if behaviour is None:
                    seen = b["behavior_logprobs"][rows]
                else:
                    # a bfloat16 policy hands back bfloat16 logprobs
                    seen = np.asarray(lp_fn(
                        common.cast_floats(p, jnp.bfloat16),
                        common.cast_floats(rb, jnp.bfloat16))
                        .astype(jnp.bfloat16), np.float32)[rows]
                gap = float(np.max(np.abs(lp - seen)))
                if gap >= worst:
                    worst, where = gap, f"batch {i + 1}, version {int(v)}"
    return worst, where
