"""The paper's workload at reduced widths, end to end: SEED-style
distributed R2D2 on an ALE stand-in (`chip_smoke.py` runs the same wiring,
`repro.core.r2d2_agent`, at the published widths on a TPU).

Actor threads step the env and query the central inference server (which
owns per-actor LSTM state, SEED-style); unrolls land in prioritized
replay; the learner runs recurrent double-Q with burn-in and publishes
fresh params. Reports the Fig-3 quantities (frames/s, batch occupancy).

    PYTHONPATH=src python examples/train_atari_r2d2.py --actors 2 --seconds 8
"""

import argparse
import sys

sys.path.insert(0, "src")

from repro.configs.r2d2_atari import AtariConfig
from repro.core.r2d2_agent import build_r2d2_system
from repro.envs.alesim import ALESimEnv
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--actors", type=int, default=2)
    ap.add_argument("--envs-per-actor", type=int, default=1,
                    help="env lanes vectorized per actor thread")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--frame", type=int, default=42)
    args = ap.parse_args()
    enable_compile_cache()

    # reduced widths so the example trains on a CPU in seconds; chip_smoke.py
    # runs the same wiring at the published AtariConfig() widths
    acfg = AtariConfig(obs_size=args.frame, obs_channels=2, core_dim=128,
                       num_actions=6, burn_in=4, unroll=16, n_step=3,
                       target_update_period=50)
    sys_ = build_r2d2_system(
        acfg, lambda: ALESimEnv(frame=args.frame, channels=2, step_cost=512,
                                episode_len=200),
        num_actors=args.actors, envs_per_actor=args.envs_per_actor,
        learner_batch=2, replay_capacity=256, min_replay=2)

    print(f"== SEED R2D2: {args.actors} actors x {args.envs_per_actor} env "
          f"lanes, {args.seconds}s wall-clock")
    stats = sys_.run(seconds=args.seconds)
    for k, v in stats.items():
        print(f"  {k:24s} {v:.3f}" if isinstance(v, float) else f"  {k:24s} {v}")
    for key in ("learner_error", "inference_error"):
        if stats[key]:
            raise SystemExit(f"{key}:\n{stats[key]}")
    assert stats["env_frames"] > 0 and stats["learner_steps"] > 0
    print("ok — actors, central inference, replay and learner all ran")


if __name__ == "__main__":
    main()
