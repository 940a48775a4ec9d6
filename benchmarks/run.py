"""Benchmark driver — one section per paper figure/table plus the roofline
table and a train/serve micro-benchmark. Prints ``name,value,derived`` CSV.
"""

import time

import jax
import jax.numpy as jnp

from benchmarks import fig2_breakdown, fig3_actor_scaling, fig4_cpu_gpu_ratio
from benchmarks import roofline as roofline_bench
from repro.launch.compile_cache import enable_compile_cache


def microbench_train_step():
    """us_per_call of the jitted V-trace train step for a tiny LM (CPU)."""
    from repro.configs.registry import make_model, smoke_config
    from repro.core.losses import init_train_state, make_train_step
    from repro.envs.tokenworld import synthetic_vtrace_batch
    from repro.optim import adamw

    print("# microbench: jitted train/serve steps (tiny configs, CPU)")
    print("name,us_per_call,derived")
    cfg = smoke_config("qwen3-14b")
    bundle = make_model(cfg)
    opt = adamw(1e-3)
    step = jax.jit(make_train_step(bundle, opt), donate_argnums=(0,))
    state = init_train_state(bundle, opt, jax.random.PRNGKey(0))
    batch = synthetic_vtrace_batch(jax.random.PRNGKey(1), 4, 32, cfg.vocab_size)
    state, _ = step(state, batch)                     # compile
    jax.block_until_ready(state["params"])
    n = 20
    t0 = time.perf_counter()
    for _ in range(n):
        state, m = step(state, batch)
    jax.block_until_ready(state["params"])
    us = (time.perf_counter() - t0) / n * 1e6
    tok = 4 * 32 / (us / 1e6)
    print(f"train_step_tiny_qwen3,{us:.0f},tokens_per_s={tok:.0f}")

    from repro.launch.serve import make_prefill, make_serve_step
    params = state["params"]
    prefill = jax.jit(make_prefill(bundle, max_len=64, dtype=jnp.float32))
    sstep = jax.jit(make_serve_step(bundle), donate_argnums=(2,))
    toks = jnp.zeros((4, 32), jnp.int32)
    tok1, cache = prefill(params, {"tokens": toks})
    tok1, cache = sstep(params, tok1, cache)          # compile
    jax.block_until_ready(tok1)
    t0 = time.perf_counter()
    for _ in range(n):
        tok1, cache = sstep(params, tok1, cache)
    jax.block_until_ready(tok1)
    us = (time.perf_counter() - t0) / n * 1e6
    print(f"serve_step_tiny_qwen3,{us:.0f},decode_tokens_per_s={4/(us/1e6):.0f}")


def main() -> None:
    enable_compile_cache()
    print("=" * 72)
    print("== Fig 2: GPU/TPU bottleneck breakdown (sequential idealization)")
    fig2_breakdown.main()
    print("=" * 72)
    print("== Fig 3: actor scaling (measured scaled-down + calibrated model)")
    fig3_actor_scaling.main()
    print("=" * 72)
    print("== Fig 4 + Conclusion 3: accelerator derating & CPU/GPU ratio")
    fig4_cpu_gpu_ratio.main()
    print("=" * 72)
    print("== Roofline table (from dry-run artifacts)")
    roofline_bench.main()
    print("=" * 72)
    microbench_train_step()


if __name__ == "__main__":
    main()
