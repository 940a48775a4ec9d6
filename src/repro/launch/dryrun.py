"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape x mesh) cell this lowers + compiles
the real step function (train_step / prefill / serve_step) against sharded
ShapeDtypeStruct stand-ins on the production mesh, prints
memory_analysis() (fits?) and cost_analysis() (roofline terms), and
records the collective schedule.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

# MUST run before any jax import — jax locks the device count on first init.
import os
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512")

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding

from repro.configs import SHAPES, get_config, shape_cells
from repro.configs.registry import ARCHS, make_model
from repro.core.losses import make_train_step
from repro.hw import TPU_V5E
from repro.launch.analysis import analyze_compiled
from repro.launch.mesh import make_production_mesh
from repro.launch.serve import make_prefill, make_serve_step
from repro.launch.specs import (batch_specs, cache_specs, params_specs,
                                rules_for, shardings_of, state_specs)
from repro.optim import adamw
from repro.sharding.ctx import sharding_ctx


def production_config(arch, mesh, kind="train"):
    cfg = get_config(arch)
    tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    if cfg.pure_dp and kind == "train":
        tp = 1  # no head/vocab padding needed — weights are replicated
        cfg = cfg.with_(grad_accum=1)  # batch is fully sharded; no splitting
    return cfg.with_(tp=tp, param_dtype="bfloat16", compute_dtype="bfloat16",
                     remat=cfg.remat if cfg.remat != "none" else "full")


def lower_cell(arch: str, shape_name: str, mesh, verbose=False):
    """Lower + compile one (arch x shape) cell on `mesh`. Returns report."""
    shape = SHAPES[shape_name]
    cfg = production_config(arch, mesh, shape.kind)
    bundle = make_model(cfg)
    rules = rules_for(cfg, mesh, shape.kind)
    if shape.kind == "decode" and cfg.family == "moe":
        # serving: 'full EP' — one expert slice per chip across model x data,
        # so decode moves the (tiny) token batch instead of expert weights.
        rules = dict(rules, experts=tuple(
            a for a in ("model", "data") if a in mesh.axis_names))
    n_chips = mesh.devices.size
    t0 = time.perf_counter()

    with sharding_ctx(mesh, rules), jax.set_mesh(mesh):
        if shape.kind == "train":
            opt = adamw(1e-4, moment_dtype=jnp.dtype(cfg.optimizer_dtype))
            step_fn = make_train_step(bundle, opt)
            state = state_specs(bundle, opt, mesh, cfg)
            batch = batch_specs(cfg, shape, mesh, rules)
            lowered = jax.jit(step_fn,
                              out_shardings=(shardings_of(state), None),
                              donate_argnums=(0,)).lower(state, batch)
        elif shape.kind == "prefill":
            params = params_specs(bundle, mesh, rules)
            batch = batch_specs(cfg, shape, mesh, rules, with_rl_fields=False)
            cache_sh = shardings_of(cache_specs(bundle, shape, mesh, rules))
            fn = make_prefill(bundle, max_len=shape.seq_len)
            lowered = jax.jit(fn, out_shardings=(None, cache_sh)
                              ).lower(params, batch)
        else:  # decode
            params = params_specs(bundle, mesh, rules)
            cache = cache_specs(bundle, shape, mesh, rules)
            tok = jax.ShapeDtypeStruct(
                (shape.global_batch, 1), jnp.int32,
                sharding=NamedSharding(mesh, P()))
            fn = make_serve_step(bundle)
            lowered = jax.jit(fn, out_shardings=(None, shardings_of(cache)),
                              donate_argnums=(2,)).lower(params, tok, cache)

        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    rep = analyze_compiled(lowered, compiled, n_chips, TPU_V5E)
    rep.update(arch=arch, shape=shape_name, mesh=list(mesh.devices.shape),
               n_chips=n_chips, lower_s=round(t_lower, 1),
               compile_s=round(t_compile, 1))
    if verbose:
        mem = rep["memory"]
        t = rep["terms"]
        print(f"[{arch} x {shape_name} x {'x'.join(map(str, mesh.devices.shape))}] "
              f"flops/chip={rep['flops_per_chip']:.3e} "
              f"hbm B/chip={rep['hbm_bytes_per_chip']:.3e} "
              f"coll B/chip={rep['collective_bytes_per_chip']:.3e} | "
              f"compute={t.compute_s*1e3:.2f}ms memory={t.memory_s*1e3:.2f}ms "
              f"collective={t.collective_s*1e3:.2f}ms -> {t.dominant()}-bound | "
              f"mem/device={mem['total_bytes']/1e9:.2f} GB "
              f"(args {mem['argument_bytes']/1e9:.2f} + temp {mem['temp_bytes']/1e9:.2f}"
              f" - alias {mem['alias_bytes']/1e9:.2f})")
    return rep


def _serialize(rep):
    t = rep.pop("terms")
    rep["terms"] = {"compute_s": t.compute_s, "memory_s": t.memory_s,
                    "collective_s": t.collective_s, "dominant": t.dominant()}
    return rep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None, help="append JSONL reports here")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    cells = []
    if args.all:
        for arch in ARCHS:
            for s in shape_cells(arch):
                cells.append((arch, s))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, s in cells:
        try:
            rep = lower_cell(arch, s, mesh, verbose=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(_serialize(rep)) + "\n")
        except Exception as e:  # noqa: BLE001 — report and continue
            traceback.print_exc()
            failures.append((arch, s, repr(e)))
    if failures:
        print(f"\nFAILED {len(failures)}/{len(cells)} cells:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print(f"\nOK: {len(cells)} cells lowered+compiled on mesh "
          f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")


if __name__ == "__main__":
    main()
