"""Compiled-artifact analysis: roofline terms from cost_analysis + an HLO
scan for collective bytes (cost_analysis does not report them).

Approximations (documented in EXPERIMENTS.md):
  * per-op wire bytes = the largest shape appearing in the op line
    (all-gather: gathered output; reduce-scatter: unscattered input;
    all-to-all / collective-permute: the tensor itself);
  * all-reduce counts 2x (ring all-reduce moves ~2 bytes per byte);
  * -start/-done pairs are counted once (on -start).
"""

import re
from typing import Dict

from repro.core.bottleneck import RooflineTerms, terms_from_hlo
from repro.hw import TPU_V5E

DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f32": 4, "s32": 4, "u32": 4,
    "f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(" + "|".join(DTYPE_BYTES) + r")\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"ragged-all-to-all)(-start)?\b")


def shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES[dtype]


def collective_stats(hlo_text: str) -> Dict[str, float]:
    """Scan HLO for collectives; returns bytes per op kind + total."""
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "ragged-all-to-all": 0,
           "count": 0}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done" in line.split("=")[0]:
            continue
        # skip the metadata/called-computation region lines
        if "=" not in line:
            continue
        kind = m.group(1)
        shapes = _SHAPE_RE.findall(line)
        if not shapes:
            continue
        byts = max(shape_bytes(d, dims) for d, dims in shapes)
        if kind == "all-reduce":
            byts *= 2
        out[kind] += byts
        out["count"] += 1
    out["total_bytes"] = sum(v for k, v in out.items()
                             if k not in ("count", "total_bytes"))
    return out


def analyze_compiled(lowered, compiled, n_chips: int, chip=TPU_V5E,
                     occupancy: float = 1.0):
    """Roofline terms + memory report for one compiled step.

    XLA's cost_analysis() counts while-loop bodies once (scans are
    undercounted by their trip count), so FLOPs/bytes/collectives come from
    the trip-count-aware HLO analyzer in repro.launch.hlo_cost; the raw
    cost_analysis numbers are kept alongside for reference.
    """
    from repro.launch.hlo_cost import module_costs
    cost = compiled.cost_analysis()
    hlo = module_costs(compiled.as_text())
    flops = hlo.flops                                # per-partition
    mem = compiled.memory_analysis()
    # Memory term: buffer-level traffic (args + outputs read/written once,
    # temps written+read). The per-op byte count from the CPU-fused HLO
    # (hlo.bytes) is kept as a pessimistic upper bound — TPU fusion keeps
    # producer-consumer chains in VMEM, so buffer traffic is the roofline
    # quantity.
    hbm_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + 2 * mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    terms = terms_from_hlo(flops, hbm_bytes, hlo.collective_bytes, n_chips,
                           chip, occupancy)
    return {
        "flops_per_chip": flops,
        "hbm_bytes_per_chip": hbm_bytes,
        "hbm_bytes_per_chip_upper": hlo.bytes,
        "collective_bytes_per_chip": hlo.collective_bytes,
        "collective_count": hlo.collective_count,
        "transcendental_per_chip": hlo.transcendental,
        "xla_cost_analysis": {"flops": float(cost.get("flops", 0.0)),
                              "bytes": float(cost.get("bytes accessed", 0.0))},
        "terms": terms,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "total_bytes": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                            + mem.temp_size_in_bytes - mem.alias_size_in_bytes),
        },
    }
