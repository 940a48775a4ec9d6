"""Readings from which the limits of `correct` are set; the benchmark's
own runs never run this. For each seed it drives one run of the cell on
the chip as `bench/run.py` does (a short window at the cell's own load)
and prints, per number compared, what the program reads, what the
bfloat16 control reads in the program's place, and what the reference
fed half of each batch (the half-batch fault) reads; for R2D2's served
policy also the reference with each lane's state read from its
neighbour's row (``slot_shuffle``) or never written back
(``state_stale``):

    python3 bench/control.py --workload r2d2_atari.inproc \
        --seeds 11,12,13 --seconds 2

One JSON line per seed; the last line holds, per number, the largest
program reading and the smallest reading of the control and of each
fault.
"""

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        run = argparse.Namespace(workload=args.workload, seed=seed,
                                 seconds=args.seconds, trace=0)
        r = harness.run(run, time.perf_counter(), controls=True)
        row = {"seed": seed, "correct": r["correct"],
               "errors": r["_errors"]}
        for kind, numbers in r["_readings"].items():
            row[kind] = {k: v for k, (v, _) in numbers.items()}
            row[kind + "_at"] = {k: at for k, (_, at) in numbers.items()}
        print(json.dumps(row), flush=True)
        rows.append(row)
    kinds = [k for k in r["_readings"] if k != "program"]
    summary = {"lower": {}, **{kind: {} for kind in kinds}}
    for k in rows[0]["program"]:
        summary["lower"][k] = max(r["program"][k] for r in rows)
        for kind in kinds:
            vals = [r[kind][k] for r in rows if k in r.get(kind, {})]
            if vals:
                summary[kind][k] = min(vals)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
