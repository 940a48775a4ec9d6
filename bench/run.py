"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload r2d2_atari.inproc --seed 7 \
        --seconds 30 --trace 0

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, the device's busy and traced seconds
and the breakdown of device operations and idle gaps. The numbers that
decide ``correct`` are printed as the last lines of standard error and
under ``checks`` at the end of the result. Exits 2, printing no result,
where JAX finds no TPU or fewer chips than the cell asks for, and 3 where
the cell cannot reach its window.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
# libtpu would otherwise log to a fixed directory outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    import harness

    try:
        result = harness.run(args, T_START)
    except harness.SetupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return e.code
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
