"""Run one benchmark cell and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler trace of the
window's first call. Exits non-zero, with no result line, where JAX finds
no TPU or fewer chips than the cell asks for. See ``bench/README.md``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    import harness

    harness.prepare_environment(ROOT)
    cell = harness.load_cell(ROOT, args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds,
                             bool(args.trace), T_PROCESS)
    except harness.NoChip as exc:
        print(f"no result: {exc}", file=sys.stderr)
        return 3
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
