"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout (``BENCHMARK.json`` beside ``bench/`` and
the program under ``src/``).  With ``--trace 0`` the last line of standard
output is one JSON object with the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, the device's busy time and a
breakdown from the profiler's trace.  Each number compared with the plain
reference is printed with its limit as the last lines of standard error
and under ``checks`` in the result.  Exits non-zero with no result line
when JAX finds no TPU, or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench/run.py: the program is missing ({src}/repro)", file=sys.stderr)
        return 2
    sys.path[:0] = [BENCH, src]
    import harness
    try:
        result = harness.Bench(ROOT).run(args.workload, args.seed, args.seconds,
                                         bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    harness.print_checks(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
