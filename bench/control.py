"""Readings for the limits of ``correct``: sound runs of a cell and runs of
its control, the program's own Q7 weights in place of the configuration's
Q15 (the precision below the stated one), all in one process.

    python3 bench/control.py --workload lowrank.backlog-131k --seconds 3 \\
        --sound 11 12 13 --control 21 22 23

Each run is a whole run of the cell at its own size with a short window;
the lines give the compared numbers of each run, the last line the
largest sound and smallest control reading of each number.  Needs the
chip, like ``run.py``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
Q7_BITS = 7


def readings(bench, workload: str, seeds: dict, seconds: float, **kw) -> dict:
    """{"sound": {name: max}, "control": {name: min}} over the runs, with
    each run's numbers printed as it ends."""
    out: dict = {"sound": {}, "control": {}}
    for kind, kind_seeds in seeds.items():
        for seed in kind_seeds:
            res = bench.run(workload, seed, seconds, False, t_start=time.perf_counter(),
                            bits=Q7_BITS if kind == "control" else None, **kw)
            nums = {n: c["value"] for n, c in res["checks"].items()}
            print(json.dumps({"kind": kind, "seed": seed, "correct": res["correct"],
                              "attempted": res["attempted"], **nums}), flush=True)
            pick = max if kind == "sound" else min
            for n, v in nums.items():
                out[kind][n] = pick(out[kind].get(n, v), v)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--sound", type=int, nargs="*", default=[])
    ap.add_argument("--control", type=int, nargs="*", default=[])
    args = ap.parse_args()
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import harness
    out = readings(harness.Bench(ROOT), args.workload,
                   {"sound": args.sound, "control": args.control}, args.seconds)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
