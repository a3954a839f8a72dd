"""Record a small profiler trace for the trace-reduction tests to read.

    python3 bench/record_trace.py --out traces/fleet_small

Runs the low-rank configuration at a small fleet (8 shards x 128 streams)
for a fraction of a second under the profiler, on the chip, keeps the
``.xplane.pb`` under ``--out`` and prints each plane's lines, event counts
and the most frequent event names with their stats.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

SMALL = {"name": "small", "generator": "staggered", "loop": "closed", "streams": 1024, "window_phases": 128,
         "packet_samples": 25, "tick_hz": 50.0, "pool_windows": 256,
         "pool_split": "test", "check_streams": 64}


def describe(path: str) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for ln in lines:
            evs = list(ln.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  line {ln.name!r}: {len(evs)} events; top {names.most_common(8)}")
            for e in evs[:2]:
                print(f"    {e.name!r} start {e.start_ns} dur {e.duration_ns} "
                      f"stats {dict(list(e.stats))}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=0.3)
    args = ap.parse_args()
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import harness
    import trace_reduce
    out = os.path.abspath(args.out)
    res = harness.Bench(ROOT).run("lowrank.backlog-131k", 1, args.seconds, True,
                                  t_start=T_START, mix=SMALL, trace_dir=out)
    path = trace_reduce.find_xplane(out)
    print(f"trace {path}: {os.path.getsize(path)} bytes")
    describe(path)
    red = trace_reduce.reduce(path)
    print(json.dumps({k: v for k, v in red.items() if k not in ("ops", "gaps")}))
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
