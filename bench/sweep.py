"""The knee sweep behind the realtime cell's stream count.

    python3 bench/sweep.py --config fastgrnn-har-lowrank-q15 \\
        --traffic realtime-sweep --seconds 20 --start 8192 --step 8192

Runs the open-loop mix at rising stream counts (multiples of ``--step``),
one fleet after another in one process, until a count fails to hold the
loop: the 99th percentile tick latency over one ``--seconds`` window must
stay within one tick period (20 ms at 50 Hz) and the median of the last
quarter of ticks too (no growing lag).  The knee is the highest count that
holds; the cell runs 4/5 of it, rounded down to whole shards x phases.
Needs the chip, like ``run.py``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--start", type=int, default=8192)
    ap.add_argument("--step", type=int, default=8192)
    ap.add_argument("--stop", type=int, default=131072)
    ap.add_argument("--seed", type=int, default=2024)
    args = ap.parse_args()
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import numpy as np
    import harness
    bench = harness.Bench(ROOT)
    bench.spec["workloads"].append({"name": "sweep", "config": args.config,
                                    "traffic": args.traffic, "chips": 1})
    _, cfg, mix = bench.load_cell("sweep")
    period_ms = 1e3 / mix["tick_hz"]
    knee, rows = None, []
    for n in range(args.start, args.stop + 1, args.step):
        res = bench.run("sweep", args.seed + n, args.seconds, False,
                        t_start=time.perf_counter(), mix=dict(mix, streams=n),
                        keep_latencies=True)
        lat = 1e3 * np.asarray(res.pop("latencies_s"))
        q = len(lat) // 4
        row = {"streams": n, "ticks": len(lat), "p50_ms": float(np.percentile(lat, 50)),
               "p99_ms": float(np.percentile(lat, 99)),
               "last_quarter_p50_ms": float(np.median(lat[-q:])) if q else None,
               "correct": res["correct"]}
        row["holds"] = bool(row["p99_ms"] <= period_ms and q
                            and row["last_quarter_p50_ms"] <= period_ms)
        rows.append(row)
        print(json.dumps(row), flush=True)
        if not row["holds"]:
            break
        knee = n
    unit = cfg["serving"]["shards"] * mix["window_phases"]
    cell = None if knee is None else (knee * 4 // 5) // unit * unit
    print(json.dumps({"knee_streams": knee, "cell_streams": cell, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
