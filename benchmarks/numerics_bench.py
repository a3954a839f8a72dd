"""Numeric-health monitor bench: overhead budget, counter parity, drift demo.

    PYTHONPATH=src python -m benchmarks.numerics_bench \
        [--out BENCH_numerics.json] [--windows 128] [--streams 64] [--reps 3]

Pins the four claims the numeric-health observability layer ships with:

  * **overhead** — attaching a live
    :class:`repro.obs.numerics.NumericsMonitor` to the exact-backend
    streaming engine costs <= 10% over the monitor-less ``Observability``
    bundle it rides on (the exact path tallies from intermediates the
    kernel already materializes; the bundle itself is budgeted by
    ``benchmarks/obs_bench.py``), and that null bundle sits at the noise
    floor vs the fully unobserved baseline;
  * **counter_parity** — the ``-DFG_NUMERIC_COUNTERS`` C build reports
    per-site saturation counts exactly equal to the monitored qvm's on
    the same quantized windows, including a x8 input-amplified stress
    segment that must witness ``h_next`` saturation on both sides
    (skipped when no host cc is available);
  * **drift_demo** — injecting input gain 1/2/4/8 produces a
    monotonically non-decreasing calibration-drift score (the score
    moves when the deployment's data distribution does);
  * **crosscheck** — the unmodified-gain runtime witnesses pass the
    static reachability cross-check (:mod:`repro.analysis.crosscheck`).

Timing numbers are wall-clock (host-dependent); every boolean gate and
counter in the record is deterministic.
"""
from __future__ import annotations

import argparse
import json
import platform as _platform
import tempfile
import time

import numpy as np

from repro.data import hapt
from repro.deploy import emit_c
from repro.deploy.goldens import build_reference_artifact
from repro.deploy.image import build_image
from repro.deploy.qvm import QVM
from repro.obs import MetricsRegistry, Observability
from repro.obs.numerics import NumericsMonitor, site_order
from repro.kernels import enable_compile_cache

#: Input gain that drives the reference model's ``h_next`` site into
#: saturation (the stress witness both engines must agree on).
STRESS_GAIN = 8

#: Acceptance budget: the monitor's marginal exact-backend throughput
#: loss over the monitor-less obs bundle.
MONITOR_BUDGET_PCT = 10.0
#: Noise floor allowance for the monitor-less bundle (this class of
#: 2-core container shows ~5-9% session rep noise — obs_bench records
#: the same as ``measured_noise_pct``; so does this record).
NULL_BUDGET_PCT = 5.0


#: Windows fed back-to-back per stream in the overhead drain — long
#: enough (~1024 ticks) that host scheduling noise stops dominating the
#: sub-100ms single-window measurement.
DRAIN_WINDOWS = 8


def _one_drain_s(art, windows: np.ndarray, make_obs) -> float:
    """Wall time of one full attach+drain pass of the exact engine."""
    from repro.serve.streaming import StreamingConfig, StreamingEngine
    eng = StreamingEngine.from_artifact(
        art, StreamingConfig(max_slots=len(windows), backend="exact"),
        obs=make_obs())
    for i, w in enumerate(windows):
        samples = np.tile(w, (DRAIN_WINDOWS, 1))
        eng.attach(f"w{i}", samples, total_steps=len(samples))
    t0 = time.perf_counter()
    eng.drain()
    return time.perf_counter() - t0


def bench_overhead(art, windows: np.ndarray, reps: int) -> tuple[dict, dict]:
    """Interleaved best-of-``reps`` so thermal / cache drift lands on
    every configuration equally (sequential per-config timing on a
    sub-100ms drain is dominated by host noise)."""
    configs = {
        "baseline": lambda: None,
        "null": lambda: Observability(metrics=MetricsRegistry()),
        "monitored": lambda: Observability(metrics=MetricsRegistry(),
                                           numerics=NumericsMonitor()),
    }
    times = {name: [] for name in configs}
    _one_drain_s(art, windows, configs["baseline"])      # shared warm-up
    for _ in range(reps):
        for name, make_obs in configs.items():
            times[name].append(_one_drain_s(art, windows, make_obs))
    steps = windows.shape[0] * windows.shape[1] * DRAIN_WINDOWS
    base, null, mon = (steps / min(times[k]) for k in
                       ("baseline", "null", "monitored"))
    # Overheads are the MEDIAN over PAIRED per-rep ratios: the three
    # configs inside one rep run back to back and share the host's
    # thermal/scheduling state, so a within-rep ratio is far stabler
    # than a ratio of best-of times taken from different reps, and the
    # median is robust to the occasional rep where noise landed on one
    # side of the pair.
    def _med(xs):
        xs = sorted(xs)
        n = len(xs)
        return xs[n // 2] if n % 2 else (xs[n // 2 - 1] + xs[n // 2]) / 2
    over_mon = _med([100.0 * (tm - tb) / tb for tb, tm in
                     zip(times["baseline"], times["monitored"])])
    over_null = _med([100.0 * (tn - tb) / tb for tb, tn in
                      zip(times["baseline"], times["null"])])
    # the budget gates the MONITOR's marginal cost over the monitor-less
    # obs bundle: the tracer/metrics bundle itself is budgeted separately
    # by benchmarks/obs_bench.py, and a NumericsMonitor only ever runs on
    # top of one
    marginal = _med([100.0 * (tm - tn) / tn for tn, tm in
                     zip(times["null"], times["monitored"])])
    # session rep noise: spread of the *unmonitored* baseline drain
    # across reps — the floor below which overhead deltas are not
    # distinguishable on this host (obs_bench records the same)
    noise = 100.0 * (max(times["baseline"]) - min(times["baseline"])) \
        / min(times["baseline"])
    overhead = {
        "baseline_steps_per_sec": round(base, 1),
        "null_steps_per_sec": round(null, 1),
        "monitored_steps_per_sec": round(mon, 1),
        "null_overhead_pct": round(over_null, 2),
        "monitored_overhead_pct": round(over_mon, 2),
        "monitor_marginal_pct": round(marginal, 2),
        "measured_noise_pct": round(noise, 2),
    }
    budgets = {
        "monitored_budget_pct": MONITOR_BUDGET_PCT,
        "monitored_within_budget": bool(marginal <= MONITOR_BUDGET_PCT),
        "null_budget_pct": NULL_BUDGET_PCT,
        "null_within_noise": bool(over_null <= NULL_BUDGET_PCT),
    }
    return overhead, budgets


def _qvm_counts(img, xq: np.ndarray) -> tuple[np.ndarray, np.ndarray, dict]:
    mon = NumericsMonitor()
    vm = QVM(img, monitor=mon)
    preds = np.argmax(vm.run_windows(xq), axis=1).astype(np.int32)
    snap = mon.snapshot()
    order = site_order(bool(img.low_rank))
    return preds, np.array([snap["sites"][s] for s in order], np.uint64), snap


def bench_counter_parity(img, windows: np.ndarray) -> tuple[dict, dict]:
    """qvm vs counter-instrumented C, golden + stress segments.  Returns
    (parity block, gain-1 qvm snapshot for the crosscheck block)."""
    vm = QVM(img)
    xq = vm.quantize_input(windows)
    xq_stress = vm.quantize_input(
        np.asarray(windows, np.float32) * STRESS_GAIN)
    preds_q, counts_q, snap = _qvm_counts(img, xq)
    _, counts_qs, _ = _qvm_counts(img, xq_stress)
    block = {
        "windows": int(len(windows)),
        "stress_gain": STRESS_GAIN,
        "available": False,
        "counters_equal": None,
        "preds_equal": None,
        "stress_counters_equal": None,
        "stress_h_next": int(counts_qs[site_order(
            bool(img.low_rank)).index("h_next")]),
    }
    if not emit_c.find_cc():
        return block, snap
    with tempfile.TemporaryDirectory() as td:
        binary = emit_c.compile_host(img, td, engine="int",
                                     numeric_counters=True)
        cm = emit_c.CHostModel(binary, img.H, img.C, engine="int")
        preds_c, counts_c = cm.counters(xq)
        _, counts_cs = cm.counters(xq_stress)
    block.update(
        available=True,
        counters_equal=bool(np.array_equal(counts_c, counts_q)),
        preds_equal=bool(np.array_equal(preds_c, preds_q)),
        stress_counters_equal=bool(np.array_equal(counts_cs, counts_qs)),
    )
    return block, snap


def bench_drift(img, windows: np.ndarray) -> dict:
    """Calibration-drift injection: gain sweep -> drift score sweep."""
    scales, scores = (1, 2, 4, 8), []
    for gain in scales:
        mon = NumericsMonitor()
        vm = QVM(img, monitor=mon)
        vm.run_windows(vm.quantize_input(
            np.asarray(windows, np.float32) * gain))
        scores.append(round(mon.drift(), 6))
    return {
        "scales": list(scales),
        "drift_scores": scores,
        "monotone": bool(all(a <= b for a, b in zip(scores, scores[1:]))),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_numerics.json")
    ap.add_argument("--windows", type=int, default=128)
    ap.add_argument("--streams", type=int, default=64,
                    help="streams in the engine-overhead drain")
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()
    enable_compile_cache()

    art = build_reference_artifact(seed=0)
    img = build_image(art)
    test = hapt.load("test", n=max(args.windows, args.streams)).windows

    print("overhead bench ...", flush=True)
    overhead, budgets = bench_overhead(art, test[:args.streams], args.reps)
    print("counter parity ...", flush=True)
    parity, snap = bench_counter_parity(img, test[:args.windows])
    print("drift demo ...", flush=True)
    drift = bench_drift(img, test[:args.windows])
    print("crosscheck ...", flush=True)
    from repro.analysis import crosscheck
    from repro.analysis.qlint import analyze_image
    verdict = crosscheck(analyze_image(img, name="bench"), snap)

    record = {
        "benchmark": "numerics_health",
        "model": "random-init reference export (seed 0)",
        "backend": "exact",
        "host": {"platform": _platform.platform(),
                 "cc": emit_c.find_cc()},
        "config": {"windows": args.windows, "streams": args.streams,
                   "reps": args.reps, "stress_gain": STRESS_GAIN},
        "overhead": overhead,
        "budgets": budgets,
        "counter_parity": parity,
        "drift_demo": drift,
        "crosscheck": verdict,
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(f"wrote {args.out}")
    print(f"  monitor marginal: {overhead['monitor_marginal_pct']:.1f}% "
          f"(budget {budgets['monitored_budget_pct']:.0f}%); "
          f"vs bare baseline: monitored "
          f"{overhead['monitored_overhead_pct']:.1f}%, "
          f"null {overhead['null_overhead_pct']:.1f}%")
    print(f"  counter parity: {parity}")
    print(f"  drift sweep: {drift['drift_scores']} "
          f"(monotone={drift['monotone']})")
    print(f"  crosscheck ok={verdict['ok']}")


if __name__ == "__main__":
    main()
