"""Observability overhead benchmark: what does telemetry cost the fleet?

    PYTHONPATH=src python -m benchmarks.obs_bench \
        [--out BENCH_obs.json] [--shards 8] [--slots-per-shard 16384] \
        [--windows 2] [--smoke]

Three measurements, one record (the PR's acceptance budgets):

* **Baseline vs traced throughput** — the capacity fleet (default
  8 x 16384 = 131,072 resident streams) stepped to completion with the
  default :data:`~repro.obs.NULL_OBS` (NullTracer path — must stay
  within the 2 % band of the committed ``BENCH_fleet.json`` capacity
  number) and with the full bundle (tracer + metrics + flight recorder)
  whose overhead must stay under 10 %.  Runs are **interleaved
  median-of-N** (``--reps``, default 3): shared-container throughput
  jitters far more than the budgets being judged, so the record also
  carries ``measured_noise_pct`` (rep spread) and a delta below the
  noise floor is not counted as a budget violation.
* **Tick-phase breakdown + deadline-miss rate** — from the traced
  capacity run: per-phase p50/p99 (``Tracer.phase_stats``) and the 50 Hz
  deadline-miss counters at 131k streams
  (``fleet.deadline_miss_stream_ticks`` / total stream-ticks).
* **Flight-recorder byte-stability** — two identical runs under the
  full phase x shard ``crash_matrix`` fault schedule must produce
  byte-identical ``dumps(deterministic=True)``.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import time

import jax
import numpy as np

from repro.core import fastgrnn as fg
from repro.core.quantization import quantize_params, QuantConfig
from repro.data import hapt
from repro.obs import Observability
from repro.serve.fleet import FleetConfig, FleetEngine, crash_matrix
from repro.serve.streaming import StreamingConfig
from repro.kernels import enable_compile_cache


def _build(qp, shards: int, slots: int, windows: int, obs, *,
           snapshot_every=None, faults=None) -> FleetEngine:
    ring = 128 * windows
    stream = StreamingConfig(max_slots=slots, backend="jit",
                             batch_events=True, ring_capacity=ring,
                             max_ring_capacity=ring)
    return FleetEngine(qp, FleetConfig(
        shards=shards, stream=stream, max_pending_per_shard=0,
        placement="host", snapshot_every=snapshot_every),
        obs=obs, faults=faults)


def _fill(fleet, src, n_streams: int, windows: int) -> None:
    total = 128 * windows
    for i in range(n_streams):
        fleet.attach(f"s{i}", total_steps=total)
        fleet.feed(f"s{i}", np.tile(src[i % len(src)], (windows, 1)))


def _timed_run(qp, src, shards: int, slots: int, windows: int,
               obs) -> dict:
    n_streams = shards * slots
    fleet = _build(qp, shards, slots, windows, obs)
    _fill(fleet, src, n_streams, windows)
    total = 128 * windows
    fleet.step()                                 # warm-up tick (jit compile)
    tick_s = []
    t_start = time.perf_counter()
    for _ in range(total - 1):
        t0 = time.perf_counter()
        fleet.step()
        tick_s.append(time.perf_counter() - t0)
    elapsed = time.perf_counter() - t_start
    stats = fleet.stats()
    assert stats["completed"] == n_streams, stats
    steps = n_streams * (total - 1)
    tick_ms = np.asarray(tick_s) * 1e3
    return {
        "concurrent_streams": n_streams,
        "ticks": len(tick_s),
        "stream_steps_per_sec": round(steps / elapsed, 1),
        "p50_ms": round(float(np.percentile(tick_ms, 50)), 4),
        "p99_ms": round(float(np.percentile(tick_ms, 99)), 4),
        "stream_ticks": steps,
    }


def _flight_stability(qp, input_dim: int, shards: int = 4) -> dict:
    """Two identical crash-matrix runs -> byte-identical deterministic
    flight dumps (the crash-forensics determinism gate)."""
    rng = np.random.default_rng(7)
    streams = {f"st{i:03d}": rng.standard_normal((300, input_dim))
               .astype(np.float32) for i in range(16)}

    def run() -> tuple[str, int]:
        obs = Observability.full()
        fleet = _build(qp, shards, 8, 3, obs, snapshot_every=32,
                       faults=crash_matrix(shards))
        for sid, w in streams.items():
            fleet.attach(sid, w, total_steps=len(w))
        fleet.drain()
        return obs.recorder.dumps(deterministic=True), obs.recorder.n_crashes

    dump_a, crashes = run()
    dump_b, _ = run()
    return {
        "shards": shards,
        "crashes": crashes,
        "dump_bytes": len(dump_a),
        "byte_stable": dump_a == dump_b,
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="BENCH_obs.json")
    parser.add_argument("--shards", type=int, default=8)
    parser.add_argument("--slots-per-shard", type=int, default=16384)
    parser.add_argument("--windows", type=int, default=3,
                        help="128-sample windows per stream (default "
                             "matches fleet_bench's capacity geometry so "
                             "the null-vs-BENCH_fleet gate is apples-to-"
                             "apples)")
    parser.add_argument("--reps", type=int, default=3,
                        help="interleaved baseline/traced repetitions "
                             "(median-of-N)")
    parser.add_argument("--fleet-bench", default="BENCH_fleet.json",
                        help="committed fleet capacity record to compare "
                             "the NullTracer run against")
    parser.add_argument("--smoke", action="store_true",
                        help="CI configuration: tiny fleet, 1 window")
    args = parser.parse_args()
    enable_compile_cache()
    if args.smoke:
        args.shards, args.slots_per_shard, args.windows = 2, 256, 1
        args.reps = 1

    cfg = fg.FastGRNNConfig(rank_w=2, rank_u=8)
    qp = quantize_params(fg.init_params(cfg, jax.random.PRNGKey(0)),
                         QuantConfig())
    src = hapt.load("test", n=256).windows
    n_streams = args.shards * args.slots_per_shard

    # interleaved A/B: baseline and traced alternate within one process,
    # so slow drift in container load hits both arms equally; medians
    # (not means) absorb the occasional noisy-neighbour outlier rep
    base_runs: list[dict] = []
    traced_runs: list[tuple[dict, Observability]] = []
    reps = max(1, args.reps)
    for rep in range(reps):
        print(f"rep {rep + 1}/{reps} baseline (NULL_OBS): "
              f"{n_streams:,} streams ...", flush=True)
        base_runs.append(_timed_run(qp, src, args.shards,
                                    args.slots_per_shard, args.windows,
                                    obs=None))
        print(f"  {base_runs[-1]['stream_steps_per_sec']:>14,.0f} steps/s  "
              f"p50 {base_runs[-1]['p50_ms']:.3f} ms", flush=True)
        print(f"rep {rep + 1}/{reps} traced (full bundle): "
              f"{n_streams:,} streams ...", flush=True)
        ob = Observability.full(capacity=8192)
        traced_runs.append((_timed_run(qp, src, args.shards,
                                       args.slots_per_shard, args.windows,
                                       obs=ob), ob))
        print(f"  {traced_runs[-1][0]['stream_steps_per_sec']:>14,.0f} "
              f"steps/s  p50 {traced_runs[-1][0]['p50_ms']:.3f} ms",
              flush=True)
    base_runs.sort(key=lambda r: r["stream_steps_per_sec"])
    baseline = base_runs[len(base_runs) // 2]
    traced_runs.sort(key=lambda t: t[0]["stream_steps_per_sec"])
    traced, obs = traced_runs[len(traced_runs) // 2]
    rates = ([r["stream_steps_per_sec"] for r in base_runs]
             + [run["stream_steps_per_sec"] for run, _ in traced_runs])
    noise_pct = round(100.0 * (max(rates) - min(rates))
                      / float(np.median(rates)), 2)

    snap = obs.metrics.snapshot()
    miss_stream_ticks = snap["counters"][
        "fleet.deadline_miss_stream_ticks"]
    deadline = {
        "deadline_ms": 20.0,           # 50 Hz real-time budget
        "concurrent_streams": n_streams,
        "miss_ticks": snap["counters"]["fleet.deadline_miss_ticks"],
        "miss_stream_ticks": miss_stream_ticks,
        "stream_ticks": traced["stream_ticks"],
        "miss_rate": round(miss_stream_ticks / traced["stream_ticks"], 6),
    }
    phases = {name: {k: st[k] for k in ("count", "p50_us", "p99_us")}
              for name, st in obs.tracer.phase_stats().items()}

    overhead_pct = round(
        100.0 * (1 - traced["stream_steps_per_sec"]
                 / baseline["stream_steps_per_sec"]), 2)
    budgets = {
        "traced_overhead_pct": overhead_pct,
        "traced_budget_pct": 10.0,
        "traced_within_budget": overhead_pct <= 10.0,
        "null_budget_pct": 2.0,
        # rep spread across all interleaved runs: the host's own
        # run-to-run jitter, recorded so budget deltas can be read
        # against the measurement's actual resolution
        "measured_noise_pct": noise_pct,
    }
    # NullTracer (= the default path) vs the committed fleet capacity
    # number, when this run used the same geometry (stream count AND
    # tick count — a different windows-per-stream setting amortizes
    # fixed costs differently and is not a valid comparison).  A delta
    # below this session's measured rep spread is not evidence of a
    # regression — the comparison crosses processes, so it inherits the
    # full inter-run noise, and the budget gate saturates at that floor.
    if os.path.exists(args.fleet_bench):
        with open(args.fleet_bench) as f:
            cap = json.load(f).get("capacity", {})
        if (cap.get("concurrent_streams") == n_streams
                and cap.get("ticks") == baseline["ticks"]):
            ref = cap["stream_steps_per_sec"]
            delta = round(
                100.0 * (1 - baseline["stream_steps_per_sec"] / ref), 2)
            budgets["null_vs_fleet_bench_pct"] = delta
            budgets["null_within_budget"] = delta <= max(2.0, noise_pct)
    print(f"traced overhead: {overhead_pct:+.2f}% "
          f"(budget 10%, rep noise {noise_pct:.1f}%); deadline misses at "
          f"{n_streams:,} streams: "
          f"{deadline['miss_rate'] * 100:.4f}%", flush=True)

    input_dim = 3
    flight = _flight_stability(qp, input_dim,
                               shards=2 if args.smoke else 4)
    print(f"flight recorder: {flight['crashes']} crashes, "
          f"{flight['dump_bytes']:,} B deterministic dump, "
          f"byte_stable={flight['byte_stable']}", flush=True)

    record = {
        "benchmark": "obs_overhead",
        "model": "FastGRNN H=16 r_w=2 r_u=8, Q15 PTQ (566-byte class)",
        "backend": "jit",
        "window": 128,
        "sample_rate_hz": 50.0,
        "host": {"platform": platform.platform(),
                 "cpus": os.cpu_count(),
                 "jax": jax.__version__,
                 "device": str(jax.devices()[0])},
        "config": {"shards": args.shards,
                   "slots_per_shard": args.slots_per_shard,
                   "windows": args.windows,
                   "concurrent_streams": n_streams},
        "baseline": baseline,
        "traced": traced,
        "budgets": budgets,
        "phases": phases,
        "deadline": deadline,
        "flight_recorder": flight,
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
