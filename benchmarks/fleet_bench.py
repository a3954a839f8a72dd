"""Fleet sharding benchmark: aggregate throughput vs shard count, and the
100k-concurrent-stream capacity point.

    PYTHONPATH=src python -m benchmarks.fleet_bench \
        [--out BENCH_fleet.json] [--backend jit] [--slots-per-shard 1024] \
        [--shards 1,2,4,8] [--capacity-shards 8] \
        [--capacity-slots 16384] [--smoke]

Two measurements, one record:

* **Scaling** — shard count sweeps (default 1 -> 8) at a fixed per-shard
  slot width (the capacity unit): every shard is fully resident and every
  slot advances every tick, so aggregate ``stream_steps_per_sec`` is the
  weak-scaling curve.  With fused ticks (one batched kernel dispatch per
  tick regardless of shard count) the per-dispatch fixed cost amortizes
  across shards, which is where the near-linear scaling comes from on
  CPU; per-shard bookkeeping is the part that stays serial.
* **Capacity** — one big fleet (default 8 x 16384 = 131,072 resident
  streams) stepped in steady state; reports aggregate steps/s and
  ``realtime_streams_50hz`` (how many live 50 Hz sensors this one process
  sustains in real time — the paper's per-device workload, multiplied).

Model weights are random-init + Q15 PTQ (throughput does not depend on
training); the fleet's bit-identity contract vs the single engine is
asserted in tests/test_fleet.py, not here.
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import jax
import numpy as np

from repro.core import fastgrnn as fg
from repro.core.quantization import quantize_params, QuantConfig
from repro.data import hapt
from repro.kernels import enable_compile_cache
from repro.kernels.fastgrnn_cell.ops import Q15StreamStep
from repro.obs import MetricsRegistry, Observability, TRANSFER_KEYS
from repro.serve.fleet import FleetConfig, FleetEngine
from repro.serve.streaming import StreamingConfig


def _build_fleet(qp, shards: int, slots: int, backend: str,
                 windows_per_stream: int, placement: str,
                 obs=None) -> FleetEngine:
    ring = 128 * windows_per_stream
    stream = StreamingConfig(max_slots=slots, backend=backend,
                             batch_events=True,     # columnar emission —
                             # a lockstep window boundary emits the whole
                             # fleet at once; per-object events would cost
                             # more than the tick's model math
                             ring_capacity=ring, max_ring_capacity=ring)
    # max_pending_per_shard=0: a full home shard overflows to the least-
    # loaded shard instead of queueing, so a fleet filled to exactly its
    # capacity is 100% resident — the steady-state regime (every slot
    # advances every tick) the throughput numbers are defined over.
    return FleetEngine(qp, FleetConfig(shards=shards, stream=stream,
                                       max_pending_per_shard=0,
                                       placement=placement), obs=obs)


def _fill(fleet: FleetEngine, src: np.ndarray, n_streams: int,
          windows_per_stream: int) -> None:
    total = 128 * windows_per_stream
    for i in range(n_streams):
        fleet.attach(f"s{i}", total_steps=total)
        fleet.feed(f"s{i}", np.tile(src[i % len(src)],
                                    (windows_per_stream, 1)))


def _run(fleet: FleetEngine, n_streams: int,
         windows_per_stream: int) -> dict:
    total = 128 * windows_per_stream
    fleet.step()                                 # warm-up tick (jit compile)
    # steady-window transfer accounting: the ticks right after warm-up
    # are emission-free (the first window boundary is tick 128), so the
    # h-state byte deltas over this window are the device-residency
    # gate — zero on the resident jit/pallas paths
    steady = min(16, total - 2)
    tr0 = fleet.stats()["transfers"]
    tick_s = []
    t_start = time.perf_counter()
    done = 1
    tr1 = tr0
    while done < total:
        t0 = time.perf_counter()
        fleet.step()
        tick_s.append(time.perf_counter() - t0)
        done += 1
        if done == 1 + steady:
            tr1 = fleet.stats()["transfers"]
    elapsed = time.perf_counter() - t_start
    stats = fleet.stats()
    assert stats["completed"] == n_streams, stats
    steps = n_streams * (total - 1)              # steps in the timed region
    tick_ms = np.asarray(tick_s) * 1e3
    transfers = {k: int(tr1[k] - tr0[k]) for k in TRANSFER_KEYS}
    return {
        "concurrent_streams": n_streams,
        "ticks": len(tick_s),
        "stream_steps_per_sec": round(steps / elapsed, 1),
        "p50_ms": round(float(np.percentile(tick_ms, 50)), 4),
        "p99_ms": round(float(np.percentile(tick_ms, 99)), 4),
        "realtime_streams_50hz": int(steps / elapsed / 50.0),
        "steady_ticks_measured": int(steady),
        "transfers": transfers,
        "zero_copy_h": transfers["h_h2d_bytes"] == 0
        and transfers["h_d2h_bytes"] == 0,
        "scheduler": {k: stats["scheduler"][k] for k in
                      ("admissions", "recycles", "spills", "peak_active")},
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="BENCH_fleet.json")
    parser.add_argument("--backend", default="jit",
                        choices=("exact", "jit", "pallas"))
    parser.add_argument("--placement", default="host,devices",
                        help="comma-separated shard-placement sweep: 'host' "
                             "fuses all shards into one dispatch (the fast "
                             "small-core CPU configuration), 'devices' "
                             "round-robins shards over jax devices and "
                             "issues every group's dispatch before waiting "
                             "on any (skipped when fewer than 2 devices "
                             "exist or the backend is exact)")
    parser.add_argument("--slots-per-shard", type=int, default=1024)
    parser.add_argument("--shards", default="1,2,4,8",
                        help="comma-separated shard counts for the scaling "
                             "sweep")
    parser.add_argument("--capacity-shards", type=int, default=8)
    parser.add_argument("--capacity-slots", type=int, default=16384,
                        help="slots per shard for the capacity point "
                             "(8 x 16384 = 131,072 resident streams)")
    parser.add_argument("--windows", type=int, default=3,
                        help="128-sample windows per stream")
    parser.add_argument("--reps", type=int, default=3,
                        help="repetitions per scaling row (median-of)")
    parser.add_argument("--metrics-out", default=None,
                        help="attach the repro.obs metrics registry and "
                             "write its snapshot (schema "
                             "'metrics_snapshot') to this path")
    parser.add_argument("--smoke", action="store_true",
                        help="CI configuration: tiny fleet, 1 window")
    args = parser.parse_args()
    enable_compile_cache()
    if args.smoke:
        args.shards, args.slots_per_shard = "1,2", 256
        args.capacity_shards, args.capacity_slots = 4, 256
        args.windows, args.reps = 1, 1
    shard_counts = [int(s) for s in args.shards.split(",")]
    placements = [p.strip() for p in args.placement.split(",") if p.strip()]
    resolved = []
    for p in placements:
        if p == "devices" and (args.backend == "exact"
                               or len(jax.devices()) < 2):
            print(f"skipping placement='devices' (backend={args.backend}, "
                  f"{len(jax.devices())} jax device(s)); run with "
                  f"XLA_FLAGS=--xla_force_host_platform_device_count=N to "
                  f"fake a multi-device CPU topology", flush=True)
            continue
        resolved.append(p)
    if not resolved:
        resolved = ["host"]
    # metrics-only bundle (no tracer): the timed path stays NullTracer
    obs = (Observability(metrics=MetricsRegistry())
           if args.metrics_out else None)

    cfg = fg.FastGRNNConfig(rank_w=2, rank_u=8)
    qp = quantize_params(fg.init_params(cfg, jax.random.PRNGKey(0)),
                         QuantConfig())
    src = hapt.load("test", n=256).windows

    rows = []
    for placement in resolved:
        base = None
        for n in shard_counts:
            n_streams = n * args.slots_per_shard
            reps = []
            for _ in range(max(1, args.reps)):   # median-of-N: small boxes
                fleet = _build_fleet(qp, n, args.slots_per_shard,
                                     args.backend, args.windows, placement,
                                     obs=obs)
                _fill(fleet, src, n_streams, args.windows)
                reps.append(_run(fleet, n_streams, args.windows))
            reps.sort(key=lambda r: r["stream_steps_per_sec"])
            row = {"shards": n, "placement": placement,
                   **reps[len(reps) // 2]}        # jitter badly
            rows.append(row)
            if base is None:
                base = row["stream_steps_per_sec"]
            row["scaling_x"] = round(row["stream_steps_per_sec"] / base, 2)
            row["scaling_efficiency"] = round(
                row["scaling_x"] / (n / shard_counts[0]), 3)
            print(f"{placement:7s} {n:2d} shards x {args.slots_per_shard}: "
                  f"{row['stream_steps_per_sec']:>12,.0f} steps/s  "
                  f"x{row['scaling_x']:.2f} vs 1 shard  "
                  f"eff {row['scaling_efficiency']:.3f}  "
                  f"p50 {row['p50_ms']:.3f} ms  "
                  f"zero_copy_h={row['zero_copy_h']}", flush=True)

    cap_placement = resolved[0]
    cap_streams = args.capacity_shards * args.capacity_slots
    cap_runs = []
    for rep in range(max(1, args.reps)):   # median-of-N, same as the rows
        cap_fleet = _build_fleet(qp, args.capacity_shards,
                                 args.capacity_slots, args.backend,
                                 args.windows, cap_placement, obs=obs)
        print(f"capacity rep {rep + 1}: filling {cap_streams:,} streams "
              f"...", flush=True)
        _fill(cap_fleet, src, cap_streams, args.windows)
        cap_runs.append(_run(cap_fleet, cap_streams, args.windows))
    cap_runs.sort(key=lambda r: r["stream_steps_per_sec"])
    capacity = {"shards": args.capacity_shards,
                "slots_per_shard": args.capacity_slots,
                "placement": cap_placement,
                **cap_runs[len(cap_runs) // 2]}
    capacity["sustained_realtime_50hz"] = bool(
        capacity["realtime_streams_50hz"] >= cap_streams)
    print(f"capacity: {cap_streams:,} concurrent streams, "
          f"{capacity['stream_steps_per_sec']:>12,.0f} steps/s = "
          f"{capacity['realtime_streams_50hz']:,} real-time 50 Hz sensors "
          f"(sustained: {capacity['sustained_realtime_50hz']})", flush=True)

    # work per stream-step from the cell's shapes (a host rate is not a
    # device metric: the kernel's roofline share comes from the device
    # trace, bench/metrics/q15_step_roofline.py)
    kern = Q15StreamStep(qp, backend=args.backend)
    record = {
        "benchmark": "fleet_sharding",
        "model": "FastGRNN H=16 r_w=2 r_u=8, Q15 PTQ (566-byte class)",
        "backend": args.backend,
        "placement": cap_placement,
        "placements": resolved,
        "slots_per_shard": args.slots_per_shard,
        "window": 128,
        "sample_rate_hz": 50.0,
        "host": {"platform": platform.platform(),
                 "cpus": __import__("os").cpu_count(),
                 "jax": jax.__version__,
                 "devices": len(jax.devices()),
                 "device": str(jax.devices()[0])},
        "results": rows,
        "scaling_1_to_max_x": max(
            r["scaling_x"] for r in rows if r["placement"] == cap_placement),
        "scaling_by_placement": {
            p: max(r["scaling_x"] for r in rows if r["placement"] == p)
            for p in resolved},
        "capacity": capacity,
        "kernel_roofline": kern.work_per_stream_step(),
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(f"wrote {args.out}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(obs.metrics.dumps() + "\n")
        print(f"wrote {args.metrics_out}")


if __name__ == "__main__":
    main()
