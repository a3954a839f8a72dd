"""Crash-failover benchmark: how fast a dead 16k-stream shard comes back.

    PYTHONPATH=src python -m benchmarks.failover_bench \
        [--out BENCH_failover.json] [--backend jit] \
        [--shards 2] [--slots-per-shard 16384] [--snapshot-every 16] \
        [--samples 256] [--reps 5] [--smoke]

Measures the two failover costs on a fully-resident fleet:

* **snapshot_ms** — one full checkpoint pass (``FleetEngine.snapshot_now``):
  wire-encode every live stream's :class:`StreamState` into the snapshot
  store.  This is the steady-state tax paid every ``snapshot_every`` ticks.
* **recovery_ms** — ``FleetEngine.crash_shard(0)``: drop the shard's
  engine, build a replacement, decode every lost stream's snapshot and
  queue its journal replay.  This is the unavailability window of the
  crashed shard's streams (the paper-level claim: recovery is a bounded
  engineering cost, correctness is free — bit-exactness is gated in
  tests/test_failover.py, not here).

The default configuration kills a shard holding 16,384 resident streams
(the capacity-unit shard width of ``fleet_bench.py``) and reports
median/p99 over ``--reps`` crash/rebuild cycles.
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
from repro.serve.fleet import FleetConfig, FleetEngine
from repro.serve.streaming import StreamingConfig
from repro.kernels import enable_compile_cache


def _build(qp, args, obs=None) -> FleetEngine:
    stream = StreamingConfig(
        max_slots=args.slots_per_shard, backend=args.backend,
        batch_events=True, ring_capacity=args.samples,
        max_ring_capacity=args.samples)
    return FleetEngine(qp, FleetConfig(
        shards=args.shards, stream=stream, max_pending_per_shard=0,
        placement="host", snapshot_every=args.snapshot_every),
        obs=obs)


def _fill(fleet: FleetEngine, src: np.ndarray, n_streams: int,
          samples: int) -> None:
    reps = -(-samples // (len(src[0])))          # ceil windows per stream
    for i in range(n_streams):
        fleet.attach(f"s{i}", total_steps=None)
        fleet.feed(f"s{i}", np.tile(src[i % len(src)], (reps, 1))[:samples])


def _one_rep(qp, src, args, rep: int, obs=None) -> dict:
    fleet = _build(qp, args, obs=obs)
    n_streams = args.shards * args.slots_per_shard
    _fill(fleet, src, n_streams, args.samples)
    for _ in range(args.ticks_before):           # reach steady state (the
        fleet.step()                             # cadence checkpoints too)
    t0 = time.perf_counter()
    stored = fleet.snapshot_now()
    snapshot_ms = (time.perf_counter() - t0) * 1e3
    for _ in range(args.ticks_between):          # dirty the journal a bit
        fleet.step()
    t0 = time.perf_counter()
    report = fleet.crash_shard(0)
    recovery_ms = (time.perf_counter() - t0) * 1e3
    assert stored == n_streams, (stored, n_streams)
    assert report["streams_recovered"] == args.slots_per_shard, report
    return {
        "rep": rep,
        "streams_recovered": report["streams_recovered"],
        "replayed_samples": report["replayed_samples"],
        "wire_bytes": report["wire_bytes"],
        "snapshot_ms": round(snapshot_ms, 3),
        "recovery_ms": round(recovery_ms, 3),
        "recovery_us_per_stream": round(
            recovery_ms * 1e3 / report["streams_recovered"], 3),
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="BENCH_failover.json")
    parser.add_argument("--backend", default="jit",
                        choices=("exact", "jit", "pallas"))
    parser.add_argument("--shards", type=int, default=2)
    parser.add_argument("--slots-per-shard", type=int, default=16384,
                        help="streams lost when shard 0 dies (the "
                             "fleet_bench capacity-unit width)")
    parser.add_argument("--snapshot-every", type=int, default=16)
    parser.add_argument("--samples", type=int, default=256,
                        help="samples buffered per stream")
    parser.add_argument("--ticks-before", type=int, default=20)
    parser.add_argument("--ticks-between", type=int, default=8,
                        help="ticks between the timed checkpoint and the "
                             "crash (journal depth at recovery)")
    parser.add_argument("--reps", type=int, default=5)
    parser.add_argument("--smoke", action="store_true",
                        help="CI configuration: tiny fleet, 2 reps")
    parser.add_argument("--metrics-out", default=None, metavar="PATH",
                        help="also dump a metrics_snapshot JSON: the "
                             "fleet's registry (tick/crash series plus "
                             "numeric-health counters) across all reps")
    args = parser.parse_args()
    enable_compile_cache()
    if args.smoke:
        args.slots_per_shard, args.samples = 256, 64
        args.ticks_before, args.reps = 10, 2

    cfg = fg.FastGRNNConfig(rank_w=2, rank_u=8)
    qp = quantize_params(fg.init_params(cfg, jax.random.PRNGKey(0)),
                         QuantConfig())
    src = hapt.load("test", n=256).windows

    obs = None
    if args.metrics_out:
        from repro.obs import MetricsRegistry, Observability
        from repro.obs.numerics import NumericsMonitor
        obs = Observability(metrics=MetricsRegistry(),
                            numerics=NumericsMonitor())

    rows = []
    for rep in range(args.reps):
        row = _one_rep(qp, src, args, rep, obs=obs)
        rows.append(row)
        print(f"rep {rep}: snapshot {row['snapshot_ms']:8.1f} ms   "
              f"crash+recover {row['recovery_ms']:8.1f} ms   "
              f"({row['streams_recovered']:,} streams, "
              f"{row['replayed_samples']:,} samples replayed)", flush=True)

    rec = np.array([r["recovery_ms"] for r in rows])
    snap = np.array([r["snapshot_ms"] for r in rows])
    recovery = {
        "streams": args.slots_per_shard,
        "recovery_ms_p50": round(float(np.percentile(rec, 50)), 3),
        "recovery_ms_p99": round(float(np.percentile(rec, 99)), 3),
        "snapshot_ms_p50": round(float(np.percentile(snap, 50)), 3),
        "recovery_us_per_stream_p50": round(float(np.percentile(
            [r["recovery_us_per_stream"] for r in rows], 50)), 3),
        "wire_mb_per_shard": round(
            rows[0]["wire_bytes"] / 1e6, 3),
    }
    print(f"recovery of a {args.slots_per_shard:,}-stream shard: "
          f"p50 {recovery['recovery_ms_p50']:.1f} ms, "
          f"p99 {recovery['recovery_ms_p99']:.1f} ms "
          f"({recovery['recovery_us_per_stream_p50']:.1f} us/stream)",
          flush=True)

    record = {
        "benchmark": "fleet_failover",
        "model": "FastGRNN H=16 r_w=2 r_u=8, Q15 PTQ (566-byte class)",
        "backend": args.backend,
        "shards": args.shards,
        "slots_per_shard": args.slots_per_shard,
        "snapshot_every": args.snapshot_every,
        "samples_per_stream": args.samples,
        "host": {"platform": platform.platform(),
                 "cpus": __import__("os").cpu_count(),
                 "jax": jax.__version__,
                 "device": str(jax.devices()[0])},
        "results": rows,
        "recovery": recovery,
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(f"wrote {args.out}")
    if obs is not None:
        with open(args.metrics_out, "w") as f:
            f.write(obs.metrics.dumps() + "\n")
        print(f"wrote {args.metrics_out}")


if __name__ == "__main__":
    main()
