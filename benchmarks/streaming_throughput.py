"""Streaming engine throughput: streams/sec + per-step latency percentiles.

    PYTHONPATH=src python -m benchmarks.streaming_throughput \
        [--out BENCH_streaming.json] [--backends exact,jit] [--windows 2] \
        [--shards N]

``--shards N`` (N > 1) drives the identical protocol through the sharded
``serve/fleet.FleetEngine`` front door — the slot budget splits across N
per-shard slot schedulers ticked by one fused kernel dispatch; see
``benchmarks/fleet_bench.py`` for the dedicated scaling/capacity study.

Drives the multi-stream engine at several concurrency levels with every
slot busy each tick (the steady-state regime: N live 50 Hz sensors), and
emits a JSON perf record so later PRs have a trajectory:

  * ``stream_steps_per_sec`` — total samples advanced per wall second;
  * ``streams_per_sec``      — completed 128-sample windows per second;
  * ``p50_ms`` / ``p99_ms``  — per-tick (one step across all streams)
    latency percentiles;
  * ``realtime_streams_50hz`` — how many live 50 Hz sensors this single
    process sustains in real time (stream_steps_per_sec / 50).

Model weights are random-init + Q15 PTQ (throughput does not depend on
training); the exact backend's bit-identity contract is asserted in
tests/test_streaming.py, not here.
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
from repro.obs import MetricsRegistry, Observability
from repro.serve.fleet import FleetConfig, FleetEngine
from repro.serve.streaming import StreamingEngine, StreamingConfig
from repro.kernels import enable_compile_cache

FULL = os.environ.get("REPRO_FULL", "0") == "1"
CONCURRENCY = (256, 1024, 2048, 4096) if FULL else (256, 1024, 2048)


def _make_engine(qp, n_streams: int, backend: str, shards: int, obs=None):
    """--shards > 1 drives the identical protocol through the sharded
    fleet front door (serve/fleet) instead of one StreamingEngine — the
    slot budget is split across per-shard schedulers."""
    if shards <= 1:
        return StreamingEngine(
            qp, StreamingConfig(max_slots=n_streams, backend=backend),
            obs=obs)
    per_shard = max(1, n_streams // shards)
    return FleetEngine(qp, FleetConfig(
        shards=shards, max_pending_per_shard=0, placement="host",
        stream=StreamingConfig(max_slots=per_shard, backend=backend)),
        obs=obs)


def bench_backend(backend: str, windows: np.ndarray, n_windows: int,
                  qp, concurrency=CONCURRENCY, shards: int = 1,
                  obs=None) -> list[dict]:
    rows = []
    for n_streams in concurrency:
        eng = _make_engine(qp, n_streams, backend, shards, obs=obs)
        n_streams = (n_streams if shards <= 1
                     else shards * max(1, n_streams // shards))
        src = windows[np.arange(n_streams) % len(windows)]
        total = 128 * n_windows
        for i in range(n_streams):
            eng.attach(f"s{i}", total_steps=total)
            eng.feed(f"s{i}", np.tile(src[i], (n_windows, 1)))
        eng.step()                               # warm-up tick (jit compile)
        tick_s = []
        t_start = time.perf_counter()
        done = 1
        while done < total:
            t0 = time.perf_counter()
            eng.step()
            tick_s.append(time.perf_counter() - t0)
            done += 1
        elapsed = time.perf_counter() - t_start
        stats = eng.stats()
        assert stats["completed"] == n_streams, stats
        steps = n_streams * (total - 1)          # steps in the timed region
        tick_ms = np.asarray(tick_s) * 1e3
        rows.append({
            "backend": backend,
            "concurrent_streams": n_streams,
            "ticks": len(tick_s),
            "stream_steps_per_sec": round(steps / elapsed, 1),
            "streams_per_sec": round(n_streams * n_windows / elapsed, 2),
            "p50_ms": round(float(np.percentile(tick_ms, 50)), 4),
            "p99_ms": round(float(np.percentile(tick_ms, 99)), 4),
            "mean_ms": round(float(np.mean(tick_ms)), 4),
            "realtime_streams_50hz": int(steps / elapsed / 50.0),
        })
        print(f"{backend:6s} S={n_streams:5d}: "
              f"{rows[-1]['stream_steps_per_sec']:>12,.0f} steps/s  "
              f"{rows[-1]['streams_per_sec']:>8.1f} windows/s  "
              f"p50 {rows[-1]['p50_ms']:.3f} ms  p99 {rows[-1]['p99_ms']:.3f} ms",
              flush=True)
    return rows


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="BENCH_streaming.json")
    parser.add_argument("--backends", default="exact,jit")
    parser.add_argument("--windows", type=int, default=2,
                        help="128-sample windows per stream")
    parser.add_argument("--concurrency", default=None,
                        help="comma-separated stream counts (CI smoke: 64)")
    parser.add_argument("--shards", type=int, default=1,
                        help="> 1: drive the same protocol through the "
                             "sharded FleetEngine (serve/fleet)")
    parser.add_argument("--metrics-out", default=None,
                        help="also run with the repro.obs metrics registry "
                             "attached and write its snapshot (schema "
                             "'metrics_snapshot') to this path")
    args = parser.parse_args()
    enable_compile_cache()
    concurrency = (tuple(int(c) for c in args.concurrency.split(","))
                   if args.concurrency else CONCURRENCY)
    # metrics-only bundle: counters/gauges/histograms accumulate across
    # every row; no tracer, so the measured path stays the NullTracer one
    obs = (Observability(metrics=MetricsRegistry())
           if args.metrics_out else None)

    cfg = fg.FastGRNNConfig(rank_w=2, rank_u=8)
    qp = quantize_params(fg.init_params(cfg, jax.random.PRNGKey(0)),
                         QuantConfig())
    windows = hapt.load("test", n=256).windows

    rows = []
    for backend in args.backends.split(","):
        rows += bench_backend(backend.strip(), windows, args.windows, qp,
                              concurrency, shards=args.shards, obs=obs)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(obs.metrics.dumps() + "\n")
        print(f"wrote {args.metrics_out}")

    record = {
        "benchmark": "streaming_throughput",
        "model": "FastGRNN H=16 r_w=2 r_u=8, Q15 PTQ (566-byte class)",
        "sample_rate_hz": 50.0,
        "window": 128,
        "shards": args.shards,
        "host": {"platform": platform.platform(),
                 "jax": jax.__version__,
                 "device": str(jax.devices()[0])},
        "results": rows,
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
