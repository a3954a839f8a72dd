"""Deployment bench: export sizes, budget audits, qvm/C throughput, parity.

    PYTHONPATH=src python -m benchmarks.deploy_bench \
        [--out BENCH_deploy.json] [--windows 512] [--trained]

Emits a JSON perf+size record for the `repro.deploy` subsystem:

  * packed-image size breakdown + per-engine flash/SRAM budget audits
    against the avr / msp430 platform profiles (core/mcu.PLATFORMS);
  * qvm throughput: pure-integer emulated windows/s and stream-steps/s
    (batched over all windows in lockstep);
  * compiled-C throughput for both engines (host cc, includes pipe I/O);
  * the parity agreement matrix from repro.deploy.verify (bitwise float-C
    <-> oracle, bitwise int-C <-> qvm, argmax agreement everywhere);
  * the structural MCU latency model's per-step predictions for context
    (core/mcu — a fitted MODEL, not a measurement; labeled as such).

Default model is the deterministic random-init reference export (sizes
and throughput do not depend on training); ``--trained`` runs the pinned
parity-protocol model instead (slower: trains first).
"""
from __future__ import annotations

import argparse
import json
import platform as _platform
import tempfile
import time

import numpy as np

from repro.core import fastgrnn as fg, mcu
from repro.data import hapt
from repro.deploy import emit_c, verify
from repro.deploy.goldens import build_reference_model
from repro.deploy.image import size_report, audit_platforms
from repro.deploy.qvm import QVM
from repro.kernels import enable_compile_cache


def bench_qvm(vm: QVM, xq: np.ndarray, repeats: int = 3) -> dict:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        vm.run_windows(xq)
        best = min(best, time.perf_counter() - t0)
    n, t = xq.shape[0], xq.shape[1]
    return {
        "windows": int(n),
        "windows_per_sec": round(n / best, 1),
        "stream_steps_per_sec": round(n * t / best, 1),
        "realtime_streams_50hz": int(n * t / best / 50.0),
    }


def bench_c(img, xq: np.ndarray, engine: str) -> dict:
    with tempfile.TemporaryDirectory() as td:
        t0 = time.perf_counter()
        binary = emit_c.compile_host(img, td, engine=engine)
        build_s = time.perf_counter() - t0
        cm = emit_c.CHostModel(binary, img.H, img.C, engine=engine)
        t0 = time.perf_counter()
        cm.predict_batch(xq)
        run_s = time.perf_counter() - t0
    n, t = xq.shape[0], xq.shape[1]
    return {
        "engine": engine,
        "cc_build_s": round(build_s, 3),
        "windows_per_sec": round(n / run_s, 1),
        "stream_steps_per_sec": round(n * t / run_s, 1),
    }


def mcu_model_context(cfg: fg.FastGRNNConfig) -> dict:
    """Fitted cycle-model predictions (NOT measurements; see core/mcu)."""
    return {
        "disclaimer": "structural cycle MODEL fitted to the paper's "
                      "measured endpoints — not a measurement",
        "per_step_ms": {
            "arduino_lut": round(1e3 * mcu.step_latency_s(cfg, mcu.ARDUINO), 3),
            "msp430_lut": round(1e3 * mcu.step_latency_s(cfg, mcu.MSP430), 3),
            "msp430_no_lut": round(1e3 * mcu.step_latency_s(
                cfg, mcu.MSP430, lut=False), 1),
        },
        "msp430_lut_speedup": round(mcu.lut_speedup(cfg, mcu.MSP430), 1),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_deploy.json")
    ap.add_argument("--windows", type=int, default=512)
    ap.add_argument("--trained", action="store_true")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="also dump a metrics_snapshot JSON: bench "
                         "counters/gauges plus the monitored qvm's "
                         "numeric-health series over the same windows")
    args = ap.parse_args()
    enable_compile_cache()

    if args.trained:
        params, calib = verify.protocol_model()
        qp, _, img = build_reference_model(params=params, calib=calib)
        model_desc = f"trained parity protocol {verify.PROTOCOL}"
    else:
        qp, _, img = build_reference_model(seed=0)
        model_desc = "random-init reference export (seed 0)"

    test = hapt.load("test", n=args.windows)
    vm = QVM(img)
    xq = vm.quantize_input(test.windows)

    print("qvm bench ...", flush=True)
    qvm_rows = bench_qvm(vm, xq)
    c_rows = []
    if emit_c.find_cc():
        for engine in ("float", "int"):
            print(f"c {engine} bench ...", flush=True)
            c_rows.append(bench_c(img, xq, engine))
    print("parity ...", flush=True)
    parity = verify.run_parity(img, qp, test.windows, use_fp32=False)

    record = {
        "benchmark": "deploy_export",
        "model": model_desc,
        "host": {"platform": _platform.platform(),
                 "cc": emit_c.find_cc()},
        "image": size_report(img),
        "budgets": {e: audit_platforms(img, engine=e)
                    for e in ("float", "int")},
        "qvm": qvm_rows,
        "c_host": c_rows,
        "parity": {
            "n_windows": parity["n_windows"],
            "agreement": parity["agreement"],
            "pairwise": parity["pairwise"],
            "bitwise": parity["bitwise"],
        },
        "mcu_cycle_model": mcu_model_context(
            fg.FastGRNNConfig(rank_w=img.rank_w or None,
                              rank_u=img.rank_u or None)),
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(f"wrote {args.out}")
    if args.metrics_out:
        from repro.obs import MetricsRegistry
        from repro.obs.numerics import NumericsMonitor
        reg = MetricsRegistry()
        reg.counter("bench.deploy.windows",
                    "windows benched per engine path").inc(len(xq))
        reg.gauge("bench.deploy.qvm.steps_per_sec", wallclock=True).set(
            qvm_rows["stream_steps_per_sec"])
        for r in c_rows:
            reg.gauge(f"bench.deploy.c_{r['engine']}.steps_per_sec",
                      wallclock=True).set(r["stream_steps_per_sec"])
        mon = NumericsMonitor()
        QVM(img, monitor=mon).run_windows(xq)
        mon.publish(reg)
        with open(args.metrics_out, "w") as f:
            f.write(reg.dumps() + "\n")
        print(f"wrote {args.metrics_out}")
    print(f"  qvm: {qvm_rows['stream_steps_per_sec']:,.0f} steps/s "
          f"({qvm_rows['realtime_streams_50hz']:,} live 50 Hz sensors)")
    for r in c_rows:
        print(f"  c[{r['engine']}]: {r['stream_steps_per_sec']:,.0f} steps/s")


if __name__ == "__main__":
    main()
