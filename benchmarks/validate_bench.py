"""Schema validation for the emitted BENCH_*.json perf artifacts.

    PYTHONPATH=src python -m benchmarks.validate_bench BENCH_*.json

CI's bench-smoke job regenerates the benchmarks in a tiny configuration
and runs this validator over the output, so a refactor that silently
breaks a bench (missing key, NaN/inf throughput, empty results) fails the
build instead of rotting the perf trajectory.

Each known ``benchmark`` kind pins its required top-level keys and, where
the record carries a ``results`` list, the required per-row keys.  Every
numeric value anywhere in the record must be finite.
"""
from __future__ import annotations

import json
import math
import sys

SCHEMAS: dict[str, dict] = {
    "streaming_throughput": {
        "top": ["benchmark", "model", "sample_rate_hz", "window", "host",
                "results"],
        "row": ["backend", "concurrent_streams", "ticks",
                "stream_steps_per_sec", "streams_per_sec", "p50_ms",
                "p99_ms", "realtime_streams_50hz"],
    },
    "serve_continuous_batching": {
        "top": ["benchmark", "model", "slots", "requests", "budgets",
                "host", "results", "speedup_tokens_per_sec"],
        "row": ["mode", "admit_policy", "requests", "tokens", "wall_s",
                "tokens_per_sec", "decode_ticks", "prefills", "scheduler"],
    },
    "deploy_export": {
        "top": ["benchmark", "model", "host", "image", "budgets", "qvm",
                "c_host", "parity", "mcu_cycle_model"],
    },
    # benchmarks/fleet_bench.py: shard-count scaling sweep + the 100k+
    # concurrent-stream capacity point.  `capacity` pins the headline
    # claims (concurrent_streams, realtime_streams_50hz) so the artifact
    # cannot silently drop them.
    "fleet_sharding": {
        "top": ["benchmark", "model", "backend", "placement", "placements",
                "slots_per_shard", "window", "sample_rate_hz", "host",
                "results", "scaling_1_to_max_x", "scaling_by_placement",
                "capacity", "kernel_roofline"],
        "row": ["shards", "placement", "concurrent_streams", "ticks",
                "stream_steps_per_sec", "p50_ms", "p99_ms",
                "realtime_streams_50hz", "scaling_x",
                "scaling_efficiency", "transfers", "zero_copy_h",
                "scheduler"],
        "capacity": ["shards", "slots_per_shard", "placement",
                     "concurrent_streams", "stream_steps_per_sec",
                     "realtime_streams_50hz", "sustained_realtime_50hz",
                     "transfers", "zero_copy_h"],
        # device-residency gate: h-state bytes over the steady window
        # (repro.obs.transfers.TRANSFER_KEYS, per-row under "transfers")
        # (achieved-vs-peak keys only in a run on a chip with peaks)
        "kernel_roofline": ["backend", "model_flops_per_stream_step",
                            "hbm_bytes_per_stream_step"],
    },
    # benchmarks/failover_bench.py: crash/recovery latency for a shard
    # holding `slots_per_shard` streams.  `recovery` pins the headline
    # (p50/p99 unavailability window of a 16k-stream shard crash).
    "fleet_failover": {
        "top": ["benchmark", "model", "backend", "shards",
                "slots_per_shard", "snapshot_every", "samples_per_stream",
                "host", "results", "recovery"],
        "row": ["rep", "streams_recovered", "replayed_samples",
                "wire_bytes", "snapshot_ms", "recovery_ms",
                "recovery_us_per_stream"],
        "recovery": ["streams", "recovery_ms_p50", "recovery_ms_p99",
                     "snapshot_ms_p50", "recovery_us_per_stream_p50",
                     "wire_mb_per_shard"],
    },
    # `python -m repro.compress --report`: one compression-pipeline run.
    # `size` is ModelArtifact.size_report() — per-tensor dense vs
    # CSR-packed bytes at the artifact's true weight width (Q15/Q7).
    "compress_artifact": {
        "top": ["benchmark", "pipeline", "sha256", "artifact_bytes",
                "size", "provenance"],
        "size": ["bits", "weight_bytes_dense", "weight_bytes_packed",
                 "tensors", "passes"],
    },
    # repro.obs.MetricsRegistry.snapshot(): the serving stack's metrics
    # export (written by --metrics-out on streaming_throughput /
    # fleet_bench / serve_demo).  Deep-checked by _check_metrics_snapshot
    # below: log2 bucket ladder, bucket-count conservation, counter
    # non-negativity.
    "metrics_snapshot": {
        "top": ["benchmark", "schema_version", "deterministic",
                "counters", "gauges", "histograms"],
    },
    # `python -m repro.analysis`: the static-analysis gate's report —
    # qlint per-site proven bounds + detlint findings/suppressions.
    # Deep-checked by _check_analysis_report below: per-target and
    # per-site keys, finding/suppression shape, summary consistency.
    "analysis_report": {
        "top": ["benchmark", "schema_version", "qlint", "detlint",
                "summary"],
        "summary": ["findings", "suppressed", "ok"],
    },
    # benchmarks/numerics_bench.py: numeric-health monitor overhead
    # budget, qvm<->C saturation-counter parity (incl. the stress
    # witness), the drift-injection demo, and the static/dynamic
    # saturation cross-check verdict.
    "numerics_health": {
        "top": ["benchmark", "model", "backend", "host", "config",
                "overhead", "budgets", "counter_parity", "drift_demo",
                "crosscheck"],
        "overhead": ["baseline_steps_per_sec", "null_steps_per_sec",
                     "monitored_steps_per_sec", "null_overhead_pct",
                     "monitored_overhead_pct", "monitor_marginal_pct",
                     "measured_noise_pct"],
        "budgets": ["monitored_budget_pct", "monitored_within_budget",
                    "null_budget_pct", "null_within_noise"],
        "counter_parity": ["windows", "stress_gain", "available",
                           "counters_equal", "preds_equal",
                           "stress_counters_equal", "stress_h_next"],
        "drift_demo": ["scales", "drift_scores", "monotone"],
        "crosscheck": ["ok", "violations", "witnessed",
                       "unwitnessed_reachable"],
    },
    # benchmarks/obs_bench.py: telemetry overhead budgets + tick-phase
    # breakdown + deadline-miss rate + flight-recorder byte stability.
    "obs_overhead": {
        "top": ["benchmark", "model", "backend", "window",
                "sample_rate_hz", "host", "config", "baseline", "traced",
                "budgets", "phases", "deadline", "flight_recorder"],
        "baseline": ["concurrent_streams", "ticks",
                     "stream_steps_per_sec", "p50_ms", "p99_ms"],
        "traced": ["concurrent_streams", "ticks", "stream_steps_per_sec",
                   "p50_ms", "p99_ms"],
        "budgets": ["traced_overhead_pct", "traced_budget_pct",
                    "traced_within_budget", "null_budget_pct"],
        "deadline": ["deadline_ms", "concurrent_streams", "miss_ticks",
                     "miss_stream_ticks", "stream_ticks", "miss_rate"],
        "flight_recorder": ["shards", "crashes", "dump_bytes",
                            "byte_stable"],
    },
}

#: The canonical metrics-snapshot bucket ladder (mirrors
#: repro.obs.metrics.BUCKET_EDGES_US; duplicated so this validator stays
#: dependency-free, with tests/test_obs.py pinning the real one).
_BUCKET_EDGES_US = [2 ** k for k in range(22)]


def _check_metrics_snapshot(record: dict, path: str,
                            errors: list[str]) -> None:
    """Deep checks beyond key presence: the parts of the snapshot schema
    a refactor could silently break without dropping a key."""
    for name, v in record.get("counters", {}).items():
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            errors.append(f"{path}: counter {name!r} must be a "
                          f"non-negative int, got {v!r}")
    for name, h in record.get("histograms", {}).items():
        if not isinstance(h, dict):
            errors.append(f"{path}: histogram {name!r} must be an object")
            continue
        if list(h.get("buckets_us", [])) != _BUCKET_EDGES_US:
            errors.append(f"{path}: histogram {name!r} bucket ladder "
                          f"differs from the canonical log2 edges")
        counts = h.get("counts", [])
        if len(counts) != len(_BUCKET_EDGES_US) + 1:
            errors.append(f"{path}: histogram {name!r} counts length "
                          f"{len(counts)} != {len(_BUCKET_EDGES_US) + 1}")
        elif sum(counts) != h.get("count"):
            errors.append(f"{path}: histogram {name!r} bucket counts sum "
                          f"{sum(counts)} != count {h.get('count')}")


_TARGET_KEYS = ["name", "bits", "low_rank", "arch", "checks", "n_sites",
                "sites", "saturation", "state_closed", "findings",
                "proved_overflow_free"]
_SITE_KEYS = ["site", "op", "declared_bits", "lo", "hi", "bits_needed",
              "margin_bits"]


def _check_analysis_report(record: dict, path: str,
                           errors: list[str]) -> None:
    """Deep checks for the repro.analysis report: every qlint target
    carries a full per-site proof table, findings/suppressions are
    well-formed, and the summary counts are consistent."""
    targets = record.get("qlint", {}).get("targets")
    if not isinstance(targets, list):
        errors.append(f"{path}: qlint.targets must be a list")
        return
    n_findings = 0
    for t in targets:
        tname = t.get("name", "?")
        for key in _TARGET_KEYS:
            if key not in t:
                errors.append(f"{path}: target {tname!r} missing {key!r}")
        for i, s in enumerate(t.get("sites", [])):
            for key in _SITE_KEYS:
                if key not in s:
                    errors.append(f"{path}: target {tname!r} sites[{i}] "
                                  f"missing {key!r}")
        n_findings += len(t.get("findings", []))
        if t.get("proved_overflow_free") != (not t.get("findings")):
            errors.append(f"{path}: target {tname!r} "
                          f"proved_overflow_free inconsistent with its "
                          f"findings list")
    det = record.get("detlint", {})
    n_suppressed = 0
    if not det.get("skipped"):
        for key in ("root", "files", "checks", "findings", "suppressions"):
            if key not in det:
                errors.append(f"{path}: detlint missing key {key!r}")
        for f in det.get("findings", []):
            if not all(k in f for k in ("check", "where", "message")):
                errors.append(f"{path}: malformed detlint finding {f!r}")
        for s in det.get("suppressions", []):
            if not all(k in s for k in ("check", "where", "reason")):
                errors.append(f"{path}: malformed suppression {s!r}")
        n_findings += len(det.get("findings", []))
        n_suppressed = len(det.get("suppressions", []))
    summary = record.get("summary", {})
    if summary.get("findings") != n_findings:
        errors.append(f"{path}: summary.findings "
                      f"{summary.get('findings')} != counted {n_findings}")
    if summary.get("suppressed") != n_suppressed:
        errors.append(f"{path}: summary.suppressed "
                      f"{summary.get('suppressed')} != counted "
                      f"{n_suppressed}")
    if summary.get("ok") != (n_findings == 0):
        errors.append(f"{path}: summary.ok inconsistent with findings")


def _walk_numbers(obj, path, errors):
    if isinstance(obj, bool):
        return
    if isinstance(obj, (int, float)):
        if not math.isfinite(obj):
            errors.append(f"{path}: non-finite number {obj!r}")
    elif isinstance(obj, dict):
        for k, v in obj.items():
            _walk_numbers(v, f"{path}.{k}", errors)
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            _walk_numbers(v, f"{path}[{i}]", errors)


def validate(path: str) -> tuple[str | None, list[str]]:
    """-> (benchmark kind, list of schema errors; empty = valid)."""
    errors: list[str] = []
    try:
        with open(path) as f:
            record = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return None, [f"{path}: unreadable ({e})"]
    kind = record.get("benchmark")
    schema = SCHEMAS.get(kind)
    if schema is None:
        return kind, [f"{path}: unknown benchmark kind {kind!r} "
                      f"(known: {sorted(SCHEMAS)})"]
    for key in schema["top"]:
        if key not in record:
            errors.append(f"{path}: missing top-level key {key!r}")
    if kind == "metrics_snapshot" and not errors:
        _check_metrics_snapshot(record, path, errors)
    if kind == "analysis_report" and not errors:
        _check_analysis_report(record, path, errors)
    for sub in ("size", "capacity", "recovery", "baseline", "traced",
                "budgets", "deadline", "flight_recorder", "kernel_roofline",
                "summary", "overhead", "counter_parity", "drift_demo",
                "crosscheck"):
        if sub not in schema:
            continue
        block = record.get(sub)
        if not isinstance(block, dict):
            errors.append(f"{path}: {sub!r} must be an object")
        else:
            for key in schema[sub]:
                if key not in block:
                    errors.append(f"{path}: {sub} missing key {key!r}")
    rows = record.get("results")
    if "row" in schema:
        if not isinstance(rows, list) or not rows:
            errors.append(f"{path}: 'results' must be a non-empty list")
        else:
            for i, row in enumerate(rows):
                for key in schema["row"]:
                    if key not in row:
                        errors.append(
                            f"{path}: results[{i}] missing key {key!r}")
    _walk_numbers(record, path, errors)
    return kind, errors


def main(argv: list[str]) -> int:
    if not argv:
        print("usage: python -m benchmarks.validate_bench BENCH_*.json")
        return 2
    failures = 0
    for path in argv:
        kind, errors = validate(path)
        if errors:
            failures += 1
            for e in errors:
                print(f"FAIL  {e}")
        else:
            print(f"ok    {path} ({kind})")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
