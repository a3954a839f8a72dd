"""Serving demo: batched prefill + decode with the L-S-Q quantized path.

    PYTHONPATH=src python examples/serve_demo.py --arch mamba2-780m
    PYTHONPATH=src python examples/serve_demo.py --shards 4

Default mode runs a reduced LM through the serving engine twice — bf16
weights and int8 (Q7) per-tensor quantized weights (the paper's Q stage
at LM scale, via the same ``repro.compress.quantize_tree`` pass the
engine uses internally) — and reports tokens generated, agreement between
the two paths, the per-tree weight-byte saving, and the analytic HBM-byte
saving for the full config.

``--shards N`` (N > 1) instead drives the *sensor-fleet* serving path:
the same entry point stands up a sharded ``serve/fleet.FleetEngine``
(N per-shard slot schedulers, rendezvous routing, one fused Q15 kernel
dispatch per tick), classifies a batch of HAPT windows through it with a
forced mid-stream migration, and checks the fleet's predictions
bit-identically against the scalar QRuntime reference.
"""
import argparse

import jax
import numpy as np

import repro.configs as C
from repro.compress import tree_size_report
from repro.models import registry
from repro.serve.engine import Engine, ServeConfig
from repro.kernels import enable_compile_cache

parser = argparse.ArgumentParser()
parser.add_argument("--arch", default="deepseek-7b", choices=list(C.ARCHS))
parser.add_argument("--batch", type=int, default=4)
parser.add_argument("--new-tokens", type=int, default=24)
parser.add_argument("--shards", type=int, default=1,
                    help="> 1: demo the sharded Q15 sensor-fleet path "
                         "(serve/fleet) instead of the LM engine")
parser.add_argument("--metrics-out", default=None,
                    help="attach the repro.obs telemetry bundle (tracer + "
                         "metrics) and write the metrics snapshot JSON "
                         "(schema 'metrics_snapshot') to this path")
args = parser.parse_args()
enable_compile_cache()


def _make_obs():
    if not args.metrics_out:
        return None
    from repro.obs import Observability
    return Observability.full()


def _write_metrics(obs) -> None:
    if obs is None:
        return
    with open(args.metrics_out, "w") as f:
        f.write(obs.metrics.dumps() + "\n")
    phases = ", ".join(sorted(obs.tracer.phase_stats())) or "none"
    print(f"wrote {args.metrics_out} (traced phases: {phases})")


def fleet_demo(n_shards: int) -> None:
    from repro.core import fastgrnn as fg
    from repro.core.qruntime import QRuntime
    from repro.core.quantization import quantize_params, QuantConfig
    from repro.data import hapt
    from repro.serve.fleet import FleetConfig, FleetEngine
    from repro.serve.streaming import StreamingConfig

    obs = _make_obs()
    qp = quantize_params(
        fg.init_params(fg.FastGRNNConfig(rank_w=2, rank_u=8),
                       jax.random.PRNGKey(0)), QuantConfig())
    windows = hapt.load("test", n=96).windows
    fleet = FleetEngine(qp, FleetConfig(
        shards=n_shards, stream=StreamingConfig(max_slots=16)), obs=obs)
    for i, w in enumerate(windows):
        fleet.attach(f"sensor-{i}", w, total_steps=len(w))
    for _ in range(40):                      # advance mid-window...
        fleet.step()
    moved = fleet.migrate("sensor-0")        # ...then live-migrate one
    dst = fleet.shard_of("sensor-0")
    events = fleet.drain()
    preds = {}
    for e in events:
        for ev in (e.events() if hasattr(e, "events") else [e]):
            preds[ev.stream_id] = ev.prediction
    ref = QRuntime(qp).predict_batch(windows)
    agree = float(np.mean([preds[f"sensor-{i}"] == ref[i]
                           for i in range(len(windows))]))
    st = fleet.stats()
    print(f"fleet: {st['shards']} shards x "
          f"{st['per_shard'][0]['max_slots']} slots, "
          f"{st['completed']} streams classified, "
          f"{st['migrations']} live migration(s) "
          f"(sensor-0 re-attached {moved!r} on shard {dst})")
    print(f"scheduler roll-up: {st['scheduler']['admissions']} admissions, "
          f"{st['scheduler']['spills']} spills, "
          f"{st['scheduler']['evictions']} evictions across "
          f"{st['shards']} per-shard schedulers")
    print(f"bit-exactness vs scalar QRuntime: {agree * 100:.1f}% "
          f"({'OK' if agree == 1.0 else 'MISMATCH'})")
    _write_metrics(obs)


if args.shards > 1:
    fleet_demo(args.shards)
    raise SystemExit(0)

full = C.get(args.arch)
if not full.has_decode:
    raise SystemExit(f"{args.arch} is encoder-only: no decode path")
cfg = C.reduced(full, compute_dtype="float32", param_dtype="float32")
params = registry.init(cfg, jax.random.PRNGKey(0))
prompts = np.random.default_rng(0).integers(0, cfg.vocab_size,
                                            (args.batch, 12))

obs = _make_obs()
fp = Engine(cfg, params, ServeConfig(max_len=64), obs=obs)
q8 = Engine(cfg, params, ServeConfig(max_len=64, quant_bits=8))
out_fp = fp.generate(prompts, max_new=args.new_tokens)
out_q8 = q8.generate(prompts, max_new=args.new_tokens)
agree = float((out_fp == out_q8).mean())
print(f"generated {out_fp.shape[1]} tokens x {args.batch} sequences")
sched = fp.stats()["scheduler"]
print(f"scheduler: {sched['admissions']} admissions, "
      f"{sched['recycles']} recycles, {sched['spills']} spills "
      f"(continuous batching via serve/scheduler.py)")
print(f"bf16-vs-int8 token agreement: {agree*100:.1f}% "
      f"(greedy, random-init model — trained models track much closer)")

# the engine quantized through repro.compress.quantize_tree (the single
# home of the PTQ math); audit the quantized pytree it actually serves
srep = tree_size_report(q8.qparams, bits=8)
print(f"quantized tree: {srep['quantized_params']} int8 params, "
      f"{srep['weight_bytes_quantized']/1e6:.2f} MB vs "
      f"{srep['weight_bytes_bf16']/1e6:.2f} MB bf16 "
      f"({srep['compression_ratio']:.2f}x)")

n = registry.param_count(full)
print(f"full {args.arch}: {n/1e9:.2f}B params -> weight bytes/decode-step "
      f"{n*2/1e9:.2f} GB (bf16) vs {n/1e9:.2f} GB (int8): the decode "
      f"memory-roofline term halves (see EXPERIMENTS.md Sec. Perf)")
_write_metrics(obs)
