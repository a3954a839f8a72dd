"""Chip smoke test: the Q15 sensor fleet and the quantized LM head, compiled
on a TPU and checked against the exact reference.

    python chip_smoke.py               # one chip: fleet (pallas, jit) + LM
    python chip_smoke.py --four-chips  # four chips: one fleet shard per chip

Fleet phase: the paper's deployed cell (``goldens.build_reference_artifact``:
low-rank H=16, d=3, r_w=2, r_u=8, Q15) serves 8 shards x 16,384 resident
streams (the capacity geometry of ``BENCH_fleet.json``), two synthetic HAPT
windows each, once per device backend.  A seeded sample of streams is
replayed through the ``exact`` backend on the same samples: every emitted
prediction must be equal and the hidden trajectories within
:func:`h_tolerance`.
LM phase: a reduced deepseek-7b through the serving engine with int8
weights; its head must be the compiled ``q15_matmul`` kernel and agree with
the dequantize-then-matmul reference.  ``--four-chips`` runs the fleet
phase alone, one shard per chip, and checks that each shard's resident h
and kernel constants live on its own chip.

Lines before the last are smoke output, not benchmark figures.  The last
line is one JSON object naming the device.  Exits non-zero with no result
line unless JAX's first device is a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

SAMPLE_STREAMS = 4096
WINDOW = 128
POOL_WINDOWS = 2048      # synthetic HAPT windows the streams draw from


def h_tolerance(art) -> float:
    """Bound on max |h_device - h_exact| over every tapped step: what one
    LUT bucket can move h by in one step.  Both sides run the same f32 ops
    and no transcendentals (sigmoid and tanh are LUTs), so they differ
    only by f32 rounding order on the VPU/MXU (operation order, fused
    multiply-adds): an ulp or so per step.  The LUTs are exact but
    stepwise, so where a pre-activation sits within that ulp of a bucket
    edge the two sides read neighbouring buckets: h~ moves by a tanh-table
    step, weighted by zeta + nu, and z by a sigmoid-table step, weighted
    by |h - zeta h~| <= 2.  A larger gap means a weight, a table or the
    recurrence went wrong."""
    from repro.kernels.fastgrnn_cell.qstep import StepWeights
    sw = StepWeights.from_quantized(art.qp)
    gap = lambda t: float(np.max(np.abs(np.diff(t))))
    return (float(sw.zeta) + float(sw.nu)) * gap(sw.tanh_lut) \
        + 2.0 * gap(sw.sig_lut)


def check(ok: bool, what: str) -> None:
    """A smoke check that holds under ``python -O`` too."""
    if not ok:
        raise AssertionError(what)


def require_tpu():
    """The devices JAX found, or SystemExit naming the platform if the
    first is not a TPU (nothing here may fall back to the host)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found platform "
                         f"{devs[0].platform!r} ({devs[0].device_kind})")
    return devs


class CompileClock:
    """Seconds JAX spends in backend compiles, from its monitoring events."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration


def _events_by_stream(batches, keep) -> dict[str, list]:
    """(kind, step, prediction) per kept stream from columnar batches."""
    out: dict[str, list] = {}
    for b in batches:
        for i, sid in enumerate(b.stream_ids):
            if sid in keep:
                out.setdefault(sid, []).append(
                    ("final" if b.final[i] else "window", int(b.steps[i]),
                     int(b.predictions[i])))
    return out


def fleet_phase(art, *, backend: str, shards: int, slots: int,
                placement: str = "auto", sample: int = SAMPLE_STREAMS,
                seed: int = 0, windows: int = 2, clock=None) -> dict:
    """Serve ``shards * slots`` streams of ``windows`` synthetic HAPT
    windows through the fleet on ``backend``, replay a seeded sample of
    them through the ``exact`` backend, and raise AssertionError unless
    every sampled prediction agrees and max |dh| is within
    :func:`h_tolerance`.  Returns the
    comparison and run counts (and the fleet, for placement checks)."""
    from repro.data import hapt
    from repro.serve.fleet import FleetConfig, FleetEngine
    from repro.serve.streaming import StreamingConfig, StreamingEngine

    n, T = shards * slots, WINDOW * windows
    pool = hapt.generate_synthetic("test", seed, n=POOL_WINDOWS).windows
    rng = np.random.default_rng(seed)
    pick = rng.integers(0, len(pool), size=(n, windows))
    tapped = np.sort(rng.choice(n, size=min(sample, n), replace=False))
    is_tapped = np.zeros(n, bool)
    is_tapped[tapped] = True
    ids = [f"sensor-{i}" for i in range(n)]
    stream = lambda width, be: StreamingConfig(
        max_slots=width, backend=be, batch_events=True,
        ring_capacity=T, max_ring_capacity=T)

    fleet = FleetEngine.from_artifact(art, FleetConfig(
        shards=shards, stream=stream(slots, backend), placement=placement,
        max_pending_per_shard=0))
    for i in range(n):
        fleet.attach(ids[i], pool[pick[i]].reshape(T, -1), total_steps=T,
                     record_trajectory=bool(is_tapped[i]))
    check(fleet.n_active == n, f"{fleet.n_active} of {n} streams resident")
    compile0 = clock.seconds if clock else 0.0
    t0 = time.perf_counter()
    got = fleet.drain()
    wall = time.perf_counter() - t0
    ticks = fleet.stats()["ticks"]

    ref = StreamingEngine.from_artifact(art, stream(len(tapped), "exact"))
    for i in tapped:
        ref.attach(ids[i], pool[pick[i]].reshape(T, -1), total_steps=T,
                   record_trajectory=True)
    keep = {ids[i] for i in tapped}
    want_ev = _events_by_stream(ref.drain(), keep)
    got_ev = _events_by_stream(got, keep)

    n_events = sum(len(v) for v in want_ev.values())
    bad = [sid for sid in sorted(keep) if got_ev.get(sid) != want_ev[sid]]
    dh, equal_rows, rows = 0.0, 0, 0
    for sid in sorted(keep):
        h_got, h_want = fleet.trajectory(sid), ref.trajectory(sid)
        check(h_got.shape == h_want.shape == (T, art.qp.fp["b_z"].shape[0]),
              f"{sid}: trajectory shapes {h_got.shape}, {h_want.shape}")
        dh = max(dh, float(np.max(np.abs(h_got - h_want))))
        equal_rows += int(np.sum(np.all(
            h_got.view(np.int32) == h_want.view(np.int32), axis=1)))
        rows += T
    out = {"backend": backend, "streams": n, "shards": shards,
           "ticks": ticks, "sampled_streams": len(keep),
           "sampled_events": n_events, "prediction_mismatches": len(bad),
           "bitwise_equal_h_share": equal_rows / rows, "max_abs_dh": dh,
           "h_tol": h_tolerance(art),
           "compile_s": (clock.seconds - compile0) if clock else None,
           "drain_s": wall}
    check(n_events == len(keep) * windows, f"event count: {out}")
    check(not bad, f"predictions differ from exact on {bad[:5]}: {out}")
    check(np.isfinite(dh) and dh <= out["h_tol"], f"max |dh| too big: {out}")
    out["fleet"] = fleet
    return out


def fleet_per_chip(art, devs, *, slots: int, seed: int = 0,
                   clock=None) -> list[dict]:
    """The fleet phase with one shard per device of ``devs`` (the first
    ``len(devs)`` jax devices), on both device backends; raises unless
    each shard's resident h and kernel constants live on its own
    device."""
    out = []
    for backend in ("pallas", "jit"):
        res = fleet_phase(art, backend=backend, shards=len(devs),
                          slots=slots, placement="devices", seed=seed,
                          clock=clock)
        for i, p in enumerate(res.pop("fleet").shard_placement()):
            where = {p["device"]} | p["h"] | p["constants"]
            check(where == {devs[i]}, f"shard {i} ({backend}): {where}")
        out.append(res)
    return out


def lm_phase(seed: int = 0, new_tokens: int = 8) -> dict:
    """A few greedy tokens from the reduced deepseek-7b with int8 weights;
    the head must lower to the compiled q15_matmul kernel and agree with
    its dequantize-then-matmul reference."""
    import jax
    import jax.numpy as jnp
    import repro.configs as C
    from repro.kernels.q15_matmul.ref import q15_matmul_ref
    from repro.models import registry
    from repro.serve.engine import Engine, ServeConfig

    cfg = C.reduced(C.get("deepseek-7b"), compute_dtype="float32",
                    param_dtype="float32")
    params = registry.init(cfg, jax.random.PRNGKey(seed))
    prompts = np.random.default_rng(seed).integers(0, cfg.vocab_size, (4, 12))
    eng = Engine(cfg, params, ServeConfig(max_len=64, quant_bits=8))
    toks = eng.generate(prompts, max_new=new_tokens)
    check(toks.shape == (4, new_tokens)
          and 0 <= toks.min() <= toks.max() < cfg.vocab_size,
          f"generated tokens {toks}")

    hid = jnp.asarray(np.random.default_rng(seed + 1).normal(
        size=(4, cfg.d_model)), jnp.float32)
    text = eng._head_fn.lower(hid).as_text()
    check("tpu_custom_call" in text, "LM head is not the compiled kernel")
    q, s = eng.qparams, eng.scales
    if not cfg.tie_embeddings and "lm_head" in q:
        wq, scale = q["lm_head"]["w"], s["lm_head"]["w"]
    else:
        wq, scale = jnp.asarray(q["embed"]["table"]).T, s["embed"]["table"]
    want = np.asarray(q15_matmul_ref(hid, wq, scale))
    rel = float(np.max(np.abs(np.asarray(eng._head_fn(hid)) - want))
                / (np.max(np.abs(want)) + 1e-9))
    check(rel < 2e-2, f"head vs reference: relative error {rel}")  # bf16
    return {"tokens": int(toks.size), "head_rel_err": rel}


def _print_fleet(res: dict) -> None:
    print(f"smoke fleet[{res['backend']}]: {res['streams']:,} streams x "
          f"{res['shards']} shards, {res['ticks']} ticks run; vs exact on "
          f"{res['sampled_streams']:,} sampled streams: "
          f"{res['sampled_events']:,} predictions, "
          f"{res['prediction_mismatches']} mismatched, bitwise-equal h "
          f"share {res['bitwise_equal_h_share']!r}, max |dh| "
          f"{res['max_abs_dh']!r} (tol {res['h_tol']!r}); compile "
          f"{res['compile_s']!r} s, drain {res['drain_s']!r} s", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="fleet phase only, one shard per chip of four")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit("chip_smoke.py must run from a checkout of the "
                         "repository (src/repro is missing)")
    sys.path.insert(0, SRC)
    devs = require_tpu()
    from repro.kernels import enable_compile_cache
    print(f"smoke compile cache: {enable_compile_cache()}", flush=True)
    from repro.deploy import goldens
    clock = CompileClock()
    art = goldens.build_reference_artifact(seed=args.seed)

    if args.four_chips:
        if len(devs) != 4:
            raise SystemExit(f"--four-chips needs 4 devices, found "
                             f"{len(devs)}")
        for res in fleet_per_chip(art, devs, slots=16384, seed=args.seed,
                                  clock=clock):
            _print_fleet(res)
            print(f"smoke fleet[{res['backend']}]: shard i's h and "
                  f"constants on device i for all 4 shards", flush=True)
    else:
        for backend in ("pallas", "jit"):
            res = fleet_phase(art, backend=backend, shards=8, slots=16384,
                              seed=args.seed, clock=clock)
            res.pop("fleet")
            _print_fleet(res)
        lm = lm_phase(args.seed)
        print(f"smoke lm: {lm['tokens']} tokens; head is tpu_custom_call, "
              f"relative error vs reference {lm['head_rel_err']!r}",
              flush=True)
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
