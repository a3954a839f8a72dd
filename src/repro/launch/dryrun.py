import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes with 512 placeholder host devices.

  single-pod : 16 x 16           (data, model)        = 256 chips
  multi-pod  : 2 x 16 x 16       (pod, data, model)   = 512 chips

For each runnable cell this prints compiled.memory_analysis() (proves the
program fits per-chip HBM) and compiled.cost_analysis() (FLOPs/bytes for
the roofline), parses collective bytes out of the partitioned HLO, and
appends a JSON record consumed by EXPERIMENTS.md Sec. Dry-run/Roofline.

Usage:
  python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod --out results/dryrun.jsonl
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.configs.base import SHAPES, applicable
from repro.launch.mesh import make_production_mesh
from repro.launch import sharding as sh
from repro.launch import roofline as rf
from repro.launch import analytic
from repro.models import registry
from repro.train.optimizer import AdamConfig


def build_cell(arch: str, shape_name: str, mesh):
    """-> (jitted fn, abstract args) for one cell."""
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    mode = sh.parallel_mode(cfg, shape, mesh)
    seqp = mode is not None
    batch_sds = registry.input_specs(cfg, shape)
    batch_specs = sh.batch_pspecs(cfg, shape, mesh, seq_parallel=seqp)
    aparams = registry.abstract_params(cfg)
    pspecs = sh.param_pspecs(aparams, mesh, mode=mode, cfg=cfg)
    n_params = sh.named(mesh, pspecs)
    n_batch = {k: jax.sharding.NamedSharding(mesh, batch_specs[k])
               for k in batch_sds}

    if shape.kind == "train":
        acfg = AdamConfig(state_dtype=cfg.opt_state_dtype)
        aopt = registry.abstract_opt(cfg, acfg)
        ospecs = sh.opt_pspecs(aopt, pspecs)
        n_opt = sh.named(mesh, ospecs)
        step = registry.make_train_step(cfg, acfg, mesh=mesh,
                                        seq_parallel=seqp)
        jf = jax.jit(step,
                     in_shardings=(n_params, n_opt, n_batch),
                     out_shardings=(n_params, n_opt, None),
                     # detlint: ignore[det-donate-argnums] training step: params/opt buffers are consumed, no bit-exactness contract
                     donate_argnums=(0, 1))
        return jf, (aparams, aopt, batch_sds)

    if shape.kind == "prefill":
        step = registry.make_prefill_step(cfg, shape, mesh=mesh,
                                          seq_parallel=seqp)
        jf = jax.jit(step, in_shardings=(n_params, n_batch))
        return jf, (aparams, batch_sds)

    # decode
    acache = registry.abstract_cache(cfg, shape)
    cspecs = sh.cache_pspecs(cfg, shape, mesh, acache)
    n_cache = sh.named(mesh, cspecs)
    splitkv = sh.use_splitkv(cfg, shape, mesh)
    quant_bits = int(os.environ.get("REPRO_SERVE_QUANT", "0"))
    if quant_bits:
        qp, scales = registry.abstract_quantized_params(cfg, quant_bits)
        n_scales = jax.tree.map(
            lambda _: jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()), scales)
        step = registry.make_decode_step_quantized(cfg, shape, quant_bits,
                                                   mesh=mesh, splitkv=splitkv)
        jf = jax.jit(step,
                     in_shardings=(n_params, n_scales, n_cache,
                                   n_batch["tokens"]),
                     out_shardings=(None, n_cache),
                     # detlint: ignore[det-donate-argnums] LM decode cache donation: compile-shape dryrun, not the FastGRNN serving path
                     donate_argnums=(2,))
        return jf, (qp, scales, acache, batch_sds["tokens"])
    step = registry.make_decode_step(cfg, shape, mesh=mesh, splitkv=splitkv)
    jf = jax.jit(step,
                 in_shardings=(n_params, n_cache, n_batch["tokens"]),
                 out_shardings=(None, n_cache),
                 # detlint: ignore[det-donate-argnums] LM decode cache donation: compile-shape dryrun, not the FastGRNN serving path
                 donate_argnums=(1,))
    return jf, (aparams, acache, batch_sds["tokens"])


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             keep_hlo: bool = False) -> dict:
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    ok, reason = applicable(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = mesh.devices.size
        jf, aargs = build_cell(arch, shape_name, mesh)
        t0 = time.time()
        lowered = jf.lower(*aargs)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        hlo = compiled.as_text()
        colls = rf.parse_collectives(hlo)
        pods = int(mesh.shape.get("pod", 1))
        data = int(mesh.shape["data"])
        model = int(mesh.shape["model"])
        n_params = registry.param_count(cfg)
        qbits = int(os.environ.get("REPRO_SERVE_QUANT", "0")) \
            if shape.kind == "decode" else 0
        cost = analytic.cell_cost(cfg, shape, n_params=n_params,
                                  batch_shards=pods * data,
                                  weight_quant_bits=qbits)
        mode = sh.parallel_mode(cfg, shape, mesh)
        seqp = mode == "ssm_seq"  # weights replicated only in ssm mode
        roof = rf.Roofline.from_cost(
            cost, shape.kind, pods=pods, data=data, model=model,
            collective_bytes_per_device=float(colls.total_bytes),
            model_flops_global=registry.step_flops_model(cfg, shape),
            device_kind="TPU v5 lite",   # the production meshes: v5e pods
            weight_shards=1 if seqp else None)
        rec["parallel_mode"] = mode
        rec.update(
            status="ok",
            chips=chips,
            lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            n_params=n_params,
            analytic={
                "flops_fwd_global": cost.flops_fwd,
                "flops_total_global": cost.flops_total,
                "weight_bytes_per_pass": cost.weight_bytes_per_pass,
                "act_bytes": cost.act_bytes,
                "cache_bytes": cost.cache_bytes,
                "opt_bytes": cost.opt_bytes,
                "notes": cost.notes,
            },
            hlo_raw={  # XLA cost_analysis — loop bodies counted ONCE (caveat)
                "flops_per_device": float(ca.get("flops", 0.0)),
                "bytes_per_device": float(ca.get("bytes accessed", 0.0)),
            },
            flops_per_device=roof.flops_per_device,
            bytes_per_device=roof.bytes_per_device,
            collective_bytes_per_device=roof.collective_bytes_per_device,
            collective_counts=colls.counts,
            collective_bytes_by_kind=colls.bytes_by_kind,
            unknown_trip_whiles=colls.unknown_trip_whiles,
            model_flops_global=roof.model_flops_global,
            memory={
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_bytes": getattr(ma, "peak_memory_in_bytes", None),
            },
            roofline=roof.row(),
        )
        if keep_hlo:
            rec["hlo_path"] = f"/tmp/hlo_{arch}_{shape_name}_{mesh_name}.txt"
            with open(rec["hlo_path"], "w") as f:
                f.write(hlo)
    except Exception as e:  # a failure here is a bug in our sharding
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--keep-hlo", action="store_true")
    args = ap.parse_args()

    cells: list[tuple[str, str, bool]] = []
    archs = list(configs.ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for m in meshes:
                cells.append((a, s, m))

    out_f = open(args.out, "a") if args.out else None
    for a, s, m in cells:
        rec = run_cell(a, s, m, keep_hlo=args.keep_hlo)
        line = json.dumps(rec)
        print(line, flush=True)
        if out_f:
            out_f.write(line + "\n")
            out_f.flush()
    if out_f:
        out_f.close()


if __name__ == "__main__":
    main()
