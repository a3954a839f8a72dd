"""Export a calibrated FastGRNN to a deployable MCU artifact, end to end.

    PYTHONPATH=src python examples/export_mcu.py [--outdir export_out]
        [--trained] [--windows 64] [--bits 15]

Pipeline (the paper's Fig. 1 deployment half, now one artifact end to end):

  1. model     — low-rank FastGRNN (H=16, r_w=2, r_u=8)
                 (random-init by default; ``--trained`` trains first);
  2. compress  — the composable pass pipeline: ``QuantizePTQ`` (Q15, or
                 Q7 with ``--bits 7``) -> ``CalibrateActivations``
                 (Sec. III-D deploy scopes: input, low-rank
                 intermediates, pre-activation, hidden, logit scales) ->
                 ``PackLUT``, all recorded as provenance on ONE versioned
                 `ModelArtifact` (saved as ``model.fgar``);
  3. pack      — lower the artifact to the deterministic wire image
                 (``model.fgrn``), size-audited against the AVR + MSP430
                 budgets;
  4. emit      — C translation units for all three targets x both
                 engines (float = the paper's deployed arithmetic,
                 int = the multiplier-less pure-integer path);
  5. verify    — compile the host target with cc and check parity on a
                 window batch: float C bit-identical to the oracle,
                 int C bit-identical to the qvm emulator.
"""
from __future__ import annotations

import argparse
import json
import os

from repro.data import hapt
from repro.deploy import emit_c, verify
from repro.deploy.goldens import build_reference_artifact
from repro.deploy.image import audit_platforms, build_image, size_report
from repro.kernels import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--outdir", default="export_out")
    ap.add_argument("--trained", action="store_true",
                    help="train the pinned parity-protocol model first")
    ap.add_argument("--windows", type=int, default=64,
                    help="parity-check windows")
    ap.add_argument("--bits", type=int, default=15, choices=(15, 7),
                    help="weight format: 15 = Q15/int16 (paper), 7 = Q7/int8")
    args = ap.parse_args()
    enable_compile_cache()

    # 1+2: model -> compression pipeline -> ONE artifact (the same
    # reference recipe the golden fixtures pin, so the Q15 default is
    # bit-identical to the checked-in golden image)
    if args.trained:
        params, calib = verify.protocol_model()
        art = build_reference_artifact(params=params, calib=calib,
                                       bits=args.bits)
    else:
        art = build_reference_artifact(seed=0, bits=args.bits)
    os.makedirs(args.outdir, exist_ok=True)
    blob = art.save(os.path.join(args.outdir, "model.fgar"))
    print(art.summary())
    print(f"artifact: {len(blob)} bytes -> {args.outdir}/model.fgar "
          f"(sha256 {art.sha256()[:16]}...)")
    srep = art.size_report()
    print(f"  weights {srep['weight_bytes_packed']} B packed "
          f"({srep['q_format']}; paper class: 566 B), "
          f"LUTs {srep['lut_bytes']} B, passes: "
          f"{' -> '.join(art.passes_applied())}")

    # 3: artifact -> wire image + budget audit (raises if unflashable)
    img = build_image(art)
    with open(os.path.join(args.outdir, "model.fgrn"), "wb") as f:
        f.write(img.to_bytes())
    rep = size_report(img)
    print(f"wire image: {rep['total_bytes']} bytes -> "
          f"{args.outdir}/model.fgrn (bits={rep['bits']})")
    for engine in ("float", "int"):
        audit = audit_platforms(img, ("avr", "msp430"), engine=engine)
        for key, a in audit.items():
            print(f"  [{engine:5s}] {key:6s}: flash {a['image_bytes']}/"
                  f"{a['flash_capacity'] - a['code_reserve']} B, "
                  f"sram {a['sram_needed']}/{a['sram_capacity']} B  OK")

    # 4: emit C for every target x engine
    for target in ("avr", "msp430", "host"):
        for engine in ("float", "int"):
            d = os.path.join(args.outdir, target, engine)
            paths = emit_c.write_sources(img, d, target=target, engine=engine)
            print(f"  emitted {target}/{engine}: "
                  f"{', '.join(os.path.basename(p) for p in paths)}")

    # 5: host parity (the artifact is the report's single source of truth)
    if emit_c.find_cc() is None:
        print("no C compiler on PATH — skipping the compile+parity check")
        return
    windows = hapt.load("test", n=args.windows).windows
    report = verify.run_parity(art, windows=windows, use_fp32=False)
    print("parity over", report["n_windows"], "windows:")
    for k, v in report["bitwise"].items():
        print(f"  bitwise {k}: {'OK' if v else 'MISMATCH'}")
    for k, v in report["pairwise"].items():
        print(f"  argmax {k}: {v['agree']:.4f}")
    with open(os.path.join(args.outdir, "parity.json"), "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {args.outdir}/parity.json")


if __name__ == "__main__":
    main()
