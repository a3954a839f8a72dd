"""Golden-trace generation for the deployment parity protocol.

A *golden* pins down the full deployed behavior of one exported model on a
fixed batch of HAPT windows:

  * per-step int16 hidden-state trajectories for the first ``n_trace``
    windows (the cross-platform bit-equivalence witness — paper
    contribution (i)),
  * final int32 logits + argmax for every window,
  * the image byte digest, so a golden can only be replayed against the
    exact export that produced it.

Goldens are deterministic end to end: synthetic HAPT is crc32-seeded,
model init is a threefry PRNGKey, PTQ/calibration are round-to-nearest,
and the qvm is integer-only — two independent export runs must produce
byte-identical goldens (asserted in tests and gated in CI).

Checked-in fixtures live in ``tests/goldens/``; regenerate with::

    PYTHONPATH=src python -m repro.deploy.goldens --out tests/goldens
"""
from __future__ import annotations

import argparse
import hashlib
import os
from typing import Any

import jax
import numpy as np

from repro.compress import ModelArtifact, default_deploy_pipeline
from repro.core import fastgrnn as fg
from repro.core.quantization import QuantizedParams
from repro.data import hapt
from .image import DeployImage, build_image
from .qvm import QVM

# Fixture geometry: small enough to check in, big enough to exercise the
# recurrence (8 full 128-step trajectories + 256 window predictions).
N_TRACE = 8
N_WINDOWS = 256
CALIB_WINDOWS = 5


def reference_params(seed: int = 0, low_rank: bool = True) -> dict:
    """The paper's low-rank H=16 r_w=2 r_u=8 FastGRNN (full rank with
    ``low_rank=False``) at random init.  The committed fixtures were
    drawn with the non-partitionable threefry, which is pinned here so
    they reproduce under any JAX default."""
    cfg = fg.FastGRNNConfig(rank_w=2 if low_rank else None,
                            rank_u=8 if low_rank else None)
    with jax.threefry_partitionable(False):
        return fg.init_params(cfg, jax.random.PRNGKey(seed))


def build_reference_artifact(seed: int = 0, low_rank: bool = True,
                             params: dict | None = None,
                             calib: np.ndarray | None = None,
                             bits: int = 15) -> ModelArtifact:
    """Deterministic calibrated model -> compression artifact.

    By default: :func:`reference_params` (threefry seed — bit-stable
    across platforms) through the
    ``default_deploy_pipeline`` (PTQ at ``bits`` -> Sec. III-D 5-window
    deploy calibration on synthetic HAPT train data -> LUT pack).  The
    Q15 artifact is bit-identical to the historical direct
    ``quantize_params`` + ``calibrate_deploy`` handoff.  Pass ``params``
    (e.g. trained weights) to export a real checkpoint; ``bits=7`` builds
    the Q7 artifact.
    """
    if params is None:
        params = reference_params(seed, low_rank)
    if calib is None:
        calib = f"hapt:train:{CALIB_WINDOWS}"
    pipe = default_deploy_pipeline(bits=bits, calib=calib)
    return pipe.run(ModelArtifact.from_params(params))


def build_reference_model(seed: int = 0, low_rank: bool = True,
                          params: dict | None = None,
                          calib: np.ndarray | None = None,
                          ) -> tuple[QuantizedParams, dict[str, float], DeployImage]:
    """Legacy-shaped convenience: the reference artifact unpacked into the
    historical ``(qp, act_scales, image)`` triple (tests and benches that
    predate the artifact API)."""
    art = build_reference_artifact(seed=seed, low_rank=low_rank,
                                   params=params, calib=calib)
    return art.qp, dict(art.act_scales), build_image(art)


def generate_goldens(img: DeployImage, windows: np.ndarray,
                     n_trace: int = N_TRACE) -> dict[str, Any]:
    """Run the qvm over ``windows`` and freeze its observable behavior."""
    vm = QVM(img)
    xq = vm.quantize_input(windows)
    logits, traces = vm.run_windows(xq[:n_trace], return_trajectory=True)
    all_logits = vm.run_windows(xq)
    blob = img.to_bytes()
    return {
        "image_sha256": hashlib.sha256(blob).hexdigest(),
        "image_bytes": np.frombuffer(blob, np.uint8),
        "xq": xq,
        "traces": traces,                       # (n_trace, T, H) int16
        "trace_logits": logits,                 # (n_trace, C) int32
        "logits": all_logits,                   # (N, C) int32
        "preds": np.argmax(all_logits, axis=1).astype(np.int32),
    }


def save_goldens(goldens: dict[str, Any], path: str) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    np.savez_compressed(path, **goldens)


def load_goldens(path: str) -> dict[str, Any]:
    with np.load(path, allow_pickle=False) as z:
        return {k: (z[k] if z[k].ndim else z[k].item()) for k in z.files}


def default_fixture(seed: int = 0) -> dict[str, Any]:
    """The checked-in fixture: reference model + deterministic test windows."""
    _, _, img = build_reference_model(seed=seed)
    windows = hapt.load("test", n=N_WINDOWS).windows
    return generate_goldens(img, windows)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="tests/goldens",
                    help="directory for the .npz fixtures")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", action="store_true",
                    help="verify existing fixtures instead of writing")
    args = ap.parse_args()
    g = default_fixture(seed=args.seed)
    path = os.path.join(args.out, f"qvm_reference_s{args.seed}.npz")
    if args.check:
        old = load_goldens(path)
        for k in ("image_bytes", "xq", "traces", "trace_logits", "logits", "preds"):
            np.testing.assert_array_equal(old[k], g[k], err_msg=k)
        assert old["image_sha256"] == g["image_sha256"]
        print(f"OK: {path} reproduces bit-for-bit")
    else:
        save_goldens(g, path)
        print(f"wrote {path} (image sha256 {g['image_sha256'][:16]}..., "
              f"{g['preds'].shape[0]} windows, {g['traces'].shape[0]} traces)")


if __name__ == "__main__":
    main()
