"""repro.compress: composable passes + the versioned ModelArtifact.

Covers the PR-4 acceptance contract:
  * artifact lifecycle — save/load byte-identical round-trip, per-pass
    provenance recorded, pipeline determinism (double-run -> identical
    bytes);
  * Q15 bit-exactness — the artifact path reproduces the historical
    ``(QuantizedParams, act_scales)`` handoff and the checked-in golden
    image byte-for-byte;
  * Q7 generality proof — a ``QuantizePTQ(bits=7)`` artifact exports,
    round-trips through the wire image, and matches the float oracle's
    argmax through the pure-integer qvm;
  * every runtime consumes the artifact (QRuntime / StreamingEngine /
    build_image / run_parity) with identical numerics;
  * the one-release deprecation shims (``quantize_for_serving`` /
    ``dequantize_params`` / legacy 2-arg ``build_image``) are gone and the
    migration path reproduces identical bytes;
  * the ``python -m repro.compress`` CLI smoke + size-report schema.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.compress import (CalibrateActivations, IHTSparsify, LowRankFactor,
                            ModelArtifact, PackLUT, Pipeline, QuantizePTQ,
                            default_deploy_pipeline, dequantize_tree,
                            pipeline_from_config, quantize_tree)
from repro.core.qruntime import QRuntime, calibrate, calibrate_deploy
from repro.core.quantization import QuantConfig, quantize_params
from repro.data import hapt

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "goldens",
                           "qvm_reference_s0.npz")


def _params(seed=0, low_rank=True):
    from repro.deploy.goldens import reference_params
    return reference_params(seed, low_rank)


@pytest.fixture(scope="module")
def artifact():
    return default_deploy_pipeline(bits=15).run(
        ModelArtifact.from_params(_params()))


@pytest.fixture(scope="module")
def windows():
    return hapt.load("test", n=96).windows


# ---------------------------------------------------------------------------
# Artifact lifecycle: round-trip, determinism, provenance
# ---------------------------------------------------------------------------

def test_save_load_byte_identical_roundtrip(artifact, tmp_path):
    path = str(tmp_path / "model.fgar")
    blob = artifact.save(path)
    art2 = ModelArtifact.load(path)
    assert art2.to_bytes() == blob
    # and the reloaded artifact re-serializes identically again
    assert ModelArtifact.from_bytes(art2.to_bytes()).to_bytes() == blob
    # contents survive: qp tensors, scales, act scales, luts, provenance
    assert art2.qp.bits == artifact.qp.bits
    for n in artifact.qp.q:
        np.testing.assert_array_equal(np.asarray(art2.qp.q[n]),
                                      np.asarray(artifact.qp.q[n]))
        assert float(np.float32(art2.qp.scales[n])) == \
            float(np.float32(artifact.qp.scales[n]))
    assert art2.act_scales == artifact.act_scales
    assert art2.provenance == artifact.provenance
    for k in artifact.luts:
        np.testing.assert_array_equal(art2.luts[k], artifact.luts[k])


def test_pipeline_double_run_is_byte_identical():
    params = _params()
    pipe = default_deploy_pipeline(bits=15)
    a = pipe.run(ModelArtifact.from_params(params))
    b = pipe.run(ModelArtifact.from_params(params))
    assert a.to_bytes() == b.to_bytes()
    assert a.sha256() == b.sha256()


def test_provenance_records_every_pass(artifact):
    assert artifact.passes_applied() == [
        "source", "quantize_ptq", "calibrate_activations", "pack_lut"]
    recs = {r["pass"]: r for r in artifact.provenance}
    assert recs["source"]["metrics"]["param_count"] > 0
    qrec = recs["quantize_ptq"]
    assert qrec["metrics"]["q_format"] == "Q15"
    assert set(qrec["metrics"]["scales"]) == set(artifact.qp.scales)
    crec = recs["calibrate_activations"]
    assert crec["metrics"]["scope"] == "deploy"
    assert crec["metrics"]["scales"] == dict(sorted(artifact.act_scales.items()))
    assert crec["config"]["windows"] == "hapt:train:5"
    assert recs["pack_lut"]["metrics"]["lut_bytes"] == 2 * 256 * (4 + 2)


def test_sparsify_pass_records_masks_and_sparsity():
    art = Pipeline((IHTSparsify(sparsity=0.5), QuantizePTQ(bits=15))).run(
        ModelArtifact.from_params(_params()))
    rec = [r for r in art.provenance if r["pass"] == "iht_sparsify"][0]
    assert rec["metrics"]["achieved_sparsity"] == pytest.approx(0.5, abs=0.02)
    for name in ("W1", "U1", "U2"):
        m = art.masks[name]
        assert m.dtype == bool
        # masked positions really are zero in the params AND the q tensors
        assert not np.any(np.asarray(art.params[name])[~m])
        assert not np.any(np.asarray(art.qp.q[name])[~m])
    srep = art.size_report()
    assert srep["weight_sparsity"] > 0.3
    assert srep["weight_bytes_packed"] <= srep["weight_bytes_dense"]
    # masks stay boolean through a serialization round-trip (a loaded
    # sparse artifact must support ~mask / boolean fancy-indexing)
    art2 = ModelArtifact.from_bytes(art.to_bytes())
    for name in ("W1", "U1"):
        assert art2.masks[name].dtype == bool
        np.testing.assert_array_equal(art2.masks[name], art.masks[name])


def test_low_rank_pass_factors_dense_checkpoint():
    art = LowRankFactor(rank_w=2, rank_u=8).apply(
        ModelArtifact.from_params(_params(low_rank=False)))
    assert set(art.params) >= {"W1", "W2", "U1", "U2"}
    assert "W" not in art.params and "U" not in art.params
    assert art.params["W1"].shape == (16, 2)
    assert art.params["U1"].shape == (16, 8)
    rec = art.provenance[-1]["metrics"]
    assert rec["rel_err_U"] < 1.0
    # already-factored checkpoints pass through untouched
    art2 = LowRankFactor().apply(ModelArtifact.from_params(_params()))
    assert art2.provenance[-1]["metrics"] == {"skipped": "already factored"}


def test_pass_ordering_errors_are_loud():
    art = ModelArtifact.from_params(_params())
    with pytest.raises(ValueError, match="QuantizePTQ"):
        CalibrateActivations().apply(art)
    with pytest.raises(ValueError, match="bits"):
        QuantizePTQ(bits=4).apply(art)
    with pytest.raises(ValueError, match="unknown pass"):
        pipeline_from_config([{"pass": "nope"}])


# ---------------------------------------------------------------------------
# Q15 bit-exactness across the API migration
# ---------------------------------------------------------------------------

def test_artifact_path_matches_legacy_handoff_bitwise(artifact):
    """The pass pipeline must reproduce the historical direct
    quantize_params + calibrate_deploy handoff exactly."""
    params = _params()
    qp = quantize_params(params, QuantConfig())
    act = calibrate_deploy(QRuntime(qp), hapt.load("train", n=5).windows)
    for n in qp.q:
        np.testing.assert_array_equal(np.asarray(qp.q[n]),
                                      np.asarray(artifact.qp.q[n]))
        assert float(np.float32(qp.scales[n])) == \
            float(np.float32(artifact.qp.scales[n]))
    assert {k: float(v) for k, v in act.items()} == artifact.act_scales


def test_artifact_image_matches_golden_fixture(artifact):
    """build_image(artifact) must be byte-identical to the checked-in
    golden image (produced pre-migration by build_image(qp, act_scales))."""
    from repro.deploy.goldens import load_goldens
    from repro.deploy.image import build_image
    g = load_goldens(GOLDEN_PATH)
    assert build_image(artifact).to_bytes() == \
        bytes(np.asarray(g["image_bytes"], np.uint8))


def test_qruntime_from_artifact_bit_identical(artifact, windows):
    rt_art = QRuntime.from_artifact(artifact)
    rt_leg = QRuntime(artifact.qp)
    for w in windows[:4]:
        a, ta = rt_art.run_window(w, return_trajectory=True)
        b, tb = rt_leg.run_window(w, return_trajectory=True)
        np.testing.assert_array_equal(a.view(np.int32), b.view(np.int32))
        np.testing.assert_array_equal(ta.view(np.int32), tb.view(np.int32))


def test_qruntime_from_artifact_storage_scales(windows):
    """quantized_acts consumes the storage-scope calibration; deploy
    scales alone must not silently enable activation storage quant."""
    art = Pipeline((
        QuantizePTQ(bits=15),
        CalibrateActivations(windows="hapt:train:5", scope="storage"),
    )).run(ModelArtifact.from_params(_params()))
    rt = QRuntime.from_artifact(art, quantized_acts=True)
    legacy = QRuntime(art.qp, act_scales=calibrate(
        QRuntime(art.qp), hapt.load("train", n=5).windows))
    np.testing.assert_array_equal(
        rt.run_window(windows[0]).view(np.int32),
        legacy.run_window(windows[0]).view(np.int32))
    # deploy-scoped artifact has no storage scales -> loud error
    art_deploy = default_deploy_pipeline(bits=15).run(
        ModelArtifact.from_params(_params()))
    with pytest.raises(ValueError, match="storage_scales"):
        QRuntime.from_artifact(art_deploy, quantized_acts=True)


def test_streaming_engine_from_artifact_bit_identical(artifact, windows):
    from repro.serve.streaming import StreamingEngine, StreamingConfig
    eng = StreamingEngine.from_artifact(
        artifact, StreamingConfig(max_slots=8))
    eng.attach("s", windows[0], total_steps=128, record_trajectory=True)
    events = eng.drain()
    rt = QRuntime.from_artifact(artifact)
    lg, traj = rt.run_window(windows[0], return_trajectory=True)
    np.testing.assert_array_equal(events[-1].logits.view(np.int32),
                                  lg.view(np.int32))
    np.testing.assert_array_equal(eng.trajectory("s").view(np.int32),
                                  traj.view(np.int32))


def test_core_pipeline_deploy_matches_legacy(windows):
    """core.pipeline.deploy (now built on the pass API) is numerically
    identical to the historical direct handoff in all three act modes."""
    from repro.core import pipeline as pl
    params = _params()
    calib = hapt.load("train", n=5).windows
    qp = quantize_params(params, QuantConfig())
    legacy = {
        "fp32": QRuntime(qp),
        "naive": QRuntime(qp, naive_acts=True),
        "calibrated": QRuntime(qp, act_scales=calibrate(QRuntime(qp), calib)),
    }
    new = {
        "fp32": pl.deploy(params, calib),
        "naive": pl.deploy(params, calib, naive_activations=True),
        "calibrated": pl.deploy(params, calib, quantize_activations=True),
    }
    for mode in legacy:
        np.testing.assert_array_equal(
            new[mode].run_window(windows[0]).view(np.int32),
            legacy[mode].run_window(windows[0]).view(np.int32), err_msg=mode)


# ---------------------------------------------------------------------------
# Q7: the redesign's generality proof
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def artifact_q7():
    return default_deploy_pipeline(bits=7).run(
        ModelArtifact.from_params(_params()))


def test_q7_artifact_exports_and_roundtrips(artifact_q7):
    from repro.deploy.image import DeployImage, build_image
    assert artifact_q7.qp.bits == 8
    assert artifact_q7.size_report()["q_format"] == "Q7"
    img = build_image(artifact_q7)
    assert img.bits == 8
    blob = img.to_bytes()
    img2 = DeployImage.from_bytes(blob)
    assert img2.bits == 8
    assert img2.to_bytes() == blob
    # Q7 weights halve the packed byte count vs the Q15 artifact
    q15 = default_deploy_pipeline(bits=15).run(
        ModelArtifact.from_params(_params()))
    assert artifact_q7.size_report()["weight_bytes_packed"] * 2 == \
        q15.size_report()["weight_bytes_dense"]


def test_q7_artifact_qvm_argmax_parity(artifact_q7, windows):
    """The Q7 image runs through the UNCHANGED pure-integer qvm (scales
    absorb the weight width) and matches the Q7 float oracle's argmax on
    every confident window."""
    from repro.deploy.qvm import QVM
    from repro.deploy.image import build_image
    vm = QVM(build_image(artifact_q7))
    xq = vm.quantize_input(windows)
    xdeq = vm.dequantize_input(xq)
    preds = np.argmax(vm.run_windows(xq), axis=1)
    rt = QRuntime.from_artifact(artifact_q7)
    ref_lg = np.stack([rt.run_window(w) for w in xdeq])
    ref = np.argmax(ref_lg, axis=1)
    srt = np.sort(ref_lg, axis=1)
    confident = (srt[:, -1] - srt[:, -2]) > 5e-3
    assert confident.sum() > 0
    np.testing.assert_array_equal(preds[confident], ref[confident])
    assert float(np.mean(preds == ref)) >= 0.97


def test_q7_emitted_c_bit_identical_to_qvm(artifact_q7, windows):
    """The C generator needs no Q7 fork either: same plan, same twin."""
    from repro.deploy import emit_c
    from repro.deploy.image import build_image
    from repro.deploy.qvm import QVM
    if emit_c.find_cc() is None:
        pytest.skip("no C compiler")
    import tempfile
    img = build_image(artifact_q7)
    vm = QVM(img)
    xq = vm.quantize_input(windows[:16])
    lg, traces = vm.run_windows(xq, return_trajectory=True)
    with tempfile.TemporaryDirectory() as td:
        binary = emit_c.compile_host(img, td, engine="int")
        cm = emit_c.CHostModel(binary, img.H, img.C, engine="int")
        ctr, clg, _ = cm.trace(xq)
    np.testing.assert_array_equal(ctr, traces)
    np.testing.assert_array_equal(clg, lg)


@pytest.mark.slow
def test_q7_full_protocol_argmax_parity():
    """Acceptance gate: a Q7 artifact of the pinned parity-protocol model
    (verify.PROTOCOL seed) runs through the qvm with near-total argmax
    agreement against its float oracle over the full 3,399-window split."""
    from repro.deploy import verify
    from repro.deploy.goldens import build_reference_artifact
    from repro.deploy.image import build_image
    from repro.deploy.qvm import QVM
    params, calib = verify.protocol_model()
    art = build_reference_artifact(params=params, calib=calib, bits=7)
    vm = QVM(build_image(art))
    test = hapt.load("test")
    assert len(test.windows) == 3399
    xq = vm.quantize_input(test.windows)
    preds = np.argmax(vm.run_windows(xq), axis=1)
    rt = QRuntime.from_artifact(art)
    ref = rt.predict_batch(vm.dequantize_input(xq))
    assert float(np.mean(preds == ref)) >= 0.999


# ---------------------------------------------------------------------------
# Post-deprecation surface (the one-release shims are gone)
# ---------------------------------------------------------------------------

def test_serve_engine_shims_removed():
    """quantize_for_serving / dequantize_params served their one release
    as DeprecationWarning shims; the canonical home is repro.compress."""
    import repro.serve.engine as se
    assert not hasattr(se, "quantize_for_serving")
    assert not hasattr(se, "dequantize_params")


def test_quantize_tree_accepts_q_format_names():
    w = {"w": np.linspace(-2, 2, 8, dtype=np.float32).reshape(2, 4)}
    for alias, width in ((7, np.int8), (8, np.int8), (15, np.int16),
                         (16, np.int16)):
        qt, _ = quantize_tree(w, alias)
        assert np.asarray(qt["w"]).dtype == width


def test_legacy_build_image_pair_rejected(artifact):
    """The 2-arg build_image(qp, act_scales) shim is gone: a bare
    QuantizedParams is rejected with a migration hint, and wrapping the
    pair in a ModelArtifact reproduces the image byte-for-byte."""
    from repro.compress import ModelArtifact
    from repro.deploy.image import build_image
    with pytest.raises(TypeError, match="ModelArtifact"):
        build_image(artifact.qp)
    wrapped = ModelArtifact(qp=artifact.qp,
                            act_scales=dict(artifact.act_scales))
    assert build_image(wrapped).to_bytes() == build_image(artifact).to_bytes()


# ---------------------------------------------------------------------------
# CLI + config loader
# ---------------------------------------------------------------------------

def test_pipeline_from_config_roundtrip():
    cfg = {"name": "custom", "passes": [
        {"pass": "iht_sparsify", "sparsity": 0.25},
        {"pass": "quantize_ptq", "bits": 7},
        {"pass": "calibrate_activations", "windows": "hapt:train:2",
         "scope": "deploy"},
        {"pass": "pack_lut"},
    ]}
    pipe = pipeline_from_config(cfg)
    assert pipe.name == "custom"
    art = pipe.run(ModelArtifact.from_params(_params()))
    assert art.qp.bits == 8
    assert art.passes_applied() == ["source", "iht_sparsify", "quantize_ptq",
                                    "calibrate_activations", "pack_lut"]


def test_cli_emits_deterministic_artifact_and_valid_report(tmp_path):
    """The CI artifact-determinism gate in miniature: two CLI runs produce
    byte-identical artifacts, and the report validates under the
    benchmarks schema."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    outs = []
    for i in (1, 2):
        a, r = str(tmp_path / f"a{i}.fgar"), str(tmp_path / f"r{i}.json")
        res = subprocess.run(
            [sys.executable, "-m", "repro.compress", "--preset", "q15-deploy",
             "--out", a, "--report", r],
            env=env, cwd=repo, capture_output=True, text=True, timeout=300)
        assert res.returncode == 0, res.stderr
        outs.append((a, r))
    blobs = [open(a, "rb").read() for a, _ in outs]
    assert blobs[0] == blobs[1]
    report = json.load(open(outs[0][1]))
    assert report["benchmark"] == "compress_artifact"
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmarks.validate_bench import validate
    kind, errors = validate(outs[0][1])
    assert kind == "compress_artifact" and errors == [], errors
    art = ModelArtifact.load(outs[0][0])
    assert report["sha256"] == art.sha256()
