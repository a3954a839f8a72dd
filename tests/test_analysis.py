"""repro.analysis: interval domain, qlint prover, detlint linter, report.

Tier-1 pins the same contract CI's static-analysis job gates on:

* the interval domain is exact (checked by brute-force enumeration);
* qlint proves the reference Q15 and Q7 images overflow-free end to
  end, with exactly the two designed load-bearing saturations;
* the live tree is detlint-clean, with every intentional exception a
  recorded suppression rather than silence;
* every seeded-defect mutation fixture is caught by the check it
  targets (a gate that cannot fire gates nothing);
* the report is canonical, byte-deterministic, schema-valid, and the
  committed ``ANALYSIS_report.json`` matches a fresh run.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.analysis import (Assumptions, DETLINT_CHECKS, Interval, Machine,
                            analyze_image, build_report, dumps, lint_source,
                            lint_tree, reference_targets, run_selftest)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ref_targets():
    return reference_targets()


@pytest.fixture(scope="module")
def tree():
    return lint_tree()


# ---------------------------------------------------------------------------
# interval domain: exact by enumeration
# ---------------------------------------------------------------------------

def test_interval_ops_exact_by_enumeration():
    a, b = Interval(-5, 3), Interval(-2, 7)
    xs = range(a.lo, a.hi + 1)
    ys = range(b.lo, b.hi + 1)
    for op, ref in (("add", lambda x, y: x + y),
                    ("sub", lambda x, y: x - y),
                    ("mul", lambda x, y: x * y)):
        got = getattr(a, op)(b)
        vals = [ref(x, y) for x in xs for y in ys]
        assert (got.lo, got.hi) == (min(vals), max(vals)), op
    for n in (0, 1, 2, 5):
        got = a.shr(n)
        vals = [int(np.int64(x) >> n) for x in xs]   # arithmetic/floor
        assert (got.lo, got.hi) == (min(vals), max(vals)), n
    got = a.neg()
    assert (got.lo, got.hi) == (-3, 5)
    got = a.clip(-2, 1)
    assert (got.lo, got.hi) == (-2, 1)


def test_interval_width_boundaries():
    assert Interval.const(I16 := 32767).bits_needed() == 16
    assert Interval.const(-32768).bits_needed() == 16
    assert Interval.const(I16 + 1).bits_needed() == 17
    assert Interval.of_width(16).fits(16)
    assert not Interval(-32769, 0).fits(16)
    assert Interval(0, 0).bits_needed() == 1
    assert Interval(-(2 ** 62), 2 ** 62).fits(64)
    assert not Interval(-(2 ** 63) - 1, 0).fits(64)


def test_matvec_bound_is_exact():
    """Per-row coefficient-sign bound equals the brute-force corner
    optimum (each v_j chosen independently at an endpoint)."""
    w = np.array([[1, -2], [3, 4]], np.int64)
    v = Interval(-1, 5)
    got = Machine().matvec("t", w, v)
    best_hi = best_lo = None
    for v0 in (v.lo, v.hi):
        for v1 in (v.lo, v.hi):
            for row in w:
                val = int(row[0]) * v0 + int(row[1]) * v1
                best_hi = val if best_hi is None else max(best_hi, val)
                best_lo = val if best_lo is None else min(best_lo, val)
    assert (got.lo, got.hi) == (best_lo, best_hi)


# ---------------------------------------------------------------------------
# qlint: the reference images are proven safe
# ---------------------------------------------------------------------------

def test_reference_q15_and_q7_proved_overflow_free(ref_targets):
    assert {t["name"] for t in ref_targets} == \
        {"reference-q15-s0", "reference-q7-s0"}
    for t in ref_targets:
        assert t["proved_overflow_free"], t["findings"]
        assert t["state_closed"]
        assert t["n_sites"] > 30
        # exactly the two designed load-bearing saturations: the int16
        # state store and the pre-store int64->int32-range bound
        assert t["saturation"]["reachable"] == ["gate.hf_clip", "h_next"]
        for s in t["sites"]:
            assert s["margin_bits"] >= 0, s


def test_acc_width_downgrade_detected():
    """The required accumulator-width-downgrade mutation: the same
    image, declared int32 accumulators — proof must fail."""
    from repro.deploy.goldens import build_reference_artifact
    from repro.deploy.image import build_image
    img = build_image(build_reference_artifact(seed=0, bits=15))
    rec = analyze_image(img, Assumptions(widths={"acc": 32}))
    assert not rec["proved_overflow_free"]
    assert any(f["check"] == "q-acc-width" for f in rec["findings"])


# ---------------------------------------------------------------------------
# detlint: live tree clean, checks fire, suppressions recorded
# ---------------------------------------------------------------------------

def test_live_tree_is_detlint_clean(tree):
    assert tree["findings"] == []
    assert len(DETLINT_CHECKS) == 8
    assert list(tree["checks"]) == list(DETLINT_CHECKS)


def test_live_tree_suppressions_are_the_known_exceptions(tree):
    """Every recorded suppression is one of the reviewed exception
    families — training/dryrun donation, the LM engine's slot-cache
    donation and block-padded window kernels — and each carries a
    reason."""
    sups = tree["suppressions"]
    by_check = {}
    for s in sups:
        by_check.setdefault(s["check"], []).append(s)
        assert s["reason"], s
    assert set(by_check) == {"det-donate-argnums", "det-jit-pallas"}
    assert len(by_check["det-donate-argnums"]) == 7
    assert len(by_check["det-jit-pallas"]) == 4
    assert all(s["where"].startswith(("launch/", "serve/engine.py:"))
               for s in by_check["det-donate-argnums"])
    assert sum(s["where"].startswith("serve/") for s in by_check["det-donate-argnums"]) == 2
    assert all(s["where"].startswith(("kernels/",))
               for s in by_check["det-jit-pallas"])


def test_unsuppressed_defect_found_suppressed_defect_recorded():
    src = ("import jax\n"
           "f = jax.jit(g, donate_argnums=(0,))\n")
    findings, sups = lint_source(src, "serve/x.py")
    assert [f.check for f in findings] == ["det-donate-argnums"]
    src_ok = ("import jax\n"
              "f = jax.jit(g, donate_argnums=(0,))"
              "  # detlint: ignore[det-donate-argnums] reviewed\n")
    findings, sups = lint_source(src_ok, "serve/x.py")
    assert findings == []
    assert len(sups) == 1 and sups[0].reason == "reviewed"


def test_selftest_every_mutation_caught():
    result = run_selftest()
    assert result["ok"], result
    fixtures = result["fixtures"]
    assert len(fixtures) >= 8
    # the two fixtures the acceptance gate names explicitly
    assert fixtures["acc-width-downgrade"]["caught"]
    assert fixtures["seeded-det-donate-argnums"]["caught"]


# ---------------------------------------------------------------------------
# report: canonical, valid, committed copy current
# ---------------------------------------------------------------------------

def test_report_byte_deterministic_and_schema_valid(ref_targets, tree,
                                                    tmp_path):
    from benchmarks.validate_bench import validate
    r1 = dumps(build_report(ref_targets, tree))
    r2 = dumps(build_report(reference_targets(), lint_tree()))
    assert r1 == r2
    p = tmp_path / "ANALYSIS.json"
    p.write_text(r1)
    kind, errors = validate(str(p))
    assert kind == "analysis_report"
    assert errors == []


def test_committed_report_matches_fresh_run(ref_targets, tree):
    """The committed artifact is regenerated by CI and cmp'd; tier-1
    pins the same so a drift is caught before push.  Regenerate with:
    PYTHONPATH=src python -m repro.analysis --report ANALYSIS_report.json
    """
    committed = os.path.join(REPO, "ANALYSIS_report.json")
    assert os.path.exists(committed), "ANALYSIS_report.json not committed"
    with open(committed) as f:
        assert f.read() == dumps(build_report(ref_targets, tree))


def test_cli_detlint_smoke(tmp_path):
    out = tmp_path / "r.json"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--detlint-only",
         "--fail-on-findings", "--report", str(out)],
        capture_output=True, text=True,
        cwd=REPO, env={**os.environ, "PYTHONPATH": "src"})
    assert proc.returncode == 0, proc.stderr
    assert out.exists()
    assert "detlint: " in proc.stderr
