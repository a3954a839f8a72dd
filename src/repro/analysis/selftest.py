"""Mutation fixtures: prove every analyzer check can actually fire.

A static gate that never fires is indistinguishable from one that is
wired up wrong, so CI runs ``python -m repro.analysis --selftest``: each
fixture below plants one seeded defect — a tampered requant, an
accumulator-width downgrade, a reintroduced ``donate_argnums`` — and the
selftest PASSES only if the corresponding check catches it.  A fixture
whose defect sails through is a selftest failure (exit 1), i.e. the
mutation killed the gate and the gate must be fixed before it can gate
anything else.

qlint fixtures drive the abstract machine / ``analyze_image`` directly
(plan injection, width overrides); detlint fixtures lint small source
strings through the production ``lint_source`` path, including one that
proves the suppression syntax is honored (a suppressed defect must
produce a recorded suppression and *no* finding).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np

from repro.deploy.qvm import I16_MIN, Requant, plan_from_image
from .detlint import lint_source
from .intervals import Interval
from .qlint import Assumptions, Machine, analyze_image
from .report import Finding

_IMG_CACHE: dict[int, Any] = {}


def _reference_image(bits: int = 15):
    if bits not in _IMG_CACHE:
        from repro.deploy.goldens import build_reference_artifact
        from repro.deploy.image import build_image
        _IMG_CACHE[bits] = build_image(
            build_reference_artifact(seed=0, bits=bits))
    return _IMG_CACHE[bits]


# ---------------------------------------------------------------------------
# qlint fixtures — each returns the findings the seeded defect produced
# ---------------------------------------------------------------------------

def _fx_acc_width_downgrade() -> list[dict[str, Any]]:
    """Downgrade the matvec accumulator to int32: the proven Q15 row-sum
    ranges exceed 32 bits, so q-acc-width must fire."""
    rec = analyze_image(_reference_image(), Assumptions(widths={"acc": 32}))
    return rec["findings"]


def _fx_fine_width_downgrade() -> list[dict[str, Any]]:
    """Downgrade the fine intermediates to int16: the ±FINE_CLIP range
    needs 30 bits, so q-acc-width must fire at the .fine sites."""
    rec = analyze_image(_reference_image(), Assumptions(widths={"fine": 16}))
    return rec["findings"]


def _fx_requant_tamper() -> list[dict[str, Any]]:
    """Replace the gate requant with a denormalized m=3, sh=0 constant
    (the kind a hand-edited image could carry): q-requant-range fires."""
    img = _reference_image()
    plan = plan_from_image(img)
    plan = dataclasses.replace(plan, rq_gate=Requant(m=3, sh=0, pre=0))
    rec = analyze_image(img, plan=plan)
    return rec["findings"]


def _fx_requant_overflow() -> list[dict[str, Any]]:
    """Feed a requant an accumulator interval wide enough that
    ``(acc >> pre) * m`` escapes int64 — the acc_bits contract of
    quantize_multiplier, violated on purpose."""
    m = Machine()
    m.requant("fx", Requant(m=(1 << 24), sh=30, pre=0),
              Interval(-(1 << 45), (1 << 45) - 1))
    return [f.to_dict() for f in m.findings]


def _fx_lut_truncated() -> list[dict[str, Any]]:
    """Hand the LUT primitive a 128-entry table while the program still
    computes 256-entry indices: q-lut-bounds fires."""
    m = Machine()
    m.lut("fx", Interval(-(1 << 20), 1 << 20), m=1 << 10, sh=15,
          table=np.zeros(128, np.int64))
    return [f.to_dict() for f in m.findings]


def _fx_int16_neg() -> list[dict[str, Any]]:
    """Negate an interval containing INT16_MIN into an int16 slot:
    ``-(-32768)`` does not exist in int16, q-int16-neg fires."""
    m = Machine()
    m.neg("fx", Interval(I16_MIN, 0), bits=16)
    return [f.to_dict() for f in m.findings]


def _fx_shift_hazard() -> list[dict[str, Any]]:
    """A shift amount outside [0, 63] and a right shift of a negative
    operand outside the documented arithmetic sites: q-shift-neg."""
    m = Machine()
    m.shr("fx.amount", Interval(0, 100), 64, 64, arith_ok=True)
    m.shr("fx.negative", Interval(-5, 5), 1, 64, arith_ok=False)
    return [f.to_dict() for f in m.findings]


# ---------------------------------------------------------------------------
# detlint fixtures — seeded-defect sources through the production linter
# ---------------------------------------------------------------------------

_DET_SOURCES: dict[str, tuple[str, str]] = {
    "det-builtin-hash": ("data/fx.py", (
        "def seed_for(split):\n"
        "    return hash(split) % 2**32\n")),
    "det-wallclock": ("serve/fx.py", (
        "import time\n"
        "def snapshot(state):\n"
        "    state['saved_at'] = time.time()\n"
        "    return state\n")),
    "det-donate-argnums": ("serve/fx.py", (
        "import jax\n"
        "def build(step):\n"
        "    return jax.jit(step, donate_argnums=(0, 1))\n")),
    "det-jit-pallas": ("kernels/fx.py", (
        "import jax\n"
        "@jax.jit\n"
        "def fused(x):\n"
        "    return pl.pallas_call(kern, out_shape=x)(x)\n")),
    "det-set-iteration": ("serve/fx.py", (
        "def dispatch_order(shards):\n"
        "    return [s for s in set(shards)]\n")),
    "det-span-pairing": ("serve/fx.py", (
        "def tick(self, tr):\n"
        "    t0 = tr.open('fleet.dispatch')\n"
        "    self.work()\n")),
    "det-span-registry": ("serve/fx.py", (
        "def tick(self, tr):\n"
        "    t0 = tr.open('fleet.dispach')\n"
        "    self.work()\n"
        "    tr.close(t0)\n")),
    "det-conserved-counters": ("serve/fleet/engine.py", (
        "class FleetEngine:\n"
        "    def __init__(self):\n"
        "        self._retired = {'stream_steps': 0, 'completed': 0,\n"
        "                         'ring_spills': 0}\n")),
}


def _det_fixture(check: str) -> Callable[[], list[dict[str, Any]]]:
    def run() -> list[dict[str, Any]]:
        path, src = _DET_SOURCES[check]
        findings, _ = lint_source(src, path)
        return [f.to_dict() for f in findings]
    return run


def _fx_suppression_honored() -> list[dict[str, Any]]:
    """The inverse fixture: a defect carrying a well-formed suppression
    comment must yield zero findings and exactly one recorded
    suppression — silence without a record would hide exceptions from
    review."""
    src = ("import jax\n"
           "def build(step):\n"
           "    return jax.jit(step,\n"
           "                   donate_argnums=(0,))"
           "  # detlint: ignore[det-donate-argnums] training-only step\n")
    findings, suppressions = lint_source(src, "serve/fx.py")
    ok = (not findings and len(suppressions) == 1
          and suppressions[0].check == "det-donate-argnums"
          and suppressions[0].reason == "training-only step")
    if ok:
        # report the expected check as "caught" via a synthetic marker
        return [Finding("suppression-honored", "serve/fx.py:4",
                        "suppressed defect recorded, not silenced").to_dict()]
    return []


#: fixture name -> (check id that must appear in the findings, runner)
FIXTURES: dict[str, tuple[str, Callable[[], list[dict[str, Any]]]]] = {
    "acc-width-downgrade": ("q-acc-width", _fx_acc_width_downgrade),
    "fine-width-downgrade": ("q-acc-width", _fx_fine_width_downgrade),
    "requant-tamper": ("q-requant-range", _fx_requant_tamper),
    "requant-overflow": ("q-requant-overflow", _fx_requant_overflow),
    "lut-truncated": ("q-lut-bounds", _fx_lut_truncated),
    "int16-neg": ("q-int16-neg", _fx_int16_neg),
    "shift-hazard": ("q-shift-neg", _fx_shift_hazard),
    **{f"seeded-{c}": (c, _det_fixture(c)) for c in _DET_SOURCES},
    "suppression-honored": ("suppression-honored", _fx_suppression_honored),
}


def run_selftest() -> dict[str, Any]:
    """Run every fixture; ``ok`` is True only when every seeded defect
    was caught by exactly the check it targets."""
    results = {}
    for name, (expect, fn) in FIXTURES.items():
        findings = fn()
        caught = any(f["check"] == expect for f in findings)
        results[name] = {"expect": expect, "caught": caught,
                         "n_findings": len(findings)}
    return {"fixtures": results,
            "ok": all(r["caught"] for r in results.values())}
