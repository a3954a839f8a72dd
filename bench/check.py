"""The comparison that decides ``correct``.

Every prediction that a sampled stream was due to emit by the last tick is
looked up among what the program emitted and compared with the reference's
logits for the same input.  Each number has the limit the configuration
file states (``check`` there):

* ``logit_max_abs_gap``: the widest |program - reference| over every
  compared logit (the fleet's configurations state a bit-exact guarantee);
* ``missing_predictions``: due but never emitted;
* ``extra_predictions``: emitted for an input that was not due, or twice;
* ``choice_mismatches``, only where the configuration gives it a limit:
  compared predictions whose emitted choice (an emitted token) is not an
  index of the largest emitted logit (greedy decode), or that came with no
  choice.  Where the reference is fed the program's own choices, this is
  what catches a choice that its logits did not make.
"""
from __future__ import annotations

import numpy as np

NAMES = ("logit_max_abs_gap", "missing_predictions", "extra_predictions",
         "choice_mismatches")


def _is_argmax(logits: np.ndarray, choice) -> bool:
    """Whether ``choice`` is an index of the largest logit (any, on a tie)."""
    return bool(0 <= int(choice) < logits.size
                and logits[int(choice)] == np.max(logits))


def collect(log: list, check_ids) -> tuple[dict, dict]:
    """From the emitted batches of the sampled streams: (stream id, step)
    -> the emitted logits, kept as a list where a prediction was emitted
    twice; and (stream id, step) -> the program's first choice there, for
    batches that carry one.  ``log`` holds (stream_ids, steps, logits) or
    (stream_ids, steps, logits, choices) per emitted batch."""
    got: dict = {}
    chosen: dict = {}
    for sids, steps, logits, *rest in log:
        for j, sid in enumerate(sids):
            if sid in check_ids:
                key = (sid, int(steps[j]))
                got.setdefault(key, []).append(logits[j])
                if rest:
                    chosen.setdefault(key, rest[0][j])
    return got, chosen


def numbers(got: dict, expected: dict, reference, limits: dict,
            chosen: dict | None = None) -> dict:
    """The compared numbers.  ``expected`` maps (stream id, step) of every
    prediction due from the sampled streams to the reference's input for
    it; ``reference.logits`` takes those inputs as a list in key order.
    ``choice_mismatches`` is counted when ``limits`` has a limit for it,
    over ``chosen`` as ``collect`` gives it."""
    keys = list(expected)
    want = reference.logits([expected[k] for k in keys]) if keys else []
    greedy = "choice_mismatches" in limits
    gaps, mism, missing = [], [], 0
    for key, ref in zip(keys, want):
        emitted = got.get(key)
        if not emitted:
            missing += 1
            continue
        lg = np.asarray(emitted[0], np.float64)
        gaps.append(float(np.max(np.abs(lg - np.asarray(ref, np.float64)))))
        c = (chosen or {}).get(key)
        mism.append(greedy and (c is None or not _is_argmax(lg, c)))
    extra = sum(len(v) - 1 for v in got.values()) + sum(
        len(v) for key, v in got.items() if key not in expected)
    # np.max, not max(): a NaN gap anywhere makes the widest gap NaN
    out = {"logit_max_abs_gap": float(np.max(gaps)) if gaps else 0.0,
           "missing_predictions": missing, "extra_predictions": extra,
           "due": len(keys), "gaps": np.asarray(gaps),
           "mismatched": np.asarray(mism, bool)}
    if greedy:
        out["choice_mismatches"] = int(np.count_nonzero(out["mismatched"]))
    return out


def verdict(nums: dict, limits: dict) -> tuple[bool, dict, int]:
    """(correct, {name: {"value", "limit"}}, failed predictions) against
    the configuration's limits; a number above its limit, or not a number,
    fails.  A prediction fails when it is missing, its gap is over the
    limit or its choice does not match."""
    checks = {n: {"value": nums[n], "limit": limits[n]} for n in NAMES if n in nums}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    over = ~(nums["gaps"] <= limits["logit_max_abs_gap"]) | nums["mismatched"]
    return ok, checks, int(np.count_nonzero(over)) + nums["missing_predictions"]
