"""The comparison that decides ``correct``.

Every prediction that a sampled stream was due to emit by the last tick is
looked up among what the fleet emitted and compared with the reference's
logits for the same window of samples.  Three numbers, each with the
limit the configuration file states (``check`` there):

* ``logit_max_abs_gap``: the widest |program - reference| over every
  compared logit (the configuration states a bit-exact guarantee);
* ``missing_predictions``: due but never emitted;
* ``extra_predictions``: emitted for a window that was not due, or twice.
"""
from __future__ import annotations

import numpy as np

NAMES = ("logit_max_abs_gap", "missing_predictions", "extra_predictions")


def collect(log: list, check_ids) -> dict:
    """(stream id, step) -> logits, from the emitted batches of the sampled
    streams.  ``log`` holds (stream_ids, steps, logits) per emitted batch;
    a prediction emitted twice is kept as a list."""
    got: dict = {}
    for sids, steps, logits in log:
        for j, sid in enumerate(sids):
            if sid in check_ids:
                got.setdefault((sid, int(steps[j])), []).append(logits[j])
    return got


def numbers(got: dict, expected: dict, reference) -> dict:
    """The compared numbers.  ``expected`` maps (stream id, step) of every
    prediction due from the sampled streams to its window of samples;
    ``reference.logits`` gives the reference logits of a batch of windows."""
    keys = list(expected)
    want = (reference.logits(np.stack([expected[k] for k in keys])) if keys
            else np.zeros((0, 1), np.float32))
    gaps, missing = [], 0
    for key, ref in zip(keys, want):
        emitted = got.get(key)
        if not emitted:
            missing += 1
            continue
        gaps.append(float(np.max(np.abs(np.asarray(emitted[0], np.float64)
                                        - ref.astype(np.float64)))))
    extra = sum(len(v) - 1 for v in got.values()) + sum(
        len(v) for key, v in got.items() if key not in expected)
    return {"logit_max_abs_gap": max(gaps, default=0.0),
            "missing_predictions": missing, "extra_predictions": extra,
            "due": len(keys), "gaps": np.asarray(gaps)}


def verdict(nums: dict, limits: dict) -> tuple[bool, dict, int]:
    """(correct, {name: {"value", "limit"}}, failed predictions) against
    the configuration's limits; a number above its limit, or not a number,
    fails.  A prediction fails when it is missing or its gap is over the
    limit."""
    checks = {n: {"value": nums[n], "limit": limits[n]} for n in NAMES}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    over = int(np.count_nonzero(~(nums["gaps"] <= limits["logit_max_abs_gap"])))
    return ok, checks, over + nums["missing_predictions"]
