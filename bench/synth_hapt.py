"""Synthetic HAPT windows: the sample source of the benchmark's traffic.

A copy of the program's ``repro.data.hapt.generate_synthetic`` (kept here so
the yardstick does not move when the program does): tri-axial 50 Hz
acceleration in g, 128-sample windows, six activity classes, per-subject
cadence, orientation and noise, AR(1) sensor noise and a slow drift.
"""
from __future__ import annotations

import zlib

import numpy as np

CLASSES = ("WALKING", "UPSTAIRS", "DOWNSTAIRS", "SITTING", "STANDING", "LAYING")
WINDOW = 128
RATE_HZ = 50.0
SPLIT_SUBJECTS = {"train": range(1, 22), "val": range(22, 26),
                  "test": range(26, 31)}

_GRAVITY = {
    "WALKING": (0.05, -0.10, 1.00),
    "UPSTAIRS": (0.18, -0.05, 0.98),
    "DOWNSTAIRS": (-0.15, 0.08, 0.98),
    "SITTING": (0.55, 0.10, 0.82),
    "STANDING": (0.02, -0.02, 1.00),
    "LAYING": (0.98, 0.05, -0.12),
}
_DYNAMIC = {"WALKING": 0.24, "UPSTAIRS": 0.20, "DOWNSTAIRS": 0.30}


def _subject_traits(subject: int) -> dict:
    rng = np.random.default_rng(10_000 + subject)
    return {
        "cadence_hz": float(rng.uniform(1.4, 2.2)),
        "orient_jitter": rng.normal(0, 0.06, size=3),
        "noise": float(rng.uniform(0.015, 0.04)),
        "amp": float(rng.uniform(0.8, 1.25)),
    }


def _window_for(cls: str, traits: dict, rng: np.random.Generator) -> np.ndarray:
    t = np.arange(WINDOW) / RATE_HZ
    g = np.asarray(_GRAVITY[cls]) + traits["orient_jitter"]
    g = g / np.linalg.norm(g)
    sig = np.tile(g, (WINDOW, 1)).astype(np.float64)
    if cls in _DYNAMIC:
        f = traits["cadence_hz"] * rng.uniform(0.92, 1.08)
        phase = rng.uniform(0, 2 * np.pi)
        amp = _DYNAMIC[cls] * traits["amp"]
        fund = np.sin(2 * np.pi * f * t + phase)
        h2 = np.sin(2 * np.pi * 2 * f * t + 2.1 * phase)
        if cls == "WALKING":
            mix = amp * (fund + 0.35 * h2)
            lateral = 0.4 * amp * np.sin(2 * np.pi * 0.5 * f * t + phase)
        elif cls == "UPSTAIRS":
            mix = amp * (0.8 * fund + 0.6 * h2)
            lateral = 0.25 * amp * np.sin(2 * np.pi * 0.5 * f * t)
        else:
            impact = np.clip(np.sin(2 * np.pi * f * t + phase), 0.55, None) - 0.55
            mix = amp * (0.6 * fund + 0.5 * h2 + 2.2 * impact)
            lateral = 0.35 * amp * np.sin(2 * np.pi * 0.5 * f * t + 0.7)
        sig[:, 2] += mix
        sig[:, 0] += 0.45 * mix + 0.3 * lateral
        sig[:, 1] += lateral
    elif cls == "SITTING":
        sig += 0.02 * np.sin(2 * np.pi * 0.25 * t + rng.uniform(0, 6.28))[:, None]
    e = rng.normal(0, traits["noise"], size=(WINDOW, 3))
    for i in range(1, WINDOW):
        e[i] += 0.5 * e[i - 1]
    drift = rng.normal(0, 0.01, size=3) * (t / t[-1])[:, None]
    return (sig + e + drift).astype(np.float32)


def windows(split: str, seed: int, n: int) -> np.ndarray:
    """(n, 128, 3) float32 synthetic windows of ``split``'s subjects."""
    subjects = list(SPLIT_SUBJECTS[split])
    rng = np.random.default_rng(seed * 7919 + zlib.crc32(split.encode()) % 100_000)
    traits = {s: _subject_traits(s) for s in subjects}
    xs = np.empty((n, WINDOW, 3), np.float32)
    for i in range(n):
        s = subjects[i % len(subjects)]
        xs[i] = _window_for(CLASSES[int(rng.integers(0, len(CLASSES)))],
                            traits[s], rng)
    return xs
