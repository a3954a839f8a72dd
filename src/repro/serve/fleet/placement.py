"""Shard -> device placement for the fleet.

Shards put their kernel dispatch on distinct jax devices when the process
has more than one (real accelerators, or CPU faked via
``--xla_force_host_platform_device_count=N`` — the setting tier-1 CI uses)
and fall back to process-local NumPy/default-device shards otherwise, so
the fleet runs everywhere tier-1 runs.

Placement modes:

* ``"auto"``    — distinct devices if the backend is jit/pallas and more
  than one jax device exists; the default device otherwise.
* ``"devices"`` — force round-robin device assignment.
* ``"host"``    — everything on the default device / process-local NumPy.
  This is also the mode under which tick fusion batches every shard into
  ONE kernel dispatch (see ``fleet.engine``), which on a small-core host
  is the fastest configuration — per-dispatch latency amortizes across
  shards instead of repeating per shard.
"""
from __future__ import annotations

from typing import Any

import jax

PLACEMENTS = ("auto", "devices", "host")


def shard_devices(n_shards: int, placement: str = "auto",
                  backend: str = "exact") -> list[Any]:
    """Per-shard device assignment (round-robin over ``jax.devices()``),
    or ``[None] * n_shards`` for the process-local fallback.  The exact
    backend is vectorized NumPy by construction — its per-stream
    bit-identity contract does not involve a jax device — so it always
    takes the fallback."""
    if placement not in PLACEMENTS:
        raise ValueError(f"placement must be one of {PLACEMENTS}")
    if placement == "host" or backend == "exact":
        return [None] * n_shards
    devs = jax.devices()
    if placement == "auto" and len(devs) < 2:
        return [None] * n_shards
    return [devs[i % len(devs)] for i in range(n_shards)]


def device_groups(devices: list[Any]) -> list[tuple[Any, list[int]]]:
    """Group shard indices by device identity, preserving shard order —
    the fleet's fused tick makes ONE kernel dispatch per group and, on
    the device-resident path, issues every group's dispatch before
    waiting on any (``fleet.engine._step_fused``).  ``None`` (the
    process-local fallback) is a single group."""
    groups: dict[Any, list[int]] = {}
    for i, dev in enumerate(devices):
        groups.setdefault(dev, []).append(i)
    return list(groups.items())
