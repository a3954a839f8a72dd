"""Host<->device transfer accounting for the device-resident serving path.

The fleet's "zero steady-state copies of h" claim (device-resident async
ticks) must be a *measured invariant*, not a comment: every
``Q15StreamStep`` owns a :class:`TransferLedger` and books the bytes it
moves across the host/device boundary — per-tick ``x``/mask staging
(``h2d``), hidden-state uploads/downloads (``h2d``/``d2h`` with
``state=True``), and emission/tap/snapshot row pulls.  The ledger is a
handful of plain int adds, cheap enough to stay always-on (no
Observability bundle required), and tests/benchmarks read it through
``stats()["transfers"]``.  Besides bytes it counts crossings
(``h2d_count`` / ``d2h_count``): on a host-bound tick each copy to the
device costs a dispatch and each pull a sync, whatever its size.


* a steady-state fused tick on the device-resident jit/pallas path books
  **zero** ``h_h2d_bytes``/``h_d2h_bytes`` (the regression gate in
  ``tests/test_device_fleet.py``);
* a device backend's host-in/host-out ``Q15StreamStep.step`` books a
  full ``h`` round-trip per call.

Byte counts are *logical* transfer volume (what would cross PCIe/ICI on
a real accelerator); on CPU jax may alias instead of copying, but the
invariant "no h crosses the boundary per steady tick" is the same.
"""
from __future__ import annotations

#: Ledger/snapshot keys, in canonical order: total staged bytes each way,
#: the hidden-state-only sub-accounts the zero-copy gate reads, and the
#: number of crossings each way.
TRANSFER_KEYS = ("h2d_bytes", "d2h_bytes", "h_h2d_bytes", "h_d2h_bytes",
                 "h2d_count", "d2h_count")


class TransferLedger:
    """Monotonic host<->device counters (one per kernel instance)."""

    __slots__ = TRANSFER_KEYS

    def __init__(self) -> None:
        for k in TRANSFER_KEYS:
            setattr(self, k, 0)

    def h2d(self, nbytes: int, *, state: bool = False) -> None:
        """Book one host->device copy; ``state=True`` marks hidden-state
        bytes (the zero-copy invariant's sub-account)."""
        self.h2d_bytes += nbytes
        self.h2d_count += 1
        if state:
            self.h_h2d_bytes += nbytes

    def d2h(self, nbytes: int, *, state: bool = False) -> None:
        """Book one device->host pull (a blocking copy)."""
        self.d2h_bytes += nbytes
        self.d2h_count += 1
        if state:
            self.h_d2h_bytes += nbytes

    def snapshot(self) -> dict[str, int]:
        return {k: getattr(self, k) for k in TRANSFER_KEYS}


def sum_transfers(snapshots) -> dict[str, int]:
    """Fold ledger snapshots (dicts) into one total — the fleet's
    ``stats()["transfers"]`` roll-up across shard + group kernels."""
    tot = dict.fromkeys(TRANSFER_KEYS, 0)
    for snap in snapshots:
        for k in TRANSFER_KEYS:
            tot[k] += snap[k]
    return tot
