"""Staggered packets from a fleet of sensor streams (``"generator":
"staggered"`` in a mix file).

The mix file (``traffic/<name>.json``) sets the parameters; the seed sets
everything else.  Each stream

* plays synthetic HAPT windows back to back (a seeded walk over a pool of
  ``pool_windows`` windows, so the k-th window of stream i is known without
  replaying it);
* starts in one of ``window_phases`` cohorts: cohort c gets its first packet
  on tick c of the roll-in, so its windows end on ticks c + 127, c + 255,
  ...  Cohorts are dealt round-robin inside each shard, so every shard has
  the same number of streams (to one) on every phase, whatever the seed.
  One phase is lockstep traffic: every window ends on the same tick;
* then gets a packet of ``packet_samples`` samples every ``packet_samples``
  ticks, on its packet phase (dealt round-robin over a seeded order), so a
  packet arrives before the last one runs out and every stream advances on
  every tick.

Every seed gives the same counts of streams, packets and windows per tick,
in another order and with other samples.
"""
from __future__ import annotations

import numpy as np

import synth_hapt

MIX_KEYS = ("loop", "streams", "window_phases", "packet_samples", "tick_hz",
            "pool_windows", "pool_split", "check_streams")


def check_mix(mix: dict) -> dict:
    """Validate a mix file's parameters; returns the mix."""
    missing = [k for k in MIX_KEYS if k not in mix]
    if missing:
        raise ValueError(f"traffic mix {mix.get('name')!r} lacks {missing}")
    if mix["loop"] not in ("closed", "open"):
        raise ValueError(f"loop must be 'closed' or 'open': {mix['loop']!r}")
    pool = mix["pool_windows"]
    if pool < 2 or pool & (pool - 1):
        raise ValueError(f"pool_windows must be a power of two: {pool}")
    if mix["window_phases"] < 1 or mix["packet_samples"] < 1:
        raise ValueError("window_phases and packet_samples must be at least 1")
    return mix


class Generator:
    """The schedule and samples of one run of a mix."""

    def __init__(self, mix: dict, model: dict, seed_seq: np.random.SeedSequence):
        check_mix(mix)
        self.mix, self.window = mix, model["window"]
        n, self.packet = mix["streams"], mix["packet_samples"]
        self.phases = mix["window_phases"]
        rng = np.random.default_rng(seed_seq)
        self.ids = [f"sensor-{i}" for i in range(n)]
        self.pool = synth_hapt.windows(mix["pool_split"], int(rng.integers(2**31)),
                                       mix["pool_windows"])
        P = len(self.pool)
        self.offset = rng.integers(0, P, n)
        self.stride = 2 * rng.integers(0, P // 2, n) + 1   # odd: visits all
        self.packet_phase = np.empty(n, np.int64)
        self.packet_phase[rng.permutation(n)] = np.arange(n) % self.packet
        self._groups = [np.nonzero(self.packet_phase == p)[0]
                        for p in range(self.packet)]
        self.cohort: np.ndarray | None = None
        self.fed = np.zeros(n, np.int64)          # packets handed out so far
        self.check = np.sort(rng.choice(n, size=min(mix["check_streams"], n),
                                        replace=False))
        self.check_ids = {self.ids[i] for i in self.check}
        self._rng = rng

    # -- what the harness calls -------------------------------------------
    @property
    def capacity(self) -> int:
        return len(self.ids)

    @property
    def max_buffered(self) -> int:
        """The most samples a stream holds at once: a packet arrives while
        up to a whole packet less one sample is still buffered."""
        return 2 * self.packet - 1

    @property
    def rollin_ticks(self) -> int:
        return self.phases

    def setup(self, system) -> None:
        """Attach every stream with an empty buffer, then deal the cohorts."""
        self.assign_cohorts(system.attach(self.ids))

    def prepare(self, tick: int):
        """The packets due before step ``tick`` (ticks count from 0, the
        first roll-in tick); made outside the timed tick."""
        idx = self.feeds(tick)
        return idx, self.packets(idx)

    def drive(self, system, batch) -> None:
        """Hand the prepared packets to the system (inside the timed tick)."""
        idx, pk = batch
        ids = self.ids
        for j, i in enumerate(idx):
            system.feed(ids[i], pk[j])

    def expected(self, last_tick: int, emitted=None) -> dict:
        """{(stream id, step): window samples} of every prediction the
        sampled streams were due to emit once ``last_tick`` has run.  A
        window's input does not depend on what the fleet emitted, so
        ``emitted`` is not read."""
        done = self.windows_done(last_tick)
        out = {}
        for i in self.check:
            ks = np.arange(done[i])
            for k, win in zip(ks, self.pool[self.window_index(i, ks)]):
                out[(self.ids[i], int(k + 1) * self.window)] = win
        return out

    # -- the schedule ----------------------------------------------------
    def assign_cohorts(self, shard_of: np.ndarray) -> None:
        """Deal window phases round-robin inside each shard, in seeded order."""
        shard_of = np.asarray(shard_of)
        cohort = np.empty(len(shard_of), np.int64)
        for s in np.unique(shard_of):
            mine = np.nonzero(shard_of == s)[0]
            cohort[self._rng.permutation(mine)] = np.arange(mine.size) % self.phases
        self.cohort = cohort

    def feeds(self, tick: int) -> np.ndarray:
        """Indices of the streams that get a packet before step ``tick``."""
        regular = self._groups[tick % self.packet]
        if tick >= self.phases:
            return regular
        first = np.nonzero(self.cohort == tick)[0]
        return np.concatenate([first, regular[self.cohort[regular] < tick]])

    def window_index(self, i, k):
        """Pool index of the k-th window of stream(s) i."""
        return (self.offset[i] + k * self.stride[i]) % len(self.pool)

    def packets(self, idx: np.ndarray) -> np.ndarray:
        """The next packet of each stream in ``idx``: (len(idx), P, d)."""
        s = self.fed[idx, None] * self.packet + np.arange(self.packet)
        self.fed[idx] += 1
        win = self.window_index(idx[:, None], s // self.window)
        return self.pool[win, s % self.window]

    def windows_done(self, last_tick: int) -> np.ndarray:
        """Windows each stream has completed once ``last_tick`` has run."""
        return np.maximum(last_tick - self.cohort + 1, 0) // self.window
