"""A closed-loop backlog of LM requests (``"generator": "lm_backlog"`` in a
mix file): heavy-tailed prompts and answers at a full decode batch.

The mix file sets the parameters; the seed sets everything else.

* Prompt and answer lengths are lognormal (``prompt`` and ``output``:
  median, sigma, clipped to [min, max]), taken from ``quantiles`` fixed
  quantiles of each distribution, each list walked in a seeded order
  reshuffled every pass, so every seed's window sees nearly the same mix.
  Token ids are uniform over the vocabulary.
* Decode is greedy with no end-of-sequence token, so a request holds its
  slot for exactly its answer's length.
* Set-up submits a first wave of ``slots`` requests, which the first tick
  admits all at once, so the window starts in steady state: each keeps a
  residual share of its answer's length, uniform over (0, 1] and taken
  from ``slots`` fixed strata paired with the answer lengths by a fixed
  rule, so the first wave drains on the same schedule for every seed (a
  random share made the window's admissions, and so its rate, swing from
  seed to seed).  From then on,
  before every tick, as many requests are submitted as the engine has
  free slots beyond its pending ones: the queue never runs dry.
* Checked: ``check_requests`` first-wave requests at evenly spaced ranks
  of their residual budgets, ties broken by the seed.  The first wave
  holds every answer quantile once a pass, so its residual budgets are
  the same for every seed, and every seed checks about as many steps:
  each checked step costs the program one row copy, which would swing
  the rate by seed.  The first tick prefills them (step 0) and decodes
  once (step 1); tick t emits step t + 1, up to the request's budget.  Step k's input is the
  prompt and the program's first k tokens, with the experts the program
  chose for those positions (carried by each emitted choice).
"""
from __future__ import annotations

import statistics

import numpy as np

MIX_KEYS = ("loop", "slots", "prompt", "output", "quantiles", "check_requests")


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """The n mid-quantiles (i + 1/2) / n of a lognormal length, clipped."""
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


class _Walk:
    """Indices of a list of values walked in seeded order, reshuffled
    every pass; ``passes`` counts the passes begun."""

    def __init__(self, values: np.ndarray, rng: np.random.Generator):
        self.values, self.rng, self.order, self.passes = values, rng, [], 0

    def next(self) -> int:
        if not self.order:
            self.order = list(self.rng.permutation(len(self.values)))
            self.passes += 1
        return int(self.order.pop())


class Generator:
    def __init__(self, mix: dict, model: dict, seed_seq: np.random.SeedSequence):
        missing = [k for k in MIX_KEYS if k not in mix]
        if missing or mix["loop"] != "closed":
            raise ValueError(f"lm_backlog mix {mix.get('name')!r}: missing {missing} "
                             f"or not a closed loop")
        rng = np.random.default_rng(seed_seq)
        self.vocab = model["vocab_size"]
        self.slots = mix["slots"]
        self._prompts = _Walk(quantile_lengths(mix["prompt"], mix["quantiles"]), rng)
        self._outputs = _Walk(quantile_lengths(mix["output"], mix["quantiles"]), rng)
        self._rng = rng
        self._n = 0
        self.system = None
        self.first_wave = [self._request() for _ in range(self.slots)]
        n_q = len(self._outputs.values)
        for r in self.first_wave:
            # stratum of the residual share: the answer's quantile spread
            # over the strata by a fixed stride, one stratum per pass
            stratum = (r["quantile"] * 37) % n_q + n_q * (r["pass"] - 1)
            share = (stratum % self.slots + 0.5) / self.slots
            r["budget"] = max(1, int(round(share * r["budget"])))
        # checked: the first-wave requests at evenly spaced ranks of the
        # residual budget, whose multiset is the same for every seed, so
        # every seed checks about as many steps (each costs a row copy)
        n = min(mix["check_requests"], self.slots)
        tie = rng.permutation(self.slots)
        ranked = sorted(range(self.slots), key=lambda i: (self.first_wave[i]["budget"], tie[i]))
        picked = [ranked[(2 * j + 1) * self.slots // (2 * n)] for j in range(n)]
        self.checked = [self.first_wave[i] for i in sorted(picked)]
        self.check_ids = {r["id"] for r in self.checked}

    def _request(self) -> dict:
        rid = f"req-{self._n}"
        self._n += 1
        n = self._prompts.values[self._prompts.next()]
        q = self._outputs.next()
        return {"id": rid, "prompt": self._rng.integers(0, self.vocab, n).astype(np.int32),
                "budget": int(self._outputs.values[q]), "quantile": q,
                "pass": self._outputs.passes}

    # -- what the harness calls -------------------------------------------
    @property
    def capacity(self) -> int:
        return self.slots

    max_buffered = 1
    rollin_ticks = 0

    def setup(self, system) -> None:
        self.system = system
        for r in self.first_wave:
            system.submit(r["id"], r["prompt"], r["budget"], r["id"] in self.check_ids)

    def prepare(self, tick: int) -> list:
        """The requests that keep the queue at least as long as the free
        slots the next tick admits into (made outside the timed tick)."""
        short = self.system.free_slots() - self.system.pending()
        return [self._request() for _ in range(max(short, 0))]

    def drive(self, system, batch: list) -> None:
        for r in batch:
            system.submit(r["id"], r["prompt"], r["budget"], False)

    def expected(self, last_tick: int, emitted: dict) -> dict:
        """{(request id, step): {"request", "tokens", "routes"}} of every
        checked step due once ``last_tick`` has run.  Tokens and routes of
        one request are views of one array; a token never emitted is
        already missing, and 0 stands in for it, as no routes do."""
        out = {}
        for r in self.checked:
            rid, prompt = r["id"], r["prompt"]
            steps = min(r["budget"], last_tick + 2)
            got = [emitted.get((rid, k)) for k in range(steps)]
            tokens = np.concatenate([prompt, [int(c or 0) for c in got[:-1]]]).astype(np.int64)
            parts = [getattr(c, "routes", None) for c in got]
            routes = None if any(p is None for p in parts) else np.concatenate(parts)
            for k in range(steps):
                n = len(prompt) + k
                out[(rid, k)] = {"request": rid, "tokens": tokens[:n],
                                 "routes": None if routes is None else routes[:n]}
        return out
