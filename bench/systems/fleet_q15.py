"""The system under test for the Q15 FastGRNN configurations: the program's
``FleetEngine`` serving the cell through its compiled Pallas step.

The artifact is made by the program's own deploy pipeline
(``default_deploy_pipeline``: hard-thresholding, PTQ, deploy calibration,
table packing) from the float weights the benchmark drew.  Like an
offline export, that runs on the host's CPU device where JAX has one.
"""
from __future__ import annotations

import contextlib

import jax
import numpy as np


def _host_device():
    try:
        return jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:            # no CPU backend next to the accelerator
        return contextlib.nullcontext()


class System:
    def __init__(self, cfg: dict, params: dict, *, slots: int, ring: int,
                 bits: int | None = None, trace: bool = False):
        from repro.compress import ModelArtifact, default_deploy_pipeline
        from repro.obs import Observability, Tracer
        from repro.serve.fleet import FleetConfig, FleetEngine
        from repro.serve.streaming import StreamingConfig

        comp, serve, model = cfg["compression"], cfg["serving"], cfg["model"]
        shards = serve["shards"]
        if slots % shards:
            raise ValueError(f"{slots} slots do not fill {shards} shards")
        pipe = default_deploy_pipeline(
            bits=bits or comp["weight_bits"], calib=comp["deploy_calibration"],
            headroom=comp["calibration_headroom"], sparsity=comp["iht_sparsity"])
        with _host_device():
            art = pipe.run(ModelArtifact.from_params(params))
        self.tracer = Tracer() if trace else None
        self.fleet = FleetEngine.from_artifact(art, FleetConfig(
            shards=shards, placement=serve["placement"], max_pending_per_shard=0,
            stream=StreamingConfig(
                max_slots=slots // shards, window=model["window"],
                sample_rate_hz=model["sample_rate_hz"], backend=serve["backend"],
                device_resident=serve["device_resident"], batch_events=True,
                ring_capacity=ring, max_ring_capacity=ring)),
            obs=Observability(tracer=self.tracer) if trace else None)

    def attach(self, ids: list) -> np.ndarray:
        """Attach streams with an empty buffer; returns each one's shard.
        Every stream has to find a slot at once."""
        fleet = self.fleet
        for sid in ids:
            if fleet.attach(sid) != "active":
                raise RuntimeError(f"stream {sid} found no free slot")
        return np.array([fleet.shard_of(sid) for sid in ids])

    def feed(self, sid: str, samples: np.ndarray) -> None:
        self.fleet.feed(sid, samples)

    def detach(self, sid: str):
        """End a stream; its partial-window event, if any, as one emitted
        batch (stream_ids, steps, logits), else None."""
        ev = self.fleet.detach(sid)
        return None if ev is None else ([ev.stream_id], [ev.step], ev.logits[None])

    def step(self) -> list:
        """One tick; the emitted batches as (stream_ids, steps, logits)."""
        return [(b.stream_ids, b.steps, b.logits) for b in self.fleet.step()]

    @staticmethod
    def sync() -> None:
        """Wait until every array the program holds is computed: the tick's
        step and the window resets it issued without waiting."""
        jax.block_until_ready(jax.live_arrays())

    def counters(self) -> dict:
        st = self.fleet.stats()
        return {"stream_steps": st["stream_steps"], "ticks": st["ticks"],
                **{f"transfers.{k}": v for k, v in st["transfers"].items()}}

    def span_totals(self) -> dict:
        """Seconds recorded so far per program span (empty when untraced)."""
        return self.tracer.totals_s() if self.tracer is not None else {}

    def close(self) -> None:
        self.fleet = None
