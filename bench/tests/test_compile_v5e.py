"""Compile each cell's step kernel for a described TPU v5e chip, at the
cell's own row count (every shard of the fleet is fused into one dispatch
on one chip).  No chip is needed: the TPU compiler compiles for a topology
that is only described, inside a fixture, never at import."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import BENCH, load_json


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _cells():
    spec = load_json("BENCHMARK.json")
    files = {c["name"]: c["file"] for c in spec["configs"]}
    out = []
    for wl in spec["workloads"]:
        mix = load_json(f"bench/traffic/{wl['traffic']}.json")
        out.append(pytest.param(files[wl["config"]], mix["streams"], id=wl["name"]))
    return out


@pytest.mark.parametrize("config_file,rows", _cells())
def test_cell_step_kernel_compiles(config_file, rows, one_chip, monkeypatch):
    import harness
    from repro.compress import ModelArtifact, default_deploy_pipeline
    from repro.kernels.fastgrnn_cell import kernel as K, qstep
    cfg = load_json(config_file)
    ref = harness._load_module(os.path.join(BENCH, "references", "fastgrnn_q15.py"))
    art = default_deploy_pipeline(sparsity=cfg["compression"]["iht_sparsity"]).run(
        ModelArtifact.from_params(ref.make_params(cfg, np.random.SeedSequence(0))))
    sw = qstep.StepWeights.from_quantized(art.qp)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # compiled path
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = [sds((rows, K.LANES), jnp.float32)] * 2 + [sds((rows, 1), jnp.int32)] + [
        sds(c.shape, c.dtype) for c in K.step_constants(sw)]
    compiled = jax.jit(K.fastgrnn_step_call(sw, rows)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
