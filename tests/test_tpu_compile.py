"""Compile the main-path kernels for a described TPU v5e chip, and check the
chip smoke test's fleet comparison on the CPU.

The v5e compiles need no chip: the TPU compiler is installed and compiles
for a topology that is only described.  The topology is described inside a
module fixture (never at import: one process at a time may load the TPU
library, and xdist workers import every test file), which skips where it
cannot be described.  ``jax.default_backend`` is steered to "tpu" inside
each compile so the kernels take their compiled (not interpreted) path.
"""
from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import REPO

S_FLEET = 16384          # one shard of the capacity geometry


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_path(monkeypatch):
    """Kernels choose interpret mode from the default backend; the compile
    target here is a TPU while the process runs on the CPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture(scope="module")
def art():
    from repro.deploy import goldens
    return goldens.build_reference_artifact(seed=0)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_fleet_step_kernel_compiles(art, one_chip, compiled_path):
    from repro.kernels.fastgrnn_cell import kernel as K, qstep
    sw = qstep.StepWeights.from_quantized(art.qp)
    args = [_sds((S_FLEET, K.LANES), jnp.float32, one_chip)] * 2 + [
        _sds((S_FLEET, 1), jnp.int32, one_chip)] + [
        _sds(c.shape, c.dtype, one_chip) for c in K.step_constants(sw)]
    text = jax.jit(K.fastgrnn_step_call(sw, S_FLEET)).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "%q15_step" in text          # the op's stable name in a trace


def test_jit_resident_step_compiles(art, one_chip, compiled_path):
    from repro.kernels.fastgrnn_cell.ops import Q15StreamStep
    k = Q15StreamStep(art.qp, backend="jit")
    compiled = k._resident_step.lower(
        _sds((S_FLEET, k.hidden_dim), jnp.float32, one_chip),
        _sds((S_FLEET, k.input_dim), jnp.float32, one_chip),
        _sds((S_FLEET,), jnp.bool_, one_chip)).compile()
    assert compiled.as_text()


def test_window_kernel_compiles(one_chip, compiled_path):
    from repro.kernels.fastgrnn_cell.kernel import LANES, fastgrnn_window
    T, B = 128, 1024
    f32 = lambda *shape: _sds(shape, jnp.float32, one_chip)
    text = fastgrnn_window.lower(
        f32(2, LANES), f32(2, LANES), f32(T, B, LANES), f32(LANES, LANES),
        f32(LANES, LANES), f32(4, LANES)).compile().as_text()
    assert "tpu_custom_call" in text
    assert "%q15_window" in text


def test_q15_matmul_compiles_at_lm_head_width(one_chip, compiled_path):
    """deepseek-7b's head: d_model 4096 x vocab 102400, int8 weights."""
    from repro.kernels.q15_matmul.kernel import q15_matmul_padded
    text = q15_matmul_padded.lower(
        _sds((128, 4096), jnp.float32, one_chip),
        _sds((4096, 102400), jnp.int8, one_chip),
        _sds((1,), jnp.float32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text


def test_mla_decode_kernel_compiles(one_chip, compiled_path):
    """Moonlight's absorbed decode attention at its benchmark size: 128
    slots x 8,192 positions, 16 heads, kv_lora_rank 512, rope 64."""
    from repro.kernels.mla_decode import mla_decode_attention
    bf = lambda *shape: _sds(shape, jnp.bfloat16, one_chip)
    i32 = lambda *shape: _sds(shape, jnp.int32, one_chip)
    fn = jax.jit(lambda ql, qp, c, p, li, pos: mla_decode_attention(
        ql, qp, c, p, li, pos, scale=192 ** -0.5))
    text = fn.lower(bf(128, 16, 512), bf(128, 16, 64), bf(5, 128, 8192, 512),
                    bf(5, 128, 4096, 128), i32(), i32(128)).compile().as_text()
    assert "tpu_custom_call" in text
    assert "%mla_decode" in text


def test_moonlight_decode_step_holds_one_cache(one_chip, compiled_path):
    """The slotted decode step at the benchmark's size, on the chip's
    row-major layouts: the donated latent cache is written in place (no
    second cache in temporaries) and the step fits beside the weights."""
    import dataclasses
    from jax.experimental.layout import Format, Layout
    import repro.configs as C
    from repro.models import transformer as T
    cfg = dataclasses.replace(C.get("moonlight-16b-a3b"), num_layers=5)
    fmt = lambda a: Format(Layout(tuple(range(a.ndim))), one_chip)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=fmt(a))
    params = jax.eval_shape(lambda k: T.init(cfg, k), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: T.init_slot_cache(cfg, 128, 8192))
    args = jax.tree.map(sds, (params, cache, jax.ShapeDtypeStruct((128, 1), jnp.int32),
                              jax.ShapeDtypeStruct((128,), jnp.bool_)))
    step = lambda p, c, t, a: T.decode_step_slotted(cfg, p, c, t, a, return_routes=True)
    outs = jax.tree.map(fmt, jax.eval_shape(step, *args))
    mem = jax.jit(step, donate_argnums=(1,), out_shardings=outs).lower(
        *args).compile().memory_analysis()
    cache_bytes = 5 * 128 * 8192 * 576 * 2
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < 1e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14e9


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("backend", ["pallas", "jit"])
def test_chip_smoke_fleet_phase_on_cpu(art, backend):
    """The smoke's fleet phase at 2 shards x 64 slots: its comparison with
    the exact backend passes, and it is not vacuous."""
    smoke = _chip_smoke()
    res = smoke.fleet_phase(art, backend=backend, shards=2, slots=64,
                            sample=48, seed=1)
    assert res["streams"] == 128 and res["sampled_streams"] == 48
    assert res["sampled_events"] == 96 and res["prediction_mismatches"] == 0
    assert res["ticks"] == 256
    assert 0.0 <= res["max_abs_dh"] <= res["h_tol"] < 0.1


def test_chip_smoke_four_chip_placement_on_cpu(art):
    """The ``--four-chips`` path on four of the forced host devices: one
    shard per device, h and constants on that device, exact comparison
    passing on both backends."""
    devs = jax.devices()[:4]
    assert len(devs) == 4
    res = _chip_smoke().fleet_per_chip(art, devs, slots=16, seed=2)
    assert [r["backend"] for r in res] == ["pallas", "jit"]
    assert all(r["streams"] == 64 and r["prediction_mismatches"] == 0
               for r in res)


def test_chip_smoke_refuses_the_cpu():
    with pytest.raises(SystemExit, match="platform 'cpu'"):
        _chip_smoke().require_tpu()
