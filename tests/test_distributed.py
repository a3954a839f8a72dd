"""Multi-device semantics on an 8-fake-device CPU mesh.

Two tiers:

* **In-process smokes** (below, not slow) — conftest.py forces
  ``--xla_force_host_platform_device_count=8`` before jax initializes, so
  the pytest process itself has 8 host devices: device enumeration,
  per-device placement of jitted compute (the substrate the fleet's
  per-shard device placement rides — see tests/test_fleet.py), and a
  pmap collective.
* **Explicit-sharding suite** (subprocess, slow) — verifies:
  sharded train step == single-device step numerically; vocab-parallel
  CE == plain CE; int8/bf16 compressed psum + error feedback; GPipe
  pipeline == sequential stages; checkpoint resharding across mesh
  shapes.  Drives the explicit-sharding APIs (jax.make_mesh with
  axis_types, jax.sharding.AxisType, top-level jax.shard_map).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess


# ---------------------------------------------------------------------------
# In-process multi-device smokes (not slow)
# ---------------------------------------------------------------------------

def test_host_devices_forced_in_process():
    """conftest.py's XLA_FLAGS setting took effect: the tier-1 process
    itself has >= 8 host devices, so multi-device paths (fleet shard
    placement included) are exercised without a subprocess."""
    assert jax.device_count() >= 8


def test_per_device_compute_placement():
    """device_put pins data AND the jitted computation that consumes it
    to each fake host device — the mechanism fleet shard placement uses."""
    results = []
    for i, dev in enumerate(jax.devices()[:4]):
        x = jax.device_put(jnp.arange(4.0) + i, dev)
        y = jax.jit(lambda v: (v * 2.0).sum())(x)
        assert y.devices() == {dev}
        results.append(float(y))
    assert results == [12.0, 20.0, 28.0, 36.0]


def test_pmap_collective_across_host_devices():
    n = jax.device_count()
    x = jnp.arange(n, dtype=jnp.float32)
    out = jax.pmap(lambda v: jax.lax.psum(v, "i"), axis_name="i")(x)
    np.testing.assert_allclose(np.asarray(out),
                               np.full(n, float(x.sum())))


# ---------------------------------------------------------------------------
# Explicit-sharding suite (subprocess; needs modern jax APIs)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
import repro.configs as C
from repro.models import registry
from repro.launch import sharding as sh
from repro.launch.mesh import make_host_mesh
from repro.train.optimizer import AdamConfig

cfg = C.reduced(C.get("deepseek-7b"), compute_dtype="float32", param_dtype="float32")
acfg = AdamConfig(state_dtype="float32")
params = registry.init(cfg, jax.random.PRNGKey(0))
import repro.train.optimizer as opt
opt_state = opt.init(params, acfg)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)), jnp.int32)}

# single device reference
step1 = registry.make_train_step(cfg, acfg)
p1, o1, m1 = jax.jit(step1)(params, opt_state, batch)

# 4x2 mesh sharded
mesh = make_host_mesh(data=4, model=2)
pspecs = sh.param_pspecs(params, mesh)
n_p = sh.named(mesh, pspecs)
n_o = sh.named(mesh, sh.opt_pspecs(opt_state, pspecs))
bsp = {k: jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data", None)) for k in batch}
step2 = registry.make_train_step(cfg, acfg, mesh=mesh)
jf = jax.jit(step2, in_shardings=(n_p, n_o, bsp), out_shardings=(n_p, n_o, None))
p2, o2, m2 = jf(jax.device_put(params, n_p), jax.device_put(opt_state, n_o),
                jax.device_put(batch, bsp))
d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
print("param delta", d)
print("loss delta", abs(float(m1["loss"]) - float(m2["loss"])))
assert d < 2e-4, d
assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
print("OK")
""", devices=8)
    assert "OK" in out


@pytest.mark.slow
def test_compressed_psum_error_feedback():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.train.grad_compression import compressed_psum
mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
g = jnp.asarray(np.random.default_rng(0).normal(size=(8, 64)).astype(np.float32))

def f(g):
    red, err = compressed_psum(g, "data", bits=8, error=None)
    return red, err
red, err = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=(P("data"), P("data")), check_vma=False))(g)
true_mean = g.mean(0)
red_np = np.asarray(red)
# every shard got the same mean; int8 error bounded by scale
for i in range(8):
    assert np.allclose(red_np[i], red_np[0])
q_err = np.abs(red_np[0] - np.asarray(true_mean)).max()
print("int8 psum err", q_err)
assert q_err < np.abs(g).max() / 127 + 1e-6
# error feedback: residual equals what was lost
total = np.asarray(err).sum(0) / 8 + red_np[0] - true_mean
assert np.abs(total).max() < 1e-5
print("OK")
""", devices=8)
    assert "OK" in out


@pytest.mark.slow
def test_vocab_parallel_ce_matches_plain():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.models import losses, layers as L
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
rng = np.random.default_rng(0)
B, S, D, V = 4, 8, 16, 32
x = jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32)
w = jnp.asarray(rng.normal(size=(V, D)), jnp.float32)   # tied table
y = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
plain = losses.vocab_parallel_ce(x, w, y, mesh=None, tied=True,
                                 z_loss=1e-4, compute_dtype=jnp.float32)
par = jax.jit(lambda x, w, y: losses.vocab_parallel_ce(
    x, w, y, mesh=mesh, tied=True, z_loss=1e-4,
    compute_dtype=jnp.float32))(x, w, y)
print("ce delta", abs(float(plain) - float(par)))
assert abs(float(plain) - float(par)) < 1e-4
# gradients too
g1 = jax.grad(lambda w: losses.vocab_parallel_ce(x, w, y, mesh=None, tied=True, z_loss=0.0, compute_dtype=jnp.float32))(w)
g2 = jax.jit(jax.grad(lambda w: losses.vocab_parallel_ce(x, w, y, mesh=mesh, tied=True, z_loss=0.0, compute_dtype=jnp.float32)))(w)
gd = float(jnp.max(jnp.abs(g1 - g2)))
print("grad delta", gd)
assert gd < 1e-4
print("OK")
""", devices=8)
    assert "OK" in out


@pytest.mark.slow
def test_pipeline_matches_sequential():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.train.pipeline import pipeline_apply
mesh = jax.make_mesh((4,), ("stage",), axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(0)
P_, M, b, d = 4, 6, 3, 8
Ws = jnp.asarray(rng.normal(size=(P_, d, d)) * 0.3, jnp.float32)
x = jnp.asarray(rng.normal(size=(M, b, d)), jnp.float32)

def stage_fn(w, h):
    return jnp.tanh(h @ w)

out_p = jax.jit(lambda Ws, x: pipeline_apply(stage_fn, Ws, x, mesh=mesh))(Ws, x)
ref = x
for s in range(P_):
    ref = jnp.tanh(ref @ Ws[s])
d_ = float(jnp.max(jnp.abs(out_p - ref)))
print("pipeline delta", d_)
assert d_ < 1e-5
print("OK")
""", devices=4)
    assert "OK" in out


@pytest.mark.slow
def test_checkpoint_elastic_resharding():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.train import checkpoint as ckpt
tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
d = tempfile.mkdtemp()
# save from a 8x1 'mesh' (full arrays — mesh-agnostic by design)
mesh_a = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
tree_a = jax.device_put(tree, {"w": NamedSharding(mesh_a, P("data", None))})
ckpt.save(d, 1, tree_a)
# restore onto a DIFFERENT mesh shape (elastic resize: 8 -> 4 devices x 2 model)
mesh_b = jax.make_mesh((2, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,)*2)
sh = {"w": NamedSharding(mesh_b, P("data", "model"))}
out = ckpt.restore(d, 1, tree, shardings=sh)
assert out["w"].sharding == sh["w"]
np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
print("OK")
""", devices=8)
    assert "OK" in out


@pytest.mark.slow
def test_sp_dense_and_splitkv_match_reference():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
import repro.configs as C
from repro.models import transformer as T
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,)*2)

# Megatron-SP dense (both KV layouts)
for kv in (4, 2):
    cfg = C.reduced(C.get("deepseek-7b"), compute_dtype="float32",
                    param_dtype="float32", num_heads=4, num_kv_heads=kv)
    params = T.init(cfg, jax.random.PRNGKey(0))
    toks = np.random.default_rng(1).integers(0, cfg.vocab_size, (4, 32))
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
    ref, _ = T.train_loss(cfg, params, batch)
    sp, _ = jax.jit(lambda p, b: T.train_loss(cfg, p, b, mesh=mesh,
                                              seq_parallel=True)[0:2])(params, batch)
    assert abs(float(ref) - float(sp)) < 1e-4, (kv, float(ref), float(sp))

# flash-decoding split-KV
cfg = C.reduced(C.get("minitron-4b"), compute_dtype="float32",
                param_dtype="float32", num_heads=4, num_kv_heads=1)
params = T.init(cfg, jax.random.PRNGKey(0))
toks = np.random.default_rng(2).integers(0, cfg.vocab_size, (2, 8))
full, _, _ = T.forward(cfg, params, {"tokens": jnp.asarray(toks)})
cache = T.init_cache(cfg, 2, 12, dtype=jnp.float32)
step = jax.jit(lambda p, c, t: T.decode_step(cfg, p, c, t, mesh=mesh, splitkv=True))
for t in range(8):
    lg, cache = step(params, cache, jnp.asarray(toks[:, t:t+1]))
    assert float(jnp.max(jnp.abs(lg[:, 0] - full[:, t]))) < 1e-3, t
print("OK")
""", devices=8)
    assert "OK" in out
