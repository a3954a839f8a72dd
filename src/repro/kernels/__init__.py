# Pallas TPU kernels for the compute hot-spots of the L-S-Q deployment
# path (paper Sec. III-E / V-G, adapted MCU->TPU per DESIGN.md Sec. 2):
#   lut_act       — 256-entry sigma/tanh LUT activations, VMEM-resident table
#   fastgrnn_cell — FastGRNN full-window scan and batched single step
#   q15_matmul    — dequant-fused int16/int8 x bf16 blocked matmul (serving)
#   ssd_scan      — Mamba2 chunked SSD scan (state carried across grid steps)
# Each package: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
# wrapper with shape plumbing), ref.py (pure-jnp oracle).  Kernels compile
# with Mosaic on a TPU and run in the Pallas interpreter everywhere else.
import os

import jax

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def interpret_mode() -> bool:
    """Whether ``pallas_call`` runs interpreted: exactly when the default
    backend is not a TPU, so no kernel ever runs interpreted on a chip."""
    return jax.default_backend() != "tpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point (a
    script, example or benchmark; never on import, never in tests) and
    return its directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is
    left to JAX; otherwise the cache lives at ``<repo>/.jax_cache``, a
    fixed path, since the path is part of the cache key."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_REPO, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


from . import lut_act, fastgrnn_cell, q15_matmul, ssd_scan  # noqa: E402,F401
