"""LM-scale demo: train a reduced assigned architecture with the full
production trainer (checkpointing, straggler monitor, deterministic
seekable data, optional IHT sparsity) on CPU.

    PYTHONPATH=src python examples/lm_train_demo.py --arch qwen2-1.5b \
        --steps 200

Use --arch with any of the 10 assigned ids; the config is reduced to a
CPU-sized model of the same family (the full configs are exercised via
the 512-chip dry-run: python -m repro.launch.dryrun).
"""
import argparse

import jax
import jax.numpy as jnp

import repro.configs as C
from repro.data import tokens
from repro.models import registry
from repro.train.optimizer import AdamConfig
from repro.train.trainer import Trainer, TrainerConfig
from repro.kernels import enable_compile_cache

parser = argparse.ArgumentParser()
parser.add_argument("--arch", default="qwen2-1.5b", choices=list(C.ARCHS))
parser.add_argument("--steps", type=int, default=200)
parser.add_argument("--batch", type=int, default=8)
parser.add_argument("--seq", type=int, default=64)
parser.add_argument("--ckpt-dir", default="/tmp/repro_lm_demo")
args = parser.parse_args()
enable_compile_cache()

cfg = C.reduced(C.get(args.arch), d_model=128, num_layers=4,
                num_heads=4 if C.get(args.arch).num_heads else 0)
print(f"arch={cfg.name} family={cfg.family} reduced to "
      f"{cfg.num_layers}L x d{cfg.d_model}")

tcfg = tokens.TokenStreamConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                                global_batch=args.batch)
acfg = AdamConfig(lr=1e-3, warmup_steps=20)
step = jax.jit(registry.make_train_step(cfg, acfg), donate_argnums=(0, 1))


def batch_fn(s):
    b = tokens.lm_batch(tcfg, s)
    out = {k: jnp.asarray(v) for k, v in b.items()}
    if cfg.family == "vlm":
        out["patch_embeds"] = jnp.zeros((args.batch, cfg.num_patches,
                                         cfg.d_model), jnp.float32)
    if cfg.family == "audio":
        out["frames"] = jnp.asarray(
            jax.random.normal(jax.random.PRNGKey(s),
                              (args.batch, args.seq, cfg.d_model)))
    return out


trainer = Trainer(
    TrainerConfig(total_steps=args.steps, checkpoint_every=50,
                  checkpoint_dir=args.ckpt_dir, log_every=20, adam=acfg),
    init_params_fn=lambda: registry.init(cfg, jax.random.PRNGKey(0)),
    step_fn=step, batch_fn=batch_fn,
    on_straggler=lambda s, dt, v: print(f"[straggler] step {s}: {dt:.2f}s"))

hist = trainer.run()
losses = [h["loss"] for h in hist if "loss" in h]
print(f"step 0 loss {losses[0]:.3f} -> step {len(losses)-1} "
      f"loss {losses[-1]:.3f}")
print(f"checkpoints in {args.ckpt_dir} (restart this script to resume)")
