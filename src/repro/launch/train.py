"""Multi-pod training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --steps 100 --batch 8 --seq 256 --reduced --ckpt-dir /tmp/ckpt

On real hardware this runs under `jax.distributed.initialize()` with one
process per host; the mesh comes from make_production_mesh and params /
optimizer states take the shardings from models.sharding.  On this CPU
container, --reduced trains the smoke-scale config end-to-end (the same
code path, a 1-device mesh).

Fault tolerance: async checkpoints every --ckpt-every steps; on restart
the loop resumes from the newest complete checkpoint (restart-from-latest);
pre-emption is survivable at the cost of one checkpoint interval.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.configs import get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.training import TrainConfig, train
from repro.training.optimizer import AdamWConfig


def synthetic_data(cfg, batch: int, seq: int, seed: int = 0):
    step = 0
    while True:
        key = jax.random.PRNGKey(seed + step)
        toks = jax.random.randint(key, (batch, seq), 4, cfg.vocab_size)
        batch_d = {"tokens": toks, "labels": toks}
        if cfg.vlm.enabled:
            batch_d["vision_embeds"] = jax.random.normal(
                key, (batch, cfg.vlm.vision_tokens, cfg.vlm.vision_dim),
                jnp.dtype(cfg.dtype))
        if cfg.encdec.enabled:
            batch_d["audio_frames"] = jax.random.normal(
                key, (batch, cfg.encdec.source_positions, cfg.d_model),
                jnp.dtype(cfg.dtype))
        yield batch_d
        step += 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    tcfg = TrainConfig(optimizer=AdamWConfig(lr=args.lr,
                                             total_steps=args.steps),
                       grad_accum=args.grad_accum)
    ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    params, _, hist = train(cfg, synthetic_data(cfg, args.batch, args.seq),
                            steps=args.steps, tcfg=tcfg, checkpointer=ck,
                            checkpoint_every=args.ckpt_every,
                            restore=args.resume)
    for h in hist:
        print(f"step {h['step']:5d}  loss {h['loss']:.4f}  "
              f"gnorm {h['grad_norm']:.3f}  wall {h['wall']:.1f}s")


if __name__ == "__main__":
    main()
