"""Train a ~100M-param LM for a few hundred steps with checkpoint/restart.

Uses the qwen1.5 architecture scaled to ~100M params, synthetic data, the
framework's AdamW + cosine schedule, async checkpointing every 50 steps,
and demonstrates restart-from-latest by resuming for 20 more steps.

    PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse
import dataclasses
import tempfile

from repro.checkpoint import Checkpointer
from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import synthetic_data
from repro.training import AdamWConfig, TrainConfig, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()
    enable_compile_cache()

    # ~100M params: qwen1.5 family topology, 12 x 512 width
    cfg = dataclasses.replace(
        get_config("qwen1.5-0.5b"), num_layers=12, d_model=512,
        num_heads=8, num_kv_heads=8, head_dim=64, d_ff=1408,
        vocab_size=32000, dtype="float32", remat="none")
    n = cfg.param_count()
    print(f"model: {n / 1e6:.1f}M params, {args.steps} steps, "
          f"batch {args.batch} x seq {args.seq}")

    tcfg = TrainConfig(optimizer=AdamWConfig(
        lr=6e-4, warmup_steps=20, total_steps=args.steps))
    with tempfile.TemporaryDirectory() as ckdir:
        ck = Checkpointer(ckdir)
        params, _, hist = train(
            cfg, synthetic_data(cfg, args.batch, args.seq),
            steps=args.steps, tcfg=tcfg, checkpointer=ck,
            checkpoint_every=50, log_every=20)
        for h in hist:
            print(f"  step {h['step']:4d}  loss {h['loss']:.4f}  "
                  f"gnorm {h['grad_norm']:.3f}  {h['wall']:.0f}s")
        print(f"checkpoints: {ck.available_steps()}")
        print("restart-from-latest for 20 more steps...")
        _, _, hist2 = train(
            cfg, synthetic_data(cfg, args.batch, args.seq),
            steps=args.steps + 20, tcfg=tcfg, checkpointer=ck,
            restore=True, log_every=10)
        print(f"  resumed at step {hist2[0]['step']}, "
              f"final loss {hist2[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
