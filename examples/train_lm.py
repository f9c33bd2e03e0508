"""End-to-end driver: train a ~100M-param qwen3-family LM for a few hundred
steps with the full production stack (data pipeline, AdamW+cosine, atomic
checkpoints, loss-spike guard, resume).

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300] [--arch qwen3-0.6b]
CPU note: uses a width-reduced config by default so a few hundred steps fit
in minutes; pass --full for the real config (TPU-scale).
"""
import argparse

from repro.configs import get_config, reduce_config
from repro.runtime.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--ckpt", default="runs/train_lm")
    args = ap.parse_args()
    cfg = get_config(args.arch)
    if not args.full:
        # ~100M-param qwen3-family config sized for the CPU harness
        cfg = cfg.replace(num_layers=12, d_model=640, num_heads=10,
                          num_kv_heads=2, head_dim=64, d_ff=2560,
                          vocab_size=2048,
                          dtype="float32", param_dtype="float32",
                          parallel=reduce_config(cfg).parallel)
    n = cfg.param_counts()["total"]
    print(f"arch={cfg.name} params={n/1e6:.1f}M", flush=True)
    t = TrainerConfig(steps=args.steps, global_batch=4, seq_len=64,
                      ckpt_dir=args.ckpt, ckpt_every=50, log_every=10,
                      lr=2e-3, warmup=20,
                      metrics_path="results/train_lm_metrics.json")
    res = Trainer(cfg, t).run()
    print(f"done: step={res['final_step']} loss={res['final_loss']:.4f}")


if __name__ == "__main__":
    main()
