"""Production training launcher: mesh + plan + fault-tolerant Trainer.

On a real pod:
    python -m repro.launch.train --arch glm4-9b --production [--multipod]
On this host (reduced config, real end-to-end loop):
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --steps 30

Under `--supervised` the loop runs beneath the heartbeat Supervisor:
crashes/hangs relaunch from the latest atomic checkpoint.
"""
from __future__ import annotations

import argparse
import sys

from repro.configs import get_config, reduce_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.configs.base import SHAPES
from repro.runtime.trainer import Trainer, TrainerConfig
from repro.sharding.partition import NULL_PLAN, make_plan


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--ckpt", default="runs/launch_train")
    ap.add_argument("--production", action="store_true",
                    help="full config on the production mesh (TPU pods)")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--supervised", action="store_true")
    args = ap.parse_args()

    if args.supervised:
        from repro.runtime.ft import Supervisor
        cmd = [sys.executable, "-m", "repro.launch.train"] + [
            a for a in sys.argv[1:] if a != "--supervised"]
        out = Supervisor(cmd=cmd, max_restarts=3).run()
        print("\n".join(out["stdout"][-5:]))
        sys.exit(0 if out["ok"] else 1)

    enable_compile_cache()
    if args.production:
        cfg = get_config(args.arch)
        mesh = make_production_mesh(multi_pod=args.multipod)
        plan = make_plan(mesh, cfg, SHAPES["train_4k"])
    else:
        cfg = reduce_config(get_config(args.arch))
        mesh, plan = None, NULL_PLAN
    t = TrainerConfig(steps=args.steps, global_batch=args.global_batch,
                      seq_len=args.seq_len, ckpt_dir=args.ckpt,
                      ckpt_every=max(args.steps // 5, 1), log_every=10)
    res = Trainer(cfg, t, plan=plan, mesh=mesh).run()
    print(f"done: step={res['final_step']} loss={res['final_loss']}")


if __name__ == "__main__":
    main()
