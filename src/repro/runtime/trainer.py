"""Fault-tolerant training loop.

Failure model (designed for 1000+ nodes, exercised here in-process):
  * crash/preemption  — atomic checkpoints every K steps; SIGTERM/SIGINT
    trigger a final save; restart resumes from the latest complete step and,
    because the data pipeline is a pure function of the step index, replays
    the exact same batches (bitwise-deterministic resume, tested).
  * bad steps         — non-finite loss or exploding grad-norm aborts the
    step, restores the last checkpoint in-process and skips the offending
    batch (loss-spike guard).
  * stragglers        — per-step wall-time watchdog: steps slower than
    `straggler_factor` x running median are logged and counted; the
    Supervisor (ft.py) escalates to a restart after `max_slow_steps`
    (on a real pod: re-scheduling the slow host).
"""
from __future__ import annotations

import json
import os
import signal
import statistics
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import numpy as np

import jax

from repro.checkpoint import CheckpointManager, latest_step, restore
from repro.configs.base import ArchConfig
from repro.data import make_batch_iterator
from repro.models.model import LM
from repro.models.steps import init_opt_state, make_train_step
from repro.optim import AdamW, cosine_schedule
from repro.sharding.partition import MeshPlan, NULL_PLAN


@dataclass
class TrainerConfig:
    steps: int = 100
    global_batch: int = 8
    seq_len: int = 64
    ckpt_dir: str = "runs/ckpt"            # inside the checkout (gitignored)
    ckpt_every: int = 20
    log_every: int = 10
    seed: int = 0
    lr: float = 3e-4
    warmup: int = 10
    straggler_factor: float = 3.0
    schedule_total: Optional[int] = None   # decouple LR horizon from loop end
    grad_spike: float = 1e4
    metrics_path: Optional[str] = None


class Trainer:
    def __init__(self, cfg: ArchConfig, tcfg: TrainerConfig,
                 plan: MeshPlan = NULL_PLAN, mesh=None):
        self.cfg, self.tcfg, self.plan, self.mesh = cfg, tcfg, plan, mesh
        self.model = LM(cfg)
        total = tcfg.schedule_total or tcfg.steps
        self.opt = AdamW(lr=cosine_schedule(tcfg.lr, tcfg.warmup, total))
        self.step_fn = jax.jit(make_train_step(self.model, cfg, plan, self.opt),
                               donate_argnums=(0, 1))
        self.ckpt = CheckpointManager(tcfg.ckpt_dir, tcfg.ckpt_every)
        self.slow_steps = 0
        self._stop = False

    # ------------------------------------------------------------- lifecycle
    def _install_signal_handlers(self):
        def handler(signum, frame):
            self._stop = True
        for sig in (signal.SIGTERM, signal.SIGINT):
            signal.signal(sig, handler)

    def init_or_restore(self):
        params = self.model.init(jax.random.key(self.tcfg.seed))
        opt_state = init_opt_state(self.cfg, self.opt, params)
        start = 0
        last = latest_step(self.tcfg.ckpt_dir)
        if last is not None:
            state = restore(self.tcfg.ckpt_dir, last,
                            {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            start = last
        return params, opt_state, start

    # ------------------------------------------------------------------ run
    def run(self) -> dict:
        self._install_signal_handlers()
        t = self.tcfg
        params, opt_state, start = self.init_or_restore()
        data = make_batch_iterator(self.cfg, t.global_batch, t.seq_len,
                                   t.seed, self.mesh, start_step=start)
        durations, metrics_log = [], []
        step = start
        for step in range(start, t.steps):
            if self._stop:
                break
            batch = next(data)
            t0 = time.time()
            new_params, new_opt, m = self.step_fn(params, opt_state, batch)
            loss = float(m["loss"])
            gnorm = float(m["grad_norm"])
            dt = time.time() - t0
            # ---- loss-spike / NaN guard ----
            if not np.isfinite(loss) or gnorm > t.grad_spike:
                last = latest_step(t.ckpt_dir)
                if last is not None:
                    st = restore(t.ckpt_dir, last,
                                 {"params": params, "opt": opt_state})
                    params, opt_state = st["params"], st["opt"]
                continue  # skip the offending batch, keep going
            params, opt_state = new_params, new_opt
            # ---- straggler watchdog ----
            durations.append(dt)
            med = statistics.median(durations[-50:])
            if len(durations) > 5 and dt > t.straggler_factor * med:
                self.slow_steps += 1
            if (step + 1) % t.log_every == 0 or step + 1 == t.steps:
                rec = {"step": step + 1, "loss": loss, "grad_norm": gnorm,
                       "step_time_s": round(dt, 4),
                       "slow_steps": self.slow_steps}
                metrics_log.append(rec)
                print(json.dumps(rec), flush=True)
            self.ckpt.maybe_save(step + 1,
                                 {"params": params, "opt": opt_state})
        self.ckpt.maybe_save(step + 1, {"params": params, "opt": opt_state},
                             force=True)
        self.ckpt.wait()
        if t.metrics_path:
            with open(t.metrics_path, "w") as f:
                json.dump(metrics_log, f, indent=1)
        return {"final_step": step + 1,
                "final_loss": metrics_log[-1]["loss"] if metrics_log else None,
                "params": params}
