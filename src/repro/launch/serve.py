"""Serving launcher: batched decode server over a (restored) checkpoint.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
        [--reduced] [--ckpt DIR] [--requests 8] [--slots 4] \
        [--policy fifo|homed] [--pods PxD[xM]]

The configuration is served as published (its widths and depth, bf16),
with parameters from ``LM.init`` at `PARAM_SEED` unless ``--ckpt`` holds
a checkpoint.  ``--reduced`` serves the 4-layer float32 smoke variant of
the same family (`reduce_config`) — what the CPU tests and the CI gate run.

``--policy`` selects the serving scheduler (`repro.runtime.scheduler`):
``fifo`` is the arrival-order oracle, ``homed`` routes/batches/evicts by
each slot's cache home.  ``--pods PxD[xM]`` serves over an emulated-pod
mesh (run under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)
so the scheduler's inter-pod vs intra-pod relayout split is visible on a
laptop.  The per-home admission summary prints at exit either way — the
launcher demonstrates the scheduler without reading code.

``--trace PATH`` streams a structured JSONL trace of the whole run
(scheduler decisions, charges, pool pins, per-wave decode spans) —
validate its counter identities with ``python -m repro.launch.tracelog
PATH --validate`` or export it for Perfetto with ``--chrome``.
"""
from __future__ import annotations

import argparse

import numpy as np

import jax

from repro.checkpoint import latest_step, restore
from repro.configs import get_config, reduce_config
from repro.configs.base import ArchConfig, ShapeSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import LM
from repro.obs import Tracer, set_tracer
from repro.obs import metrics as obs_metrics
from repro.runtime.server import DecodeServer, Request


def parse_pods(spec: str):
    """``PxD`` or ``PxDxM`` -> (n_pods, n_data, n_model)."""
    parts = [int(p) for p in spec.lower().split("x")]
    if len(parts) == 2:
        parts.append(1)
    if len(parts) != 3 or any(p < 1 for p in parts):
        raise argparse.ArgumentTypeError(
            f"--pods wants PxD or PxDxM with positive ints, got {spec!r}")
    return tuple(parts)


#: decode cache length of every served slot
MAX_LEN = 96
#: seed of the parameters `LM.init` draws when no checkpoint is restored
PARAM_SEED = 0


def serve_config(arch: str, reduced: bool) -> ArchConfig:
    """The configuration as published, or its 4-layer smoke variant."""
    cfg = get_config(arch)
    return reduce_config(cfg, layers=4) if reduced else cfg


def synthetic_requests(cfg: ArchConfig, n: int, *, slots: int, max_new: int,
                       sessions: int):
    """The launcher's request stream: prompts of 2-8 tokens drawn from the
    vocabulary, ``max_new`` or half of it new tokens, ``sessions`` affinity
    keys, ``slots`` arrivals per step."""
    rng = np.random.RandomState(0)
    out = []
    for rid in range(n):
        plen = rng.randint(2, 9)
        out.append(Request(
            rid=rid,
            prompt=rng.randint(0, cfg.vocab_size, plen).astype(np.int32),
            max_new=int(rng.choice([max_new // 2 or 1, max_new])),
            session=f"s{rng.randint(sessions)}",
            t_arrive=float(rid // max(1, slots))))
    return out


def build_plan(pods, slots: int, max_len: int, cfg):
    """The serving MeshPlan: flat data mesh, or the emulated-pod mesh."""
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.partition import NULL_PLAN, make_plan
    n_dev = len(jax.devices())
    if pods is None:
        if n_dev == 1:
            return NULL_PLAN
        mesh = make_host_mesh(n_data=n_dev, n_model=1)
    else:
        p, d, m = pods
        mesh = make_host_mesh(n_pods=p, n_data=d, n_model=m)
    return make_plan(mesh, cfg, ShapeSpec("serve", max_len, slots, "decode"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the 4-layer float32 smoke variant of --arch "
                    "instead of the published configuration")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--policy", choices=("fifo", "homed"), default="fifo",
                    help="serving scheduler: arrival-order oracle vs "
                    "home-aware routing/batching/eviction")
    ap.add_argument("--pods", type=parse_pods, default=None, metavar="PxD[xM]",
                    help="serve over an emulated (pod, data, model) mesh")
    ap.add_argument("--sessions", type=int, default=4,
                    help="distinct affinity keys in the synthetic stream")
    ap.add_argument("--prompt-pad", type=int, default=16,
                    help="fixed prefill pad bucket (wave-composition-"
                    "independent numerics); 0 = per-wave max")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="stream a structured JSONL trace here (validate "
                    "with `python -m repro.launch.tracelog PATH --validate`)")
    ap.add_argument("--json", action="store_true",
                    help="also print the summary as one JSON line (same "
                    "dict the human report and bench rows render)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: 8 requests, 4 slots, max-new 4 "
                    "(the traced smoke the gate validates)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests, args.slots, args.max_new = 8, 4, 4

    enable_compile_cache()
    cfg = serve_config(args.arch, args.reduced)
    model = LM(cfg)
    params = model.init(jax.random.key(PARAM_SEED))
    if args.ckpt and latest_step(args.ckpt) is not None:
        params = restore(args.ckpt, latest_step(args.ckpt),
                         {"params": params})["params"]
    plan = build_plan(args.pods, args.slots, MAX_LEN, cfg)
    tracer = None
    if args.trace:
        tracer = Tracer(args.trace, tool="launch.serve", arch=args.arch,
                        policy=args.policy, slots=args.slots,
                        pods=args.pods, requests=args.requests)
        set_tracer(tracer)     # engine-level spans join the same stream
    srv = DecodeServer(cfg, params, batch_slots=args.slots, max_len=MAX_LEN,
                       plan=plan, scheduler=args.policy,
                       prompt_pad=args.prompt_pad or None, tracer=tracer)
    for req in synthetic_requests(cfg, args.requests, slots=args.slots,
                                  max_new=args.max_new,
                                  sessions=args.sessions):
        srv.submit(req)
    for r in sorted(srv.run(), key=lambda r: r.rid):
        print(f"req {r.rid} (session {r.session}, home {r.home}, "
              f"wait {r.wait:.0f}): -> {r.out}")
    # one code path: the trace's sched.summary event, the human report
    # and the optional JSON line all render the same canonical dict
    summary = srv.scheduler.emit_summary()
    print(obs_metrics.format_summary(summary))
    if args.json:
        import json
        print(json.dumps(summary))
    if tracer is not None:
        tracer.close()
        set_tracer(None)
        print(f"# trace: {args.trace} ({len(tracer.records())} records)")


if __name__ == "__main__":
    main()
