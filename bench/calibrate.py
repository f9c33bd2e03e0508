"""Readings for the limits of a cell's correctness comparison, on the chip.

    python3 -m bench.calibrate --workload <name> --seeds 1,2,3 --seconds 8

Runs the cell once per seed in one process, each with a short window at the
cell's own load, and prints, per seed, every compared number of the program
and of the control: for a served model the control is the plain reference
computed in int8 (weights per output channel, activations per token), read
on the same sample of served requests; for the sort it is numpy's sort on
the top 16 bits of each key only, put in the program's place.  The
benchmark's own runs never run the control.  One JSON line per seed; the
last line gathers the largest program reading and the smallest control
reading of each number.
"""
from __future__ import annotations

import argparse
import json
import sys

from bench import run as bench_run


def control_sort(x):
    """The reference one step below the configuration's guarantee: a radix
    sort that stops after its two high-order byte passes, so keys equal in
    their top 16 bits keep their input order."""
    import jax
    import numpy as np
    xs = np.asarray(x)
    return jax.device_put(xs[np.argsort(xs >> 16, kind="stable")], x.sharding)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    spec = bench_run.load_spec()
    cell, _, config, mix, limits = bench_run.cell_of(spec, args.workload)
    sys.path.insert(0, str(bench_run.ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("calibrate: needs the cell's chips", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(bench_run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import importlib
    system = importlib.import_module(f"bench.systems.{config['system']}")
    runs = [(int(s), False) for s in args.seeds.split(",") if s]
    runs += [(int(s), True) for s in args.control_seeds.split(",") if s]
    program, control = {}, {}
    for seed, is_control in runs:
        run = bench_run.Run(cell, config, mix, limits, seed, args.seconds,
                            False, devices[:cell["chips"]])
        if config["system"] == "serve":
            run.control = True
            system.run(run)
        else:
            system.run(run, sort_fn=control_sort if is_control else None)
        side = control if is_control else program
        for k, c in run.checks.items():
            side.setdefault(k, []).append(c["value"])
        if "control_logit_gap" in run.info:
            control.setdefault("logit_gap", []).append(
                run.info["control_logit_gap"])
        print(json.dumps({"seed": seed, "control": is_control,
                          "checks": run.checks, "info": run.info,
                          "metrics": run.metrics}), flush=True)
    print(json.dumps({
        "workload": args.workload,
        "program_max": {k: max(v) for k, v in program.items()},
        "control_min": {k: min(v) for k, v in control.items()},
        "program": program, "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
