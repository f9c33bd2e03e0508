"""Run one benchmark cell once and print its result.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell names a
configuration (``bench/configs/<file>.json``, whose ``system`` picks the
module in ``bench/systems/`` that runs it) and a traffic mix
(``bench/traffic/<mix>.json``); the cell's limits for the correctness
comparison are in ``bench/limits/<cell>.json``; each per-layer metric is
read by ``bench/metrics/<metric>.py``.

With ``--trace 0`` the last line of standard output holds the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from a
profiler window over the first bursts or calls of the measured window,
with the device's busy time and the breakdown of device ops and idle gaps.
Earlier lines give the set-up split and the compiles inside the window;
the last lines of standard error give each compared number with its limit.

The command exits non-zero, printing no result, when JAX finds no TPU or
fewer chips than the cell asks for, or when anything it needs is missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
#: the persistent compile cache: a fixed directory inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: where a traced run's profiler writes (deleted once read)
TRACE_DIR = ROOT / ".bench_trace"

COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


class Unavailable(RuntimeError):
    """The run cannot be made here: no chip, too few chips, missing files."""


def load_spec() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise Unavailable(f"{path} not found")
    return json.loads(path.read_text())


def cell_of(spec: dict, name: str):
    """(cell, config entry, config file dict, mix dict, limits dict)."""
    from bench import traffic
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Unavailable(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    limits = json.loads((HERE / "limits" / f"{name}.json").read_text())
    return cell, entry, config, traffic.load(cell["traffic"]), limits


class Run:
    """One run of one cell: its inputs, its clocks and what it measured.

    The cell's system module (`bench.systems.*.run`) fills it: set-up phases in
    ``setup``, end-to-end metrics through `metric`, compared numbers
    through `compare`, and in ``data`` what the per-layer readers need.
    """

    def __init__(self, cell: dict, config: dict, mix: dict, limits: dict,
                 seed: int, seconds: float, trace: bool, devices,
                 t_start: float = T_START):
        self.cell, self.config, self.mix = cell, config, mix
        self.limits = limits
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = list(devices)
        self.t_start = t_start
        self.setup: dict = {}
        self.info: dict = {}
        self.data: dict = {}
        self.metrics: dict = {}
        self.checks: dict = {}
        self.attempted = self.failed = 0
        self.memory_peak = None
        self.host_spans: list = []
        self.capture = None
        #: also read the control (the reference in int8) on the same sample
        self.control = False
        self.t0 = None
        self.phase = "setup"
        self.compiles = {"setup": {}, "window": {}, "after": {}}

    # -- clocks --------------------------------------------------------------
    def open_window(self) -> None:
        self.t0 = time.perf_counter()
        self.phase = "window"
        self.setup_s = self.t0 - self.t_start

    def close_window(self) -> None:
        self.phase = "after"

    def on_compile_event(self, event: str, secs: float, **_) -> None:
        kind = COMPILE_EVENTS.get(event)
        if kind:
            c = self.compiles[self.phase]
            c[kind] = c.get(kind, 0) + 1
            c[kind + "_s"] = c.get(kind + "_s", 0.0) + secs

    # -- tracing -------------------------------------------------------------
    def capture_begin(self) -> None:
        from bench.trace import Capture
        self.capture = Capture(str(TRACE_DIR))
        self.capture.start()

    def capture_end(self) -> None:
        self.capture.stop()

    def annotate(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    # -- results -------------------------------------------------------------
    def metric(self, name: str, value: float, unit: str) -> None:
        self.metrics[name] = {"value": float(value), "unit": unit}

    def compare(self, name: str, value: float) -> None:
        self.checks[name] = {"value": float(value),
                             "limit": float(self.limits[name])}

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            c["value"] <= c["limit"] for c in self.checks.values())

    def read_memory(self) -> None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        self.memory_peak = max((p for p in peaks if p is not None),
                               default=None)


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def per_layer(spec: dict, run: Run) -> dict:
    """Every per-layer metric of this cell that its reader finds."""
    out = {}
    name = run.cell["name"]
    for m in spec["per_layer"]:
        if name not in m.get("workloads", [name]):
            continue
        value = load_reader(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def device_info(run: Run) -> dict:
    d = run.devices[0]
    out = {"platform": d.platform, "kind": d.device_kind,
           "count": len(run.devices), "memory_peak_bytes": run.memory_peak}
    if run.trace and run.capture is not None and run.capture.trace:
        from bench.trace import busy_ns
        lo, hi = run.capture.bounds_ns
        ids = [d.id for d in run.devices]
        out["busy_s"] = sum(busy_ns(run.capture.trace, i, lo, hi)
                            for i in ids) / len(ids) / 1e9
        out["window_s"] = (hi - lo) / 1e9
    return out


def execute(spec: dict, run: Run, system) -> dict:
    """Drive the cell through its system and assemble the result line."""
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(
        run.on_compile_event)
    system.run(run)
    if run.trace:
        from bench.trace import breakdown
        run.capture.read(run.host_spans)
        run.data["trace"] = run.capture.trace
        run.data["trace_bounds"] = run.capture.bounds_ns
        metrics = per_layer(spec, run)
    else:
        metrics = dict(run.metrics)
        metrics["setup_s"] = {"value": run.setup_s, "unit": "s"}
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics,
              "device": device_info(run)}
    if run.trace:
        lo, hi = run.capture.bounds_ns
        result["breakdown"] = breakdown(run.capture.trace,
                                        [d.id for d in run.devices], lo, hi)
    result["checks"] = run.checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_spec()
        cell, entry, config, mix, limits = cell_of(spec, args.workload)
        if not (ROOT / "src" / "repro").is_dir():
            raise Unavailable(f"{ROOT / 'src' / 'repro'} not found: the "
                              f"system under test is missing")
        sys.path.insert(0, str(ROOT / "src"))
        import jax
        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise Unavailable(f"no TPU: JAX sees {len(devices)} "
                              f"{devices[0].platform} device(s)")
        if len(devices) < cell["chips"]:
            raise Unavailable(f"{args.workload} needs {cell['chips']} chips, "
                              f"JAX sees {len(devices)}")
        from bench.peaks import peak
        peak(devices[0].device_kind)
    except (Unavailable, OSError, KeyError, RuntimeError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    system = importlib.import_module(f"bench.systems.{config['system']}")
    run = Run(cell, config, mix, limits, args.seed, args.seconds,
              bool(args.trace), devices[:cell["chips"]])
    result = execute(spec, run, system)
    setup = dict(run.setup, total_s=run.setup_s,
                 compile=run.compiles["setup"])
    print("# setup " + json.dumps(setup), flush=True)
    print("# window " + json.dumps(dict(run.info,
                                        compiles=run.compiles["window"])),
          flush=True)
    for name, c in run.checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
