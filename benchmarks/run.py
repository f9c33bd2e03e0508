"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and writes machine-readable
``BENCH_sort.json`` / ``BENCH_microbench.json`` / ``BENCH_engine.json`` /
``BENCH_kernels.json`` (one record per case: name, n, median wall-clock in
us, backend, derived) so the perf trajectory is tracked across PRs
(``benchmarks/compare.py`` diffs two runs).  Distributed benchmarks run in
subprocesses with 8 placeholder host devices (the main process keeps the
single real device, mirroring the dry-run discipline); the LOCAL benches
run in-process with their stdout captured so their CSV reaches
`parse_records` too.

``--smoke`` runs every entry point at toy sizes on 2 placeholder devices —
fast enough for the test suite, so the benchmark surface can't silently rot.

``--check`` runs the homecheck static analyzer (rules R1-R11, see
`repro.analysis`) over each bench family *before* timing it and stamps the
verdict (``"homecheck": "clean"`` / ``"findings:N"`` / ``"failed"``) into
every record the family contributes to BENCH_*.json; the serving families
additionally get the R9 scheduler certificate as ``"schedcheck":
"certified"`` / ``"findings:N"``.  ``compare.py`` then fails a PR whose
previously clean (or certified) case gained findings.
``benchmarks/ci_gate.sh`` additionally stamps a ``"ci_gate"`` verdict
(fast tests + the full analyzer sweep) gated the same way.

``bench_roofline`` reads the committed dry-run artifacts under
``results/dryrun`` — its rows are analytic (compile-only), so its
``BENCH_roofline.json`` baseline is deterministic across machines.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import re
import subprocess
import sys

from benchmarks.common import host_device_env, run_with_devices

# (key, module, description): `key` names the run (a module may appear more
# than once with different argv — the pods grid reuses bench_sort_cases)
MULTIDEV = [
    ("bench_microbench", "bench_microbench",
     "paper Fig 1: localised vs non-localised microbench"),
    ("bench_sort_cases", "bench_sort_cases",
     "paper Table 1 + Fig 2: merge sort cases 1-8"),
    ("bench_sort_pods", "bench_sort_cases",
     "hierarchical multi-pod engine: inter/intra-pod exchange bytes (Fig 9)"),
    ("bench_sort_sizes", "bench_sort_sizes", "paper Fig 3: input-size sweep"),
    ("bench_striping", "bench_striping", "paper Fig 4: striping analogue"),
    ("bench_serve", "bench_serve",
     "home-aware serving scheduler: fifo vs homed, flat mesh"),
    ("bench_serve_pods", "bench_serve",
     "home-aware serving scheduler on the (2,2,2) emulated-pod mesh"),
]
LOCAL = [
    ("bench_kernels", "Pallas kernel localisation (Fig 1, TPU-native)"),
    ("bench_roofline", "dry-run roofline table (results/dryrun)"),
]

# per-run argv for the full harness (8 devices)
FULL_ARGS = {
    "bench_sort_pods": ["--pods", "2x4", "--logn", "18"],
    "bench_serve_pods": ["--pods", "2x2x2"],
}

# per-run argv for --smoke: toy sizes, a case subset, short sweeps;
# the pods grid runs on the 2 smoke devices as a (2, 1, 1) emulated mesh
SMOKE_ARGS = {
    "bench_microbench": ["--n", "4096", "--reps", "2"],
    "bench_sort_cases": ["--logn", "12", "--cases", "3,8"],
    "bench_sort_pods": ["--pods", "2x1", "--logn", "10"],
    "bench_sort_sizes": ["--logns", "12"],
    "bench_striping": ["--logn", "14", "--logb", "6"],
    "bench_serve": ["--slots", "4", "--requests", "10", "--max-len", "32",
                    "--short-new", "2", "--long-new", "6", "--sessions", "6",
                    "--reps", "1"],
    "bench_serve_pods": ["--pods", "2x1", "--slots", "4", "--requests", "16",
                         "--max-len", "32", "--short-new", "2",
                         "--long-new", "6", "--sessions", "6", "--reps", "1"],
    "bench_kernels": ["--only", "local,merge", "--chunks", "2",
                      "--logcs", "8"],
}

# --check: homecheck CLI argv per bench family ("{D}" = device count).
# Each entry lowers the family's workload/policy surface and runs rules
# R1-R11 (repro.analysis) on the partitioned HLO + jaxpr + exchange network
# — nothing times until the home contract holds.  Families with no
# collective surface of their own (striping/roofline are local-copy /
# compile-only sweeps) map to an empty list.
CHECK_ARGS = {
    "bench_microbench": [["--workload", "microbench", "--pods", "1x{D}",
                          "--policy", "all"]],
    "bench_sort_cases": [["--workload", "sort", "--pods", "1x{D}",
                          "--policy", "all"],
                         ["--workload", "sort", "--pods", "1x{D}",
                          "--backend", "constraint"]],
    "bench_sort_pods": [["--workload", "sort", "--pods", "{PODS}",
                         "--policy", "all"]],
    "bench_sort_sizes": [["--workload", "sort", "--pods", "1x{D}"]],
    "bench_striping": [],
    "bench_serve": [["--workload", "serve", "--pods", "{SERVE}"]],
    "bench_serve_pods": [["--workload", "serve", "--pods", "{PODS}"]],
    "bench_kernels": [["--workload", "sort"]],   # single device: R3/R4
    "bench_roofline": [],
}
# substitutions for the full (8-device) harness vs --smoke (2 devices)
CHECK_SUBST = {
    False: {"{D}": "8", "{PODS}": "2x2x2", "{SERVE}": "1x4x2"},
    True: {"{D}": "2", "{PODS}": "2x1", "{SERVE}": "1x2"},
}

_CHECK_SUMMARY_RE = re.compile(
    r"homecheck: (\d+) target\(s\), (\d+) finding\(s\), (\d+) error\(s\)")
_R9_OK_RE = re.compile(r"^R9 certificate \[scheduler\]:", re.M)
_R9_BAD_RE = re.compile(r"^R9 certificate FAILED", re.M)


def run_homecheck(key: str, smoke: bool, timeout: int = 600):
    """Run the family's homecheck sweep.

    Returns ``(status, sched)``: status is "clean" | "findings:N" |
    "failed"; sched is the R9 scheduler-certificate verdict ("certified"
    | "findings:N") when the sweep printed one, else None (non-serve
    families).  The CLI subprocess sets its own XLA_FLAGS from --pods, so
    the harness process keeps its single real device (same discipline as
    the benches).
    """
    subst = CHECK_SUBST[smoke]
    findings = 0
    sched = None
    for argv in CHECK_ARGS.get(key, []):
        for k, v in subst.items():
            argv = [a.replace(k, v) for a in argv]
        # the CLI sets its own device count from --pods
        env = host_device_env(None)
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.homecheck", *argv],
            capture_output=True, text=True, timeout=timeout, env=env)
        m = _CHECK_SUMMARY_RE.search(r.stdout)
        if r.returncode not in (0, 1) or m is None:
            print(f"# homecheck {key} DRIVER FAILURE:\n{r.stderr[-2000:]}",
                  file=sys.stderr)
            return "failed", sched
        findings += int(m.group(2))
        if int(m.group(2)):
            sys.stdout.write(r.stdout)
        n_bad = len(_R9_BAD_RE.findall(r.stdout))
        if n_bad:
            sched = f"findings:{n_bad}"
        elif _R9_OK_RE.search(r.stdout) and sched is None:
            sched = "certified"
    status = "clean" if findings == 0 else f"findings:{findings}"
    return status, sched


# json targets: which CSV prefixes land in which BENCH_*.json
JSON_FILES = {
    "BENCH_sort.json": ("sort_",),
    "BENCH_microbench.json": ("microbench_",),
    "BENCH_engine.json": ("engine_",),
    "BENCH_kernels.json": ("kernel_",),
    "BENCH_serve.json": ("serve_",),
    "BENCH_roofline.json": ("roofline_",),
}


def parse_records(csv_text: str):
    """CSV ``name,us_per_call,derived`` rows -> dict records.

    `backend` and `n` are recovered from the benchmark's name convention
    (``sort_<backend>_case<k>_...``, ``sort_<backend>_n<n>_...``); rows
    without a wall-clock (structure-only lines) keep ``us=None``.
    """
    records = []
    for line in csv_text.splitlines():
        line = line.strip()
        if not line or line.startswith("#") or line.startswith("name,"):
            continue
        parts = line.split(",", 2)
        if len(parts) < 2:
            continue
        name, us = parts[0], parts[1]
        derived = parts[2] if len(parts) > 2 else ""
        m_backend = re.match(r"sort_(constraint|shard_map)_", name)
        m_n = re.search(r"_n(\d+)_", name)
        records.append({
            "name": name,
            "n": int(m_n.group(1)) if m_n else None,
            "us": float(us) if us else None,
            "backend": m_backend.group(1) if m_backend else None,
            "derived": derived,
        })
    return records


def write_json(records, out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    for fname, prefixes in JSON_FILES.items():
        rows = [r for r in records if r["name"].startswith(prefixes)]
        path = os.path.join(out_dir, fname)
        with open(path, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"# wrote {path} ({len(rows)} records)", flush=True)


class _Tee(io.TextIOBase):
    """Write-through to several sinks: capture without losing streaming."""

    def __init__(self, *sinks):
        self.sinks = sinks

    def write(self, s):
        for sink in self.sinks:
            sink.write(s)
        return len(s)

    def flush(self):
        for sink in self.sinks:
            sink.flush()


def run_local(mod: str, args=None) -> str:
    """Run a single-process benchmark module, returning its captured CSV.

    The LOCAL benches print from ``main()`` in-process; without capture
    their rows never reached `parse_records`/`write_json` — BENCH_kernels
    stayed empty no matter what ran.  Output still streams to the real
    stdout as it is produced (interpret-mode sweeps take minutes; a silent
    harness reads as hung).
    """
    m = __import__(f"benchmarks.{mod}", fromlist=["main"])
    buf = io.StringIO()
    with contextlib.redirect_stdout(_Tee(sys.stdout, buf)):
        m.main(args or [])
    return buf.getvalue()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="toy sizes / 2 devices: exercise every entry point")
    ap.add_argument("--out", default=".",
                    help="directory for BENCH_*.json")
    ap.add_argument("--skip-local", action="store_true",
                    help="skip the single-process (non-mesh) benches")
    ap.add_argument("--check", action="store_true",
                    help="run homecheck (R1-R11) over each bench family "
                         "before timing it; the verdict is stamped into "
                         "every BENCH_*.json record (serve families also "
                         "get the R9 scheduler certificate)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    n_devices = 2 if args.smoke else 8
    records = []

    def precheck(key):
        """Homecheck the family before timing it; None when not checking."""
        if not args.check:
            return None
        status, sched = run_homecheck(key, smoke=args.smoke)
        tail = f", schedcheck: {sched}" if sched else ""
        print(f"# homecheck[{key}]: {status}{tail}", flush=True)
        return status, sched

    def stamp(rows, verdicts):
        if verdicts is not None:
            status, sched = verdicts
            for r in rows:
                r["homecheck"] = status
                if sched is not None:
                    r["schedcheck"] = sched
        return rows

    for key, mod, desc in MULTIDEV:
        print(f"# === {key}: {desc} ===", flush=True)
        extra = (SMOKE_ARGS.get(key, []) if args.smoke
                 else FULL_ARGS.get(key, []))
        status = precheck(key)
        out = run_with_devices(mod, n_devices=n_devices, args=extra)
        sys.stdout.write(out)
        sys.stdout.flush()
        records += stamp(parse_records(out), status)
    if not args.skip_local:
        for mod, desc in LOCAL:
            print(f"# === {mod}: {desc} ===", flush=True)
            status = precheck(mod)
            out = run_local(mod, SMOKE_ARGS.get(mod, []) if args.smoke
                            else FULL_ARGS.get(mod, []))
            records += stamp(parse_records(out), status)
    write_json(records, args.out)


if __name__ == "__main__":
    main()
