"""homecheck CLI: statically verify workloads against their home contract.

    PYTHONPATH=src python -m repro.launch.homecheck \
        --workload sort|engine|microbench|serve|all \
        [--pods PxD[xM]] [--policy flat|hash|nonloc|nonloc-hash|hier|hier-hash] \
        [--backend shard_map|constraint] [--logn N] [--num-workers W] \
        [--rules all|R1 R5 R6 ...] [--suppress R4 ...] [--json] [--verbose]

Lowers the selected workload(s) over the requested (emulated) mesh and
runs rules R1-R11 (see `repro.analysis`) on the partitioned HLO + jaxpr +
exchange network — nothing executes.  ``--rules`` selects a subset
(default all): R1/R2 collective budget + home leaks, R3 VMEM, R4
donation, R5 pallas write-race/coverage, R6 sorting-network
certification, R7 index-arithmetic/sentinel lint, R8 dead grid lanes,
R9 scheduler-invariant certification, R10 HBM live-range vs the
per-device ceiling (``--hbm-ceiling`` overrides), R11 collectives under
data-dependent control flow.
When R6 is active the sweep also prints the repo-wide certificate: every
supported policy 0-1-certified over every mesh shape up to 16 devices.
When R9 is active the sweep prints the scheduler certificate: invariants
I1-I8 proved by exhaustive interleaving search over the full small-config
lattice (per-target reports run the fast corner; the certificate here is
the full one).
``--pods`` sets ``XLA_FLAGS`` itself, so the command is self-sufficient
on a laptop.  Exit status 1 on any ERROR-severity finding (and 2 on a
driver failure), so `runtime.ft.Supervisor`/CI can supervise it
uniformly.
"""
from __future__ import annotations

import argparse
import os
import sys


def parse_pods(spec: str):
    """``PxD`` or ``PxDxM`` -> (n_pods, n_data, n_model)."""
    parts = [int(p) for p in spec.lower().split("x")]
    if len(parts) == 2:
        parts.append(1)
    if len(parts) != 3 or any(p < 1 for p in parts):
        raise argparse.ArgumentTypeError(
            f"--pods wants PxD or PxDxM with positive ints, got {spec!r}")
    return tuple(parts)


POLICIES = ("auto", "flat", "hash", "nonloc", "nonloc-hash",
            "hier", "hier-hash", "all")


def make_policy(name: str, n_pods: int):
    """Resolve a --policy name to a LocalisationPolicy (lazy jax import)."""
    from repro.core.homing import Homing
    from repro.core.localisation import LocalisationPolicy
    if name == "auto":
        name = "hier" if n_pods > 1 else "flat"
    return {
        "flat": LocalisationPolicy(),
        "hash": LocalisationPolicy(homing=Homing.HASH_INTERLEAVED),
        "nonloc": LocalisationPolicy(localised=False),
        "nonloc-hash": LocalisationPolicy(
            localised=False, homing=Homing.HASH_INTERLEAVED),
        "hier": LocalisationPolicy.hierarchical(),
        "hier-hash": LocalisationPolicy.hierarchical(inner="hash"),
    }[name]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="static locality analyzer (homecheck)")
    ap.add_argument("--workload", default="sort",
                    choices=("sort", "engine", "microbench", "serve", "all"))
    ap.add_argument("--pods", type=parse_pods, default=None, metavar="PxD[xM]",
                    help="emulated (pod, data, model) mesh; sets XLA_FLAGS")
    ap.add_argument("--policy", choices=POLICIES, default="auto",
                    help="localisation policy (auto = hier on a pod mesh; "
                         "all = every policy the mesh supports)")
    ap.add_argument("--backend", choices=("shard_map", "constraint"),
                    default="shard_map",
                    help="sort backend (R1 needs the shard_map byte model)")
    ap.add_argument("--logn", type=int, default=12,
                    help="~log2 of the representative input length")
    ap.add_argument("--num-workers", type=int, default=None)
    ap.add_argument("--reps", type=int, default=4, help="microbench passes")
    ap.add_argument("--arch", default="qwen3-0.6b", help="serve config")
    ap.add_argument("--rules", nargs="*", default=None, metavar="RULE",
                    help="rules to run (R1..R11 or 'all'; default all); "
                         "with R6/R9 active the repo-wide mesh and "
                         "scheduler certificates are printed too")
    ap.add_argument("--hbm-ceiling", type=int, default=None,
                    help="R10 per-device HBM ceiling in bytes (default "
                         "repro.kernels.HBM_BYTES_PER_DEVICE)")
    ap.add_argument("--suppress", nargs="*", default=(), metavar="RULE",
                    help="rule ids to drop from the report (e.g. R4)")
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)

    # the mesh is emulated out of host devices: the flags must be set before
    # jax (transitively, any repro module) first touches the backend, and
    # the CPU platform keeps this process off any accelerator
    if args.pods is not None:
        n_dev = args.pods[0] * args.pods[1] * args.pods[2]
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_dev}").strip()

    import jax

    from repro.analysis import (certify_supported_meshes, check_decode,
                                check_workload, normalize_rules, summarize)
    from repro.core.api import Locale
    from repro.launch.mesh import make_host_mesh

    try:
        rules = normalize_rules(args.rules)
    except ValueError as e:
        ap.error(str(e))

    if args.pods is not None:
        p, d, m = args.pods
        mesh = make_host_mesh(n_pods=p, n_data=d, n_model=m)
        sort_axis = ("pod", "data") if p > 1 else "data"
        n_pods = p
    else:
        n_dev = len(jax.devices())
        mesh = make_host_mesh(n_data=n_dev, n_model=1) if n_dev > 1 else None
        sort_axis = "data"
        n_pods = 1
    def pol_names(workload: str):
        """--policy all: every policy the mesh supports for this workload."""
        if args.policy != "all":
            return (args.policy,)
        if workload == "microbench":            # Fig-1 bench: loc vs nonloc
            return ("flat", "nonloc")
        return (("hier", "hier-hash") if n_pods > 1
                else ("flat", "hash", "nonloc", "nonloc-hash"))

    names = (("sort", "microbench", "serve") if args.workload == "all"
             else (args.workload,))
    reports = []
    for name in names:
        if name == "serve":
            reports.append(check_decode(mesh, cfg_name=args.arch,
                                        hbm_ceiling=args.hbm_ceiling,
                                        rules=rules,
                                        suppress=args.suppress))
            continue
        for pname in pol_names(name):
            locale = Locale(mesh=mesh, axis=sort_axis,
                            policy=make_policy(pname, n_pods))
            reports.append(check_workload(
                locale, name, backend=args.backend,
                num_workers=args.num_workers, logn=args.logn,
                reps=args.reps, hbm_ceiling=args.hbm_ceiling,
                rules=rules, suppress=args.suppress))

    for rep in reports:
        print(rep.to_json() if args.as_json
              else rep.format(verbose=args.verbose))

    cert_errors = 0
    if "R9" in rules:
        from repro.analysis import DEFAULT_LATTICE, certify_lattice
        cert = certify_lattice(DEFAULT_LATTICE)
        bad = {n: rec for n, rec in cert.items()
               if rec["witness"] is not None}
        total_states = sum(rec["states"] for rec in cert.values())
        if bad:
            cert_errors += len(bad)
            for n, rec in bad.items():
                print(f"R9 certificate FAILED [{n}]: "
                      f"{rec['witness'].format()}")
        else:
            configs = ", ".join(f"{n}({rec['states']})"
                                for n, rec in cert.items())
            print(f"R9 certificate [scheduler]: I1-I8 hold over "
                  f"{len(cert)} lattice config(s), {total_states} "
                  f"canonical states explored exhaustively ({configs})")
    if "R6" in rules:
        cert = certify_supported_meshes()
        for pname, rec in sorted(cert.items()):
            meshes = ", ".join("x".join(map(str, s))
                               for s in rec["certified"])
            line = (f"R6 certificate [{pname}]: "
                    f"{len(rec['certified'])} mesh(es) 0-1 certified"
                    f" ({meshes})")
            if rec["failed"]:
                cert_errors += len(rec["failed"])
                line += f"; FAILED: {rec['failed']}"
            print(line)

    dirty, errors = summarize(reports)
    errors += cert_errors
    total = sum(len(r.findings) for r in reports)
    print(f"homecheck: {len(reports)} target(s), {total} finding(s), "
          f"{errors} error(s)")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
