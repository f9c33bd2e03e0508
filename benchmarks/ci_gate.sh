#!/usr/bin/env bash
# Pre-merge gate: fast tests + the full static analyzer sweep, one command.
#
#   benchmarks/ci_gate.sh [BENCH_DIR]
#
# Runs `pytest -m "not slow"` and `launch/homecheck.py --workload all
# --rules all` over a flat and a hierarchical emulated mesh (the analyzer
# subprocesses set their own XLA_FLAGS).  `--rules all` is R1-R11: each
# sweep includes the R9 scheduler certificate over the full small-config
# lattice — now including the paged (page_capacity > 0) and continuous-
# refill configs, so I8 (page refcounts never leak) is part of the
# certificate — and the R10/R11 (HBM live-range, collective control
# flow) checks on every lowered workload.  A traced ``--smoke`` serve then
# runs with ``--trace`` and `launch/tracelog.py --validate` replays it,
# proving the observability counter identities (trace schema, charged
# bytes == scheduler stats == summary, pool refcounts balance, every
# off-home decode paid for).  A dedicated step then proves
# the certificate has teeth: every committed scheduler mutant (including
# `leak_page`, which drops a page-refcount release) must be *refuted*
# with a minimal witness tagged with its invariant — an R9 that stopped
# catching a known-bad scheduler fails the gate even though every clean
# sweep still passes.  It then stamps the combined verdict
# (`"ci_gate": "pass"|"fail"`) into every record of every BENCH_*.json in
# BENCH_DIR (default: repo root) alongside the existing "homecheck" key —
# `benchmarks/compare.py` fails a PR whose baseline was "pass" but whose
# fresh run is not.  Exit status 0 iff everything passed.
set -u
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
BENCH_DIR="${1:-.}"
verdict=pass

echo "== ci_gate: pytest -m 'not slow' =="
python -m pytest -x -q -m "not slow" || verdict=fail

echo "== ci_gate: homecheck --workload all --rules all (flat 1x8) =="
python -m repro.launch.homecheck --workload all --pods 1x8 \
    --policy all --rules all || verdict=fail

echo "== ci_gate: homecheck --workload all --rules all (hier 2x2x2) =="
python -m repro.launch.homecheck --workload all --pods 2x2x2 \
    --policy all --rules all || verdict=fail

echo "== ci_gate: traced smoke serve + trace reconciliation =="
TRACE="$(mktemp -t ci_trace.XXXXXX.jsonl)"
python -m repro.launch.serve --reduced --policy homed --smoke --trace "$TRACE" \
    > /dev/null || verdict=fail
# the validator replays the trace and proves every counter identity
# (charges == stats == summary bytes, pool refs balance, off-home decodes
# all paid for) — a broken instrumentation layer fails the gate here
python -m repro.launch.tracelog "$TRACE" --validate || verdict=fail
rm -f "$TRACE"

echo "== ci_gate: R9 mutant refutation (every committed mutant witnessed) =="
python - <<'EOF' || verdict=fail
from repro.analysis.fixtures import MUTANT_INVARIANT, mutant_scheduler
from repro.analysis.schedcheck import certify
from repro.runtime.scheduler import MUTATIONS

ok = True
for mutation in MUTATIONS:
    witness, states = certify(mutant_scheduler(mutation))
    if witness is None:
        print(f"R9 mutant NOT refuted: {mutation} certified clean over "
              f"{states} states — the certificate lost its teeth")
        ok = False
    elif witness.invariant != MUTANT_INVARIANT[mutation]:
        print(f"R9 mutant {mutation}: wrong invariant "
              f"{witness.invariant} (want {MUTANT_INVARIANT[mutation]}): "
              f"{witness.format()}")
        ok = False
    else:
        print(f"R9 mutant refuted: {mutation} -> {witness.format()}")
raise SystemExit(0 if ok else 1)
EOF

python - "$verdict" "$BENCH_DIR" <<'EOF'
import glob, json, os, sys
verdict, bench_dir = sys.argv[1], sys.argv[2]
for path in sorted(glob.glob(os.path.join(bench_dir, "BENCH_*.json"))):
    with open(path) as f:
        rows = json.load(f)
    for r in rows:
        r["ci_gate"] = verdict
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"# stamped ci_gate={verdict} into {path} ({len(rows)} records)")
EOF

echo "== ci_gate: $verdict =="
[ "$verdict" = pass ]
