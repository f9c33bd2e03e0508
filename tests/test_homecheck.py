"""homecheck: every rule R1-R4 provably fires on a committed fixture, and
the analyzer runs clean over every registered workload x policy x backend
(R5-R8 fixtures and the network-certification sweep: test_kernelcheck.py).

The R1/R2 fixtures need a partitioned lowering, so they run in one
8-device subprocess; R3/R4 and the Report API are single-device and run
in-process.  The clean sweep drives the real CLI (exit status included) —
one subprocess per mesh shape, each covering every policy via
``--policy all``.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import repro.core  # noqa: F401  (must precede repro.kernels imports)
from repro.analysis import (Finding, Report, Severity, check_artifacts,
                            summarize)
from repro.analysis.rules import r3_vmem_budget
from repro.analysis.vmem import pallas_footprints
from repro.core import Homing, Locale, LocalisationPolicy
from repro.kernels import VMEM_BYTES_PER_CORE
from repro.kernels.local_sort import local_sort

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout: int = 420) -> str:
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": "src"},
                       cwd=ROOT, timeout=timeout)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


# ---------------------------------------------------------------------------
# findings/report mechanics
# ---------------------------------------------------------------------------
def test_report_clean_errors_suppress_and_summarize():
    rep = Report(target="t")
    assert rep.clean and not rep.errors
    rep.add(Finding("R4", Severity.WARN, "parameter"))
    rep.add(Finding("R1", Severity.ERROR, "all-to-all",
                    predicted_bytes=0.0, actual_bytes=128.0))
    assert not rep.clean
    assert [f.rule for f in rep.errors] == ["R1"]
    assert "[R1 ERROR] all-to-all" in rep.format()
    rep.suppress(["R4"])
    assert rep.suppressed == ["R4"]
    assert [f.rule for f in rep.findings] == ["R1"]
    assert summarize([rep, Report(target="u")]) == (1, 1)


# ---------------------------------------------------------------------------
# R3 fixture: an oversized local_sort chunk cannot fit per-core VMEM
# ---------------------------------------------------------------------------
def test_r3_vmem_budget_flags_oversized_local_sort_chunk():
    big = jax.ShapeDtypeStruct((1, 1 << 25), jnp.float32)   # 128 MiB row
    jx = jax.make_jaxpr(lambda v: local_sort(v))(big)       # trace only
    rep = Report(target="r3-fixture")
    r3_vmem_budget(rep, pallas_footprints(jx), VMEM_BYTES_PER_CORE)
    errs = rep.errors
    assert errs and all(f.rule == "R3" for f in errs), rep.format()
    assert errs[0].actual_bytes > VMEM_BYTES_PER_CORE

    ok = jax.ShapeDtypeStruct((4, 1 << 10), jnp.float32)    # 4 KiB rows
    rep2 = Report(target="r3-small")
    r3_vmem_budget(rep2, pallas_footprints(
        jax.make_jaxpr(lambda v: local_sort(v))(ok)), VMEM_BYTES_PER_CORE)
    assert rep2.clean, rep2.format()


# ---------------------------------------------------------------------------
# R4 fixture: a large step-carried buffer that is not donated
# ---------------------------------------------------------------------------
def test_r4_donation_audit_flags_then_clean_when_donated():
    x = jnp.zeros((1 << 19,), jnp.float32)                  # 2 MiB
    step = lambda b: b * 2.0
    hlo = jax.jit(step).lower(x).compile().as_text()
    rep = check_artifacts("r4-fixture", hlo)
    assert any(f.rule == "R4" and f.severity == Severity.WARN
               for f in rep.findings), rep.format()
    assert not rep.clean and not rep.errors     # WARN dirties, not ERROR

    donated = jax.jit(step, donate_argnums=(0,)).lower(x).compile().as_text()
    assert check_artifacts("r4-donated", donated).clean

    sup = check_artifacts("r4-suppressed", hlo, suppress=("R4",))
    assert sup.clean and sup.suppressed == ["R4"]


# ---------------------------------------------------------------------------
# R1 + R2 fixtures: need a multi-device partitioned lowering
# ---------------------------------------------------------------------------
R1_R2_FIXTURES = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.analysis import check_artifacts
from repro.core import Homing, Locale, LocalisationPolicy, collective_census
from repro.core.engine import engine_granule
from repro.launch.mesh import make_host_mesh

# R2: values homed across a mesh axis the locale never declared.  The old
# miscompile (GSPMD summing padded elements across an unrelated "model"
# axis) no longer reproduces on this XLA, so the fixture homes the keys
# over ("pod", "data", "model") itself: the sort's gather then spans
# "model", and homecheck must flag it.
mesh = make_host_mesh(n_pods=2, n_data=2, n_model=2)

def leaky(x):
    pad = jnp.full((31,), jnp.iinfo(jnp.int32).max, jnp.int32)
    y = jnp.concatenate([x, pad])
    y = jax.lax.with_sharding_constraint(
        y, NamedSharding(mesh, P(("pod", "data", "model"))))
    return jnp.sort(y)

hlo = jax.jit(leaky).lower(jnp.zeros((4065,), jnp.int32)).compile().as_text()
rep = check_artifacts("r2-fixture", hlo, mesh=mesh,
                      allowed_axes=("pod", "data"))
assert any(f.rule == "R2" for f in rep.errors), rep.format(verbose=True)
assert any("model" in f.message for f in rep.errors)
print("R2_FLAGGED")

# R1: lower the hash-interleaved engine, then diff it against the budget
# for the *chunked* policy — the hash pre-exchange all-to-all is unbudgeted.
flat = make_host_mesh(n_data=8, n_model=1)
loc = Locale(mesh=flat, axis="data",
             policy=LocalisationPolicy(homing=Homing.HASH_INTERLEAVED))
g = engine_granule(8, None, True)
n = ((1 << 13) + g - 1) // g * g
fn = loc.workload("sort", backend="shard_map")
hlo = fn.lower(jnp.arange(n, dtype=jnp.int32)).compile().as_text()
wrong = collective_census(n, (8,), LocalisationPolicy())
rep = check_artifacts("r1-fixture", hlo, predicted=wrong, mesh=flat,
                      allowed_axes=("data",))
assert any(f.rule == "R1" and "unbudgeted" in f.message
           for f in rep.errors), rep.format(verbose=True)

# the matching budget must be clean (same artifacts, right policy)
right = collective_census(
    n, (8,), LocalisationPolicy(homing=Homing.HASH_INTERLEAVED))
assert check_artifacts("r1-match", hlo, predicted=right, mesh=flat,
                       allowed_axes=("data",)).clean
print("R1_FLAGGED")
"""


def test_r1_r2_fixtures_flag_committed_patterns():
    out = _run(R1_R2_FIXTURES)
    assert "R2_FLAGGED" in out and "R1_FLAGGED" in out


# ---------------------------------------------------------------------------
# Locale.check(): the in-API hook (degenerate single-device locale)
# ---------------------------------------------------------------------------
def test_locale_check_api_single_device():
    for policy in (LocalisationPolicy(),
                   LocalisationPolicy(homing=Homing.HASH_INTERLEAVED)):
        rep = Locale(mesh=None, policy=policy).check(
            "sort", backend="constraint")
        assert rep.clean, rep.format(verbose=True)
    rep = Locale(mesh=None).check("microbench", reps=2)
    assert rep.clean, rep.format(verbose=True)
    assert rep.target == "microbench"


# ---------------------------------------------------------------------------
# acceptance sweep: every workload x {flat, hierarchical} x both backends
# runs homecheck-clean through the real CLI (exit status 0)
# ---------------------------------------------------------------------------
SWEEP = [
    ("flat-all-policies",
     ["--workload", "all", "--pods", "1x4", "--policy", "all"]),
    ("hier-all-policies",
     ["--workload", "all", "--pods", "2x2x2", "--policy", "all"]),
    ("flat-constraint", ["--workload", "sort", "--pods", "1x4",
                         "--backend", "constraint"]),
    ("engine-hier", ["--workload", "engine", "--pods", "2x2",
                     "--policy", "hier"]),
    ("flat-new-rules", ["--workload", "sort", "--pods", "1x4",
                        "--policy", "all", "--rules", "R5", "R6", "R7",
                        "R8"]),
    ("serve-r9r10r11", ["--workload", "serve", "--pods", "1x4",
                        "--rules", "r9,r10,r11"]),
]


@pytest.mark.parametrize("name,argv", SWEEP, ids=[s[0] for s in SWEEP])
def test_homecheck_cli_sweep_clean(name, argv):
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.homecheck", *argv],
        capture_output=True, text=True, cwd=ROOT, timeout=420,
        env={**os.environ, "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 finding(s), 0 error(s)" in r.stdout, r.stdout
    if any("r9" in a.lower() for a in argv):
        # the full-lattice scheduler certificate prints with the sweep
        assert "R9 certificate [scheduler]" in r.stdout, r.stdout
