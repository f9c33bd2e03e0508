"""The shard_map execution engine vs `jnp.sort` — bit-exact, all policies.

Fast tier covers the single-device mesh (padding, dtypes, backend dispatch);
the slow tier runs the real thing: an 8-device host mesh, all four Table-1
policy combinations (localised x homing — the engine *is* the static
mapping, so `static_mapping` has no engine-side analogue), both backends.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
from jax.sharding import AxisType
import numpy as np
import pytest

from repro.core import (BACKENDS, Homing, Locale, LocalisationPolicy,
                        exchange_schedule, pad_to_multiple, pad_value)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_8dev(code: str, timeout: int = 900):
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": "src"},
                       cwd=ROOT, timeout=timeout)
    return r

POLICIES = [LocalisationPolicy(loc, True, h)
            for loc in (True, False)
            for h in (Homing.LOCAL_CHUNKED, Homing.HASH_INTERLEAVED)]


def _rand(n, dtype, seed=0):
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        return jax.random.randint(jax.random.key(seed), (n,), -10**6, 10**6,
                                  dtype=dtype)
    return jax.random.normal(jax.random.key(seed), (n,), dtype)


def test_pad_value_covers_core_dtypes():
    assert pad_value(jnp.int32) == jnp.iinfo(jnp.int32).max
    assert pad_value(jnp.float32) == jnp.inf
    assert pad_value(jnp.int16) == jnp.iinfo(jnp.int16).max


@pytest.mark.parametrize("n,m", [(64, 8), (65, 8), (7, 8), (100, 4)])
def test_pad_to_multiple_strips_cleanly(n, m):
    x = _rand(n, jnp.int32)
    xp = pad_to_multiple(x, m)
    assert xp.shape[0] % m == 0 and xp.shape[0] - n < m
    np.testing.assert_array_equal(np.sort(np.asarray(xp))[:n],
                                  np.sort(np.asarray(x)))


def test_unknown_backend_rejected():
    with pytest.raises(ValueError):
        Locale().workload("sort", backend="nope")


# one (n, dtype) config per policy; the fast lane keeps the two policy
# extremes (fully localised, non-localised hash) and the slow 8-device test
# sweeps every policy x dtype x length combination
ENGINE_SINGLE = [pytest.param(p, n, dt, marks=() if i in (0, 3)
                              else (pytest.mark.slow,))
                 for i, (p, (n, dt)) in enumerate(zip(
                     POLICIES, [(512, "int32"), (1000, "float32"),
                                (1000, "int32"), (512, "float32")]))]


@pytest.mark.parametrize("policy,n,dtype", ENGINE_SINGLE,
                         ids=lambda v: getattr(v, "name", v))
def test_engine_single_device_bit_exact(policy, dtype, n):
    """1-device mesh: leaves + local merge path, Pallas bitonic local sort."""
    mesh = jax.make_mesh((len(jax.devices()),), ("data",),
                         axis_types=(AxisType.Auto,))
    x = _rand(n, jnp.dtype(dtype))
    expect = np.sort(np.asarray(x))
    fn = Locale(mesh=mesh, policy=policy).workload("engine", num_workers=8)
    np.testing.assert_array_equal(np.asarray(fn(x)), expect)


def test_constraint_backend_arbitrary_length_padding():
    """Satellite: BIG-padding replaces the old n % m == 0 assert."""
    for n, dtype in ((4097, jnp.int32), (100, jnp.float32)):
        x = _rand(n, dtype)
        expect = np.sort(np.asarray(x))
        fn = Locale().workload("sort", num_workers=8)
        np.testing.assert_array_equal(np.asarray(fn(x)), expect)


def test_sentinel_values_in_data_survive():
    """Real elements equal to the BIG sentinel must not be stripped."""
    for backend in ("constraint", "shard_map"):
        # fresh input per backend: the jitted sorts donate their argument
        x = jnp.asarray([5, jnp.iinfo(jnp.int32).max, -3, 1, 2], jnp.int32)
        expect = np.sort(np.asarray(x))
        fn = Locale().workload("sort", num_workers=4, backend=backend)
        np.testing.assert_array_equal(np.asarray(fn(x)), expect)


@pytest.mark.slow
def test_engine_8dev_all_cases_both_backends():
    """Acceptance: bit-identical to jnp.sort on a >=8-device host mesh."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import Homing, Locale, LocalisationPolicy
locale = Locale.auto()
for backend in ["constraint", "shard_map"]:
    for loc in [True, False]:
        for h in [Homing.LOCAL_CHUNKED, Homing.HASH_INTERLEAVED]:
            for n, dt in [(1 << 13, jnp.int32), (5000, jnp.float32)]:
                if dt == jnp.int32:
                    x = jax.random.randint(jax.random.key(0), (n,), -10**6,
                                           10**6, dtype=dt)
                else:
                    x = jax.random.normal(jax.random.key(0), (n,), dt)
                expect = np.asarray(jnp.sort(x))
                pol = LocalisationPolicy(loc, True, h)
                fn = locale.with_policy(pol).workload("sort", backend=backend)
                y = np.asarray(fn(x))
                np.testing.assert_array_equal(y, expect,
                    err_msg=f"{backend} {pol.name} {n} {dt}")
print("ENGINE_8DEV_OK")
"""
    r = _run_8dev(code)
    assert "ENGINE_8DEV_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_engine_collective_structure_matches_policy():
    """Localised => chunk-sized ppermute merge-split network, log2(m) stages
    with i+1 exchanges each = 6 for m=8 (+ one-shot all-to-all under hash
    homing); non-localised => one all-gather per level. Lowered-HLO check."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.core import Homing, Locale, LocalisationPolicy
from repro.launch.hlo_cost import analyze
locale = Locale.auto()
x = jnp.zeros((1 << 13,), jnp.int32)
def counts(policy):
    fn = locale.with_policy(policy).workload("sort", backend="shard_map")
    return analyze(fn.lower(x).compile().as_text())["collective_counts"]
c = counts(LocalisationPolicy(True, True, Homing.LOCAL_CHUNKED))
assert c.get("collective-permute") == 6 and "all-gather" not in c, c
c = counts(LocalisationPolicy(True, True, Homing.HASH_INTERLEAVED))
assert c.get("collective-permute") == 6 and c.get("all-to-all") == 1, c
c = counts(LocalisationPolicy(False, True, Homing.LOCAL_CHUNKED))
assert c.get("all-gather", 0) >= 4 and "collective-permute" not in c, c
c = counts(LocalisationPolicy(False, True, Homing.HASH_INTERLEAVED))
assert c.get("all-gather", 0) >= 4 and "collective-permute" not in c, c
print("STRUCTURE_OK")
"""
    r = _run_8dev(code)
    assert "STRUCTURE_OK" in r.stdout, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# satellite: NaN-unsafe sentinel padding now fails loudly
# ---------------------------------------------------------------------------
def test_pad_to_multiple_rejects_nan_when_padding():
    x = jnp.asarray([1.0, jnp.nan, 2.0], jnp.float32)
    with pytest.raises(ValueError, match="NaN"):
        pad_to_multiple(x, 8)
    # no padding needed -> pass-through, NaN or not (nothing to corrupt)
    x4 = jnp.asarray([1.0, jnp.nan, 2.0, 0.0], jnp.float32)
    assert pad_to_multiple(x4, 4) is x4


@pytest.mark.parametrize("backend", BACKENDS)
def test_sort_rejects_nan_floats_eagerly(backend):
    """Both float sort paths refuse NaN inputs before tracing/donating."""
    fn = Locale().workload("sort", num_workers=4, backend=backend)
    x = jnp.asarray([3.0, jnp.nan, 1.0, 2.0, 5.0], jnp.float32)
    with pytest.raises(ValueError, match="NaN"):
        fn(x)
    # NaN-free floats (padded and unpadded lengths) still sort bit-exactly
    for n in (5, 8):
        y = jax.random.normal(jax.random.key(0), (n,), jnp.float32)
        expect = np.sort(np.asarray(y))
        np.testing.assert_array_equal(np.asarray(fn(y)), expect)


def test_put_pad_rejects_nan():
    loc = Locale(mesh=jax.make_mesh((1,), ("data",),
                                    axis_types=(AxisType.Auto,)))
    # axis_size 1 never pads -> accepted; explicit pad granule via the sort
    h = loc.put(jnp.asarray([jnp.nan, 1.0], jnp.float32), pad=True)
    assert h.size == 2
    with pytest.raises(ValueError, match="NaN"):
        pad_to_multiple(jnp.asarray([jnp.nan, 1.0], jnp.float32), 4)


# ---------------------------------------------------------------------------
# satellite: host-mesh shape validation
# ---------------------------------------------------------------------------
def test_make_host_mesh_validates_shape():
    from repro.launch.mesh import make_host_mesh
    n = len(jax.devices())          # 1 in the main test process
    with pytest.raises(ValueError, match=rf"n_model=3.*device count {n}"):
        make_host_mesh(n_model=3)
    with pytest.raises(ValueError, match=rf"needs {5 * n}.*has {n}"):
        make_host_mesh(n_data=5 * n)
    with pytest.raises(ValueError, match="n_pods=2"):
        make_host_mesh(n_pods=2)
    with pytest.raises(ValueError, match="positive int"):
        make_host_mesh(n_model=0)
    m = make_host_mesh()
    assert dict(zip(m.axis_names, m.devices.shape)) == {"data": n, "model": 1}


# ---------------------------------------------------------------------------
# tentpole: hierarchical policy + exchange schedule (fast, analytic)
# ---------------------------------------------------------------------------
def test_hierarchical_policy_factory():
    pol = LocalisationPolicy.hierarchical()
    assert pol.localised and pol.outer == "hash"
    assert pol.homing == Homing.LOCAL_CHUNKED
    assert pol.name.startswith("hier.hash-")
    assert LocalisationPolicy.hierarchical(inner="hash").homing == \
        Homing.HASH_INTERLEAVED
    with pytest.raises(ValueError, match="outer"):
        LocalisationPolicy(outer="nope")
    with pytest.raises(ValueError, match="inner"):
        LocalisationPolicy.hierarchical(inner="nope")


def test_hierarchical_policy_needs_pod_axis():
    """A hierarchical policy on a flat single-axis locale is an error."""
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    fn = Locale(mesh=mesh,
                policy=LocalisationPolicy.hierarchical()).workload(
                    "engine", num_workers=4)
    with pytest.raises(ValueError, match="pod"):
        fn(jnp.arange(16, dtype=jnp.int32))


def test_exchange_schedule_hier_strictly_fewer_inter_pod_bytes():
    """The acceptance inequality, as pure schedule math on every pod shape."""
    n = 1 << 13
    for sizes in [(2, 4), (2, 2), (4, 2), (2, 1), (4, 4)]:
        hier = exchange_schedule(n, sizes, LocalisationPolicy.hierarchical())
        nonloc = exchange_schedule(
            n, sizes, LocalisationPolicy(False, True, Homing.LOCAL_CHUNKED))
        tot = lambda s, k: sum(r[k] for r in s)
        assert tot(hier, "inter_pod_bytes") < tot(nonloc, "inter_pod_bytes"), \
            sizes
        # intra-pod ppermutes never cross the DCN boundary, and the deep
        # (low-stride) levels are entirely intra-pod
        for r in hier:
            assert r["inter_pod_bytes"] == 0 or r["intra_pod_bytes"] == 0
            if r["op"] == "all_gather":
                assert r["intra_pod_bytes"] == 0
    # single flat axis: everything is "intra-pod" (there is only one pod)
    flat = exchange_schedule(n, (8,), LocalisationPolicy())
    assert all(r["inter_pod_bytes"] == 0 for r in flat)
    assert sum(1 for r in flat if r["op"] == "ppermute") == 6


def test_exchange_schedule_counts_match_network():
    """ppermute count = sum_{i} substages; one gather per top stage (hier)."""
    sched = exchange_schedule(1 << 12, (2, 4),
                              LocalisationPolicy.hierarchical())
    assert sum(1 for r in sched if r["op"] == "all_gather") == 1   # log2(2)
    assert sum(1 for r in sched if r["op"] == "ppermute") == 5     # 1+2+2
    flat = exchange_schedule(1 << 12, (2, 4), LocalisationPolicy())
    assert sum(1 for r in flat if r["op"] == "ppermute") == 6      # 1+2+3
    # hash input homing adds exactly one relayout all_to_all up front
    hashed = exchange_schedule(1 << 12, (2, 4),
                               LocalisationPolicy.hierarchical(inner="hash"))
    assert hashed[0]["op"] == "all_to_all" and hashed[0]["level"] == 0


# ---------------------------------------------------------------------------
# tentpole: emulated-pod meshes, bit-exact + HLO structure (slow subprocess)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_engine_pod_meshes_bit_exact_all_policies():
    """Acceptance: (2,2,2) and (2,4,1) emulated pods, hierarchical + flat
    policies, shard_map engine vs jnp.sort; constraint backend spot-checked
    on a padded length (the GSPMD mis-partition regression)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import Homing, Locale, LocalisationPolicy
from repro.launch.mesh import make_host_mesh
for shape in [(2, 2, 2), (2, 4, 1)]:
    mesh = make_host_mesh(n_pods=shape[0], n_data=shape[1], n_model=shape[2])
    locale = Locale(mesh=mesh, axis=("pod", "data"))
    pols = [LocalisationPolicy.hierarchical(),
            LocalisationPolicy.hierarchical(inner="hash"),
            LocalisationPolicy(True, True, Homing.LOCAL_CHUNKED),
            LocalisationPolicy(True, True, Homing.HASH_INTERLEAVED),
            LocalisationPolicy(False, True, Homing.LOCAL_CHUNKED),
            LocalisationPolicy(False, True, Homing.HASH_INTERLEAVED)]
    for pol in pols:
        for n, dt in [(1 << 13, jnp.int32), (5000, jnp.float32)]:
            if dt == jnp.int32:
                x = jax.random.randint(jax.random.key(0), (n,), -10**6,
                                       10**6, dtype=dt)
            else:
                x = jax.random.normal(jax.random.key(0), (n,), dt)
            expect = np.asarray(jnp.sort(x))
            fn = locale.with_policy(pol).workload("sort", backend="shard_map",
                                                  local_sort=jnp.sort)
            np.testing.assert_array_equal(np.asarray(fn(x)), expect,
                err_msg=f"shard_map {shape} {pol.name} {n}")
    # constraint backend on the pod mesh: a padded length used to come back
    # doubled (GSPMD partitioned concatenate/scatter on a mesh with a >1
    # unrelated axis); eager padding + the gather-form merge fixed it
    for pol in [LocalisationPolicy.hierarchical(),
                LocalisationPolicy(True, True, Homing.LOCAL_CHUNKED),
                LocalisationPolicy(False, True, Homing.HASH_INTERLEAVED)]:
        x = jax.random.randint(jax.random.key(2), (4097,), -10**6, 10**6,
                               dtype=jnp.int32)
        expect = np.sort(np.asarray(x))
        fn = locale.with_policy(pol).workload("sort", backend="constraint")
        np.testing.assert_array_equal(np.asarray(fn(x)), expect,
            err_msg=f"constraint {shape} {pol.name}")
    print("POD_MESH_OK", shape)
# bypassing the eager-padding entry points with a non-granular length on a
# mesh with a >1 unrelated axis must fail loudly at trace time, not return
# silently-doubled values (check_pad_outside_trace)
from functools import partial
from repro.core.sort import distributed_merge_sort
from repro.core.engine import shard_map_sort
mesh = make_host_mesh(n_pods=2, n_data=2, n_model=2)
for raw in [partial(distributed_merge_sort, mesh=mesh, axis=("pod", "data")),
            partial(shard_map_sort, mesh=mesh, axis=("pod", "data"))]:
    try:
        jax.jit(raw)(jnp.zeros((4097,), jnp.int32))
        raise SystemExit("in-trace pad on an unsafe mesh did not raise")
    except ValueError as e:
        assert "pad_to_multiple" in str(e), e
print("PAD_GUARD_OK")
"""
    r = _run_8dev(code)
    assert r.stdout.count("POD_MESH_OK") == 2, r.stdout + r.stderr
    assert "PAD_GUARD_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_engine_pod_collective_structure():
    """Lowered-HLO proof of the two distance classes on a (2,4,1) mesh:
    hierarchical => 5 intra-pod ppermutes + ONE pod-axis all_gather (the
    only DCN collective); flat localised => 6 pairwise ppermutes, no
    gather; non-localised => one all_gather per level, no ppermutes.  The
    counts must agree with exchange_schedule, which the benchmark reports."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from collections import Counter
from repro.core import Homing, Locale, LocalisationPolicy, exchange_schedule
from repro.launch.hlo_cost import analyze
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(n_pods=2, n_data=4, n_model=1)
locale = Locale(mesh=mesh, axis=("pod", "data"))
x = jnp.zeros((1 << 13,), jnp.int32)
def counts(policy):
    fn = locale.with_policy(policy).workload("sort", backend="shard_map")
    return analyze(fn.lower(x).compile().as_text())["collective_counts"]
def sched_counts(policy):
    ops = Counter(r["op"] for r in exchange_schedule(1 << 13, (2, 4), policy))
    return {"collective-permute": ops.get("ppermute", 0),
            "all-gather": ops.get("all_gather", 0),
            "all-to-all": ops.get("all_to_all", 0)}
hier = LocalisationPolicy.hierarchical()
c = counts(hier)
assert c.get("collective-permute") == 5 and c.get("all-gather") == 1, c
flat = LocalisationPolicy(True, True, Homing.LOCAL_CHUNKED)
c2 = counts(flat)
assert c2.get("collective-permute") == 6 and "all-gather" not in c2, c2
hh = LocalisationPolicy.hierarchical(inner="hash")
c3 = counts(hh)
assert c3.get("all-to-all") == 1 and c3.get("collective-permute") == 5 \
    and c3.get("all-gather") == 1, c3
nl = LocalisationPolicy(False, True, Homing.LOCAL_CHUNKED)
c4 = counts(nl)
assert c4.get("all-gather", 0) >= 4 and "collective-permute" not in c4, c4
assert sched_counts(nl)["all-gather"] == 4
# the analytic schedule the benchmark emits matches the lowered HLO of the
# localised variants exactly
for pol, c in [(hier, c), (flat, c2), (hh, c3)]:
    sc = sched_counts(pol)
    for k, v in sc.items():
        assert c.get(k, 0) == v, (pol.name, k, v, c)
print("POD_STRUCTURE_OK")
"""
    r = _run_8dev(code)
    assert "POD_STRUCTURE_OK" in r.stdout, r.stdout + r.stderr
