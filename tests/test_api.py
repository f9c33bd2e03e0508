"""The unified `Locale`/`Homed` placement API: contracts + property tests.

Fast tier runs the single-device mesh and the degenerate (mesh=None) locale;
the slow tier runs the acceptance sweep on an 8-device host mesh:
`Locale.workload("sort")` bit-exact vs `jnp.sort` for every policy x backend.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
from jax.sharding import AxisType
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # offline CI image: deterministic shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import (Homed, Homing, Locale, LocalisationPolicy,
                        check_divisible, chunk_bounds)
from repro.core.api import register_workload

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh1():
    return jax.make_mesh((len(jax.devices()),), ("data",),
                         axis_types=(AxisType.Auto,))


# ---------------------------------------------------------------------------
# Locale.put -> Homed round-trips
# ---------------------------------------------------------------------------
@given(st.lists(st.integers(-2**31, 2**31 - 1), min_size=1, max_size=96))
@settings(max_examples=20, deadline=None)
def test_put_roundtrip_preserves_logical_order(vals):
    x = jnp.asarray(vals, jnp.int32)
    for homing in (Homing.LOCAL_CHUNKED, Homing.HASH_INTERLEAVED):
        for mesh in (None, _mesh1()):
            loc = Locale(mesh=mesh, policy=LocalisationPolicy(homing=homing))
            h = loc.put(x)
            assert isinstance(h, Homed) and h.homing == homing
            np.testing.assert_array_equal(np.asarray(h.logical()),
                                          np.asarray(x))


def test_put_pad_strips_like_sort_padding():
    for loc in (Locale(mesh=_mesh1()), Locale(mesh=None)):
        x = jnp.arange(13, dtype=jnp.int32)
        h = loc.put(x, pad=True)
        # pad granule is the axis size (1 here), so content survives intact
        np.testing.assert_array_equal(np.asarray(h.logical())[:13],
                                      np.arange(13))


def test_check_divisible_names_homing_and_sizes():
    with pytest.raises(ValueError, match=r"7 % 8.*pad_to_multiple"):
        check_divisible(7, 8, Homing.HASH_INTERLEAVED, "data")
    with pytest.raises(ValueError, match="local"):
        check_divisible(5, 4, Homing.LOCAL_CHUNKED, "data")


# ---------------------------------------------------------------------------
# Locale.pin: strict no-op without a mesh / under runtime mapping
# ---------------------------------------------------------------------------
@given(st.lists(st.integers(-1000, 1000), min_size=4, max_size=64))
@settings(max_examples=10, deadline=None)
def test_pin_noop_without_mesh_or_static(vals):
    x = jnp.asarray(vals, jnp.float32)
    assert Locale(mesh=None).pin(x) is x
    auto = LocalisationPolicy(static_mapping=False)
    assert Locale(mesh=_mesh1(), policy=auto).pin(x) is x


def test_pin_tree_noop_without_mesh():
    tree = {"k": jnp.zeros((2, 4, 8)), "pos": jnp.zeros((2,))}
    out = Locale(mesh=None).pin_tree(tree, dim=1)
    assert out["k"] is tree["k"] and out["pos"] is tree["pos"]


def test_pin_rejects_mixed_homing():
    loc = Locale(mesh=_mesh1(),
                 policy=LocalisationPolicy(homing=Homing.LOCAL_CHUNKED))
    h = Homed(jnp.arange(8.0), Homing.HASH_INTERLEAVED)
    with pytest.raises(TypeError, match="hash.*local"):
        loc.pin(h)
    # ...but the auto corner stays a strict no-op, mismatch or not
    auto = loc.with_policy(LocalisationPolicy(static_mapping=False,
                                              homing=Homing.LOCAL_CHUNKED))
    assert auto.pin(h) is h


def test_pin_homed_preserves_placed_form():
    """pin(put(x)) must stay shape-compatible with put(x) (same homing)."""
    loc = Locale(mesh=_mesh1(),
                 policy=LocalisationPolicy(homing=Homing.HASH_INTERLEAVED))
    h = loc.put(jnp.arange(12, dtype=jnp.int32))
    h2 = loc.pin(h)
    assert h2.data.shape == h.data.shape
    out = jax.tree.map(lambda a, b: a + b, h, h2)     # no shape mismatch
    np.testing.assert_array_equal(np.asarray(out.logical()),
                                  2 * np.arange(12))


# ---------------------------------------------------------------------------
# Homed: layout metadata travels with the array
# ---------------------------------------------------------------------------
def test_mixed_homing_is_a_tree_structure_error():
    a = Homed(jnp.ones(4), Homing.LOCAL_CHUNKED)
    b = Homed(jnp.ones(4), Homing.HASH_INTERLEAVED)
    with pytest.raises(ValueError):
        jax.tree.map(lambda u, v: u + v, a, b)


def test_homed_passes_through_jit():
    h = Homed(jnp.arange(8.0), Homing.HASH_INTERLEAVED)
    out = jax.jit(lambda v: jax.tree.map(lambda d: d * 2, v))(h)
    assert isinstance(out, Homed) and out.homing == Homing.HASH_INTERLEAVED
    np.testing.assert_allclose(np.asarray(out.logical()),
                               2 * np.arange(8.0))


# ---------------------------------------------------------------------------
# chunk_bounds: ownership math, including m > n (empty tail chunks)
# ---------------------------------------------------------------------------
@given(st.integers(0, 500), st.integers(1, 64))
@settings(max_examples=30, deadline=None)
def test_chunk_bounds_cover_exactly_even_when_m_exceeds_n(n, m):
    bounds = chunk_bounds(n, m)
    assert len(bounds) == m
    covered = [i for lo, hi in bounds for i in range(lo, hi)]
    assert covered == list(range(n)), (n, m)
    if m > n:   # the tail workers own empty chunks, not out-of-range ones
        assert all(lo == hi == n for lo, hi in bounds[n:])


# ---------------------------------------------------------------------------
# workload registry
# ---------------------------------------------------------------------------
def test_unknown_workload_and_backend_rejected():
    with pytest.raises(ValueError, match="unknown workload"):
        Locale().workload("nope")
    with pytest.raises(ValueError, match="unknown backend"):
        Locale().workload("sort", backend="nope")


def test_register_workload_extends_registry():
    @register_workload("_test_double")
    def _double(locale, *, factor=2):
        return locale.jit(lambda x: x * factor, donate=())

    fn = Locale().workload("_test_double", factor=3)
    np.testing.assert_array_equal(np.asarray(fn(jnp.arange(4))),
                                  3 * np.arange(4))


@pytest.mark.parametrize("backend", ["constraint", "shard_map"])
def test_workload_sort_bit_exact_single_device(backend):
    """All 8 policy corners x both backends vs jnp.sort (1-device mesh)."""
    locale = Locale(mesh=_mesh1())
    x0 = jax.random.randint(jax.random.key(0), (513,), -10**6, 10**6,
                            dtype=jnp.int32)
    expect = np.sort(np.asarray(x0))
    for loc in (True, False):
        for static in (True, False):
            for h in (Homing.LOCAL_CHUNKED, Homing.HASH_INTERLEAVED):
                pol = LocalisationPolicy(loc, static, h)
                fn = locale.with_policy(pol).workload(
                    "sort", backend=backend, num_workers=8,
                    local_sort=jnp.sort)
                np.testing.assert_array_equal(np.asarray(fn(jnp.array(x0))),
                                              expect, err_msg=pol.name)


def test_microbench_auto_policy_emits_no_constraints():
    """Satellite regression: the 'leave it to the compiler' baseline must
    not sneak a chunk-contiguous constraint in via localise()."""
    auto = LocalisationPolicy(localised=False, static_mapping=False,
                              homing=Homing.HASH_INTERLEAVED)
    fn = Locale(mesh=_mesh1(), policy=auto).workload("microbench", reps=3)
    def constrains(f):
        # GSPMD lowers a constraint to a @Sharding custom call, Shardy to
        # sdy.sharding_constraint
        txt = f.lower(jnp.linspace(0, 1, 64)).as_text()
        return "Sharding" in txt or "sharding_constraint" in txt

    assert not constrains(fn), "auto baseline leaked a layout constraint"
    # and the static non-localised case still pins layouts
    static = LocalisationPolicy(localised=False, static_mapping=True,
                                homing=Homing.HASH_INTERLEAVED)
    fn = Locale(mesh=_mesh1(), policy=static).workload("microbench", reps=3)
    assert constrains(fn)


# ---------------------------------------------------------------------------
# multi-axis locales: Locale(mesh, axis=("pod", "data")) end-to-end
# ---------------------------------------------------------------------------
def _pod_mesh1():
    """A (1,1,1)-shape (pod, data, model) mesh: the multi-axis *type* paths
    on the single test-process device; real pod shapes run in the slow
    subprocess tests."""
    return jax.make_mesh((1, 1, 1), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)


def test_multi_axis_locale_placement_roundtrips():
    for homing in (Homing.LOCAL_CHUNKED, Homing.HASH_INTERLEAVED):
        loc = Locale(mesh=_pod_mesh1(), axis=("pod", "data"),
                     policy=LocalisationPolicy(homing=homing))
        assert loc.axis_size == 1
        x = jnp.arange(24, dtype=jnp.int32)
        h = loc.put(x)
        assert h.homing == homing and h.axis == ("pod", "data")
        np.testing.assert_array_equal(np.asarray(h.logical()), np.arange(24))
        # pin accepts both raw arrays and Homed under the tuple axis
        pinned = jax.jit(lambda v: loc.pin(v))(x)
        np.testing.assert_array_equal(np.asarray(pinned), np.arange(24))
        np.testing.assert_array_equal(
            np.asarray(jax.jit(lambda v: loc.localise(v))(x)), np.arange(24))


def test_multi_axis_locale_make_and_workloads():
    loc = Locale(mesh=_pod_mesh1(), axis=("pod", "data"))
    born = loc.make((8, 2), lambda idx: np.ones((8, 2), np.float32)[idx])
    assert born.shape == (8, 2)
    x = jax.random.randint(jax.random.key(0), (513,), -10**6, 10**6,
                           dtype=jnp.int32)
    expect = np.sort(np.asarray(x))
    for backend in ("constraint", "shard_map"):
        fn = loc.workload("sort", backend=backend, num_workers=8,
                          local_sort=jnp.sort)
        np.testing.assert_array_equal(np.asarray(fn(jnp.array(x))), expect,
                                      err_msg=backend)
    mb = loc.workload("microbench", reps=2)
    out = mb(jnp.linspace(0, 1, 16))
    assert out.shape == (16,)


# ---------------------------------------------------------------------------
# deprecation shims: removed after two PRs of warnings
# ---------------------------------------------------------------------------
def test_free_function_shims_are_gone():
    """The pre-Locale free functions were deprecation shims for two PRs and
    are now removed; the building blocks live only in their own modules."""
    import repro.core as core
    for name in ("to_layout", "constrain", "logical_view", "localise",
                 "place", "make_sort_fn", "make_engine_fn",
                 "make_microbench_fn"):
        assert not hasattr(core, name), name
        assert name not in core.__all__, name
    # workload discovery sees only the register_workload registry
    assert set(core.workload_names()) >= {"sort", "engine", "microbench"}


# ---------------------------------------------------------------------------
# acceptance: 8-device host mesh, every policy x backend, via the API only
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_workload_sort_8dev_all_policies_both_backends():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import Homing, Locale, LocalisationPolicy
locale = Locale.auto()
assert locale.axis_size == 8
x0 = jax.random.randint(jax.random.key(0), (1 << 13,), -10**6, 10**6,
                        dtype=jnp.int32)
expect = np.sort(np.asarray(x0))
for backend in ["constraint", "shard_map"]:
    for loc in [True, False]:
        for static in [True, False]:
            for h in [Homing.LOCAL_CHUNKED, Homing.HASH_INTERLEAVED]:
                pol = LocalisationPolicy(loc, static, h)
                fn = locale.with_policy(pol).workload(
                    "sort", backend=backend, local_sort=jnp.sort)
                y = np.asarray(fn(jnp.array(x0)))
                np.testing.assert_array_equal(y, expect,
                    err_msg=f"{backend} {pol.name}")
# put/logical round-trip under real 8-way sharding, both homings
for h in [Homing.LOCAL_CHUNKED, Homing.HASH_INTERLEAVED]:
    l = locale.with_policy(LocalisationPolicy(homing=h))
    hm = l.put(jnp.arange(64, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(hm.logical()), np.arange(64))
print("API_8DEV_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": "src"},
                       cwd=ROOT, timeout=900)
    assert "API_8DEV_OK" in r.stdout, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# benchmark surface: --smoke keeps every entry point alive
# ---------------------------------------------------------------------------
def test_benchmarks_smoke_emits_json(tmp_path):
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--smoke", "--skip-local",
         "--out", str(tmp_path)],
        capture_output=True, text=True, cwd=ROOT, timeout=420,
        env={**os.environ, "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stdout + r.stderr
    import json
    import re
    sort = json.load(open(tmp_path / "BENCH_sort.json"))
    micro = json.load(open(tmp_path / "BENCH_microbench.json"))
    assert sort and micro, (sort, micro)
    timed = [rec for rec in sort if rec["us"] is not None]
    assert timed and all(rec["us"] > 0 for rec in timed)
    assert {rec["backend"] for rec in sort} >= {"constraint"}
    assert any(rec["n"] for rec in sort)
    # the --pods grid ran too: BENCH_engine.json carries the per-policy
    # inter/intra-pod exchange-byte totals, and the hierarchical policy
    # moves strictly fewer inter-pod bytes than the flat non-localised path
    engine = json.load(open(tmp_path / "BENCH_engine.json"))

    def inter_total(prefix):
        recs = [r for r in engine
                if prefix in r["name"] and "inter_total=" in r["derived"]]
        assert len(recs) == 1, (prefix, engine)
        return int(re.search(r"inter_total=(\d+)", recs[0]["derived"]).group(1))

    assert inter_total("_hier.") < inter_total("_nonloc-")
    # the serving scheduler ran too: both policies timed, and the recorded
    # acceptance facts hold (bit-identical decode, homed strictly fewer
    # cross-home relayout bytes, homed no more deterministic steps)
    serve = json.load(open(tmp_path / "BENCH_serve.json"))
    assert {r["name"].split("_")[1] for r in serve
            if r["us"] is not None} >= {"fifo", "homed"}
    checks = [r for r in serve if r["name"].startswith("serve_check_")]
    assert checks, serve
    for rec in checks:
        assert "bit_identical=True" in rec["derived"], rec
        assert "relayout_homed_lt_fifo=True" in rec["derived"], rec
        assert "steps_homed_le_fifo=True" in rec["derived"], rec
