"""Core localisation/sort/microbench correctness (single device + property)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # offline CI image: deterministic shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import (Homing, LocalisationPolicy, chunk_bounds,
                        distributed_merge_sort, merge_sorted,
                        repetitive_copy)
from repro.core.microbench import reference as micro_reference
from repro.configs.paper_sort import CASES


def test_chunk_bounds_cover_exactly():
    for n, m in [(100, 8), (64, 8), (1000, 63), (7, 8)]:
        bounds = chunk_bounds(n, m)
        covered = []
        for lo, hi in bounds:
            covered.extend(range(lo, hi))
        assert covered == list(range(n)), (n, m)


@given(st.lists(st.integers(-2**31, 2**31 - 1), min_size=0, max_size=200),
       st.lists(st.integers(-2**31, 2**31 - 1), min_size=0, max_size=200))
@settings(max_examples=30, deadline=None)
def test_merge_sorted_property(a, b):
    a = jnp.sort(jnp.asarray(a, jnp.int32))
    b = jnp.sort(jnp.asarray(b, jnp.int32))
    out = np.asarray(merge_sorted(a, b))
    expect = np.sort(np.concatenate([np.asarray(a), np.asarray(b)]),
                     kind="stable")
    np.testing.assert_array_equal(out, expect)


@pytest.mark.slow
@given(st.integers(0, 2**32 - 1), st.sampled_from([64, 256, 1024]),
       st.sampled_from([2, 4, 8]))
@settings(max_examples=20, deadline=None)
def test_distributed_sort_property(seed, n, m):
    x = jax.random.randint(jax.random.key(seed), (n,), -10**6, 10**6,
                           dtype=jnp.int32)
    out = np.asarray(distributed_merge_sort(x, mesh=None, num_workers=m))
    xs = np.sort(np.asarray(x))
    np.testing.assert_array_equal(out, xs)       # sorted AND a permutation


# fast lane keeps the bench-featured corners (1, 3, 7, 8); tier-1 runs all 8
@pytest.mark.parametrize("case", [
    pytest.param(c, marks=() if c in (1, 3, 7, 8) else (pytest.mark.slow,))
    for c in sorted(CASES)])
def test_all_table1_cases_same_result(case):
    c = CASES[case]
    policy = LocalisationPolicy(localised=c.localised,
                                static_mapping=c.static_mapping,
                                homing=Homing(c.homing))
    x = jax.random.randint(jax.random.key(0), (512,), 0, 10**6, jnp.int32)
    out = np.asarray(distributed_merge_sort(x, mesh=None, policy=policy,
                                            num_workers=8))
    np.testing.assert_array_equal(out, np.sort(np.asarray(x)))


def test_microbench_matches_reference():
    x = jnp.linspace(0.0, 1.0, 256, dtype=jnp.float32)
    for pol in [LocalisationPolicy(localised=True),
                LocalisationPolicy(localised=False,
                                   homing=Homing.HASH_INTERLEAVED)]:
        y = repetitive_copy(x, 7, mesh=None, policy=pol)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(micro_reference(x, 7)),
                                   rtol=1e-6)


@pytest.mark.slow
def test_sort_multidevice_subprocess():
    """8 host devices: all cases produce the sorted array under real sharding."""
    import subprocess, sys, os
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.core import LocalisationPolicy, Homing, distributed_merge_sort
from repro.core.microbench import repetitive_copy, reference
mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
x = jax.random.randint(jax.random.key(1), (1 << 14,), 0, 1 << 30, jnp.int32)
expect = np.sort(np.asarray(x))
for loc in [True, False]:
    for st_ in [True, False]:
        for h in [Homing.LOCAL_CHUNKED, Homing.HASH_INTERLEAVED]:
            p = LocalisationPolicy(loc, st_, h)
            y = distributed_merge_sort(x, mesh=mesh, policy=p)
            np.testing.assert_array_equal(np.asarray(y), expect), p
xf = jnp.linspace(0, 1, 1 << 14, dtype=jnp.float32)
for p in [LocalisationPolicy(True), LocalisationPolicy(False, True, Homing.HASH_INTERLEAVED)]:
    np.testing.assert_allclose(np.asarray(repetitive_copy(xf, 5, mesh, p)),
                               np.asarray(reference(xf, 5)), rtol=1e-5)
print("MULTIDEV_OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=600)
    assert "MULTIDEV_OK" in r.stdout, r.stdout + r.stderr
