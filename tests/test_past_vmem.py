"""The engine's local phase past VMEM: a chunk larger than one `local_sort`
holds is sorted as VMEM-sized runs in one `local_sort` call, then merged in
HBM by the block bitonic network over run index, one `run_merge` call per
substage.

The VMEM bounds are shrunk by patching the engine's own bound functions
(`max_chunk`, `max_run`), so runs of one tile exercise every path on the
CPU, where the kernels run in the Pallas interpreter.  Every result is
compared with `numpy.sort`, the plain reference of the sort cells.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.engine as engine
from repro.analysis.vmem import pallas_footprints
from repro.core import Locale, LocalisationPolicy, exchange_schedule
from repro.kernels.bitonic_sort import LANES, TILE
from repro.kernels.merge_split import HALF_CLEAN_ROWS, run_merge
from repro.obs import Tracer, set_tracer
from repro.obs.reconcile import check_engine, check_schema

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
I32 = np.iinfo(np.int32)
RUN = TILE                               # the shrunk run: one (8, 128) tile


@pytest.fixture
def small_vmem(monkeypatch):
    """One local sort holds a single run of RUN keys."""
    monkeypatch.setattr(engine, "max_chunk", lambda: RUN)
    monkeypatch.setattr(engine, "max_run", lambda: RUN)


def _keys(name: str, n: int, seed: int = 0):
    g = np.random.default_rng([n, seed])
    if name == "uniform":                # the whole int32 range, both ends
        x = g.integers(I32.min, I32.max, n, dtype=np.int64, endpoint=True)
        x[g.integers(0, n, 8)] = I32.max     # the sentinel's value as data
        x[g.integers(0, n, 8)] = I32.min
        return x.astype(np.int32)
    if name == "ties":
        return g.integers(-3, 3, n).astype(np.int32)
    if name == "all_max":                # every key equals the sentinel
        return np.full(n, I32.max, np.int32)
    if name == "all_equal":
        return np.full(n, -7, np.int32)
    if name == "float_zeros":            # -0.0 and +0.0 tie; +-inf as data
        x = g.standard_normal(n).astype(np.float32)
        x[::3], x[1::5] = 0.0, -0.0
        x[2::11], x[3::13] = np.inf, -np.inf
        return x
    raise AssertionError(name)


def _sort(x):
    fn = Locale().workload("sort", backend="shard_map", local_phase="pallas")
    return np.asarray(fn(jnp.asarray(x)))


def _bits(x):
    return np.asarray(x).view(np.int32)


def _expect_bits(x):
    """numpy's sort as bits, with -0.0 before +0.0 (the keys' total order)."""
    if x.dtype != np.float32:
        return np.sort(x)
    k = x.view(np.int32)
    flip = lambda v: v ^ ((v >> 31) & I32.max)   # noqa: E731
    return flip(np.sort(flip(k)))


# runs: 1 (within the bound: today's single local_sort), then 2, 3, 12 and
# 16 runs, the last one partial except for one exact 16
SIZES = {1: RUN - 100, 2: RUN + 1, 3: 2 * RUN + 300, 12: 11 * RUN + 517,
         16: 15 * RUN + 9, "16 whole": 16 * RUN}


@pytest.mark.usefixtures("small_vmem")
@pytest.mark.parametrize("runs", list(SIZES))
def test_past_vmem_matches_numpy_sort(runs):
    x = _keys("uniform", SIZES[runs])
    got = _sort(x.copy())
    np.testing.assert_array_equal(got, np.sort(x))


@pytest.mark.usefixtures("small_vmem")
@pytest.mark.parametrize("name", ["ties", "all_max", "all_equal",
                                  "float_zeros"])
@pytest.mark.parametrize("runs", [3, 12])
def test_past_vmem_ties_extremes_and_floats(name, runs):
    x = _keys(name, SIZES[runs])
    got = _sort(x.copy())
    np.testing.assert_array_equal(got, np.sort(x))
    np.testing.assert_array_equal(_bits(got), _expect_bits(x))


def test_run_merge_past_one_half_clean_block():
    """Rows of two half-clean register blocks, each row's partner below or
    above it under either keep flag (the last run all sentinels): every row
    is bit for bit the kept half of the merge of its pair."""
    L = 2 * HALF_CLEAN_ROWS * LANES
    x = np.sort(np.stack([_keys("uniform", L, 0), _keys("ties", L, 1),
                          _keys("uniform", L, 2), _keys("all_max", L)]),
                axis=-1)
    partner = np.array([1, 0, 3, 2])     # below, above, below, above
    keep = np.array([True, False, False, True])
    got = np.asarray(run_merge(jnp.asarray(x), partner, keep))
    for r in range(4):
        pair = np.sort(np.concatenate([x[r], x[partner[r]]]))
        np.testing.assert_array_equal(got[r], pair[:L] if keep[r] else pair[L:],
                                      err_msg=f"row {r}")


@pytest.mark.parametrize("runs, p, substages",
                         [(1, 1, 0), (2, 2, 1), (3, 4, 3), (12, 16, 10),
                          (16, 16, 10), (17, 32, 15)])
def test_run_network_is_the_exchange_networks(runs, p, substages):
    """The run network's partner and keep tables are the exchange
    network's over the same power of two, and the bitonic ones."""
    net = engine.run_network(runs)
    assert net.m == p and len(net.levels) == substages
    same = engine.exchange_network(LocalisationPolicy(), (p,))
    d = np.arange(p)
    for ex, ref in zip(net.levels, same.levels):
        assert (ex.partner, ex.keep_low) == (ref.partner, ref.keep_low)
        i, j = ex.stage, ex.substage
        np.testing.assert_array_equal(ex.partner, d ^ (1 << j))
        np.testing.assert_array_equal(
            ex.keep_low, (((d >> j) & 1) == 0) == (((d >> (i + 1)) & 1) == 0))
    assert [(ex.stage, ex.substage) for ex in net.levels] == \
        [(i, j) for i in range(p.bit_length() - 1) for j in range(i, -1, -1)]


@pytest.mark.usefixtures("small_vmem")
@pytest.mark.parametrize("runs", [2, 12])
def test_schedule_records_are_the_calls_made(runs):
    """exchange_schedule's local records name the kernels the program
    calls, in order, priced per run; the traced call reconciles."""
    n = SIZES[runs]
    x = _keys("uniform", n)
    fn = Locale().workload("sort", backend="shard_map", local_phase="pallas")
    jx = jax.make_jaxpr(fn.__wrapped__)(jnp.asarray(x))
    calls = [f.name for f in pallas_footprints(jx)]
    net = engine.run_network(runs)
    assert calls == ["local_sort"] + ["run_merge"] * len(net.levels)
    sched = exchange_schedule(n, (1,), LocalisationPolicy())
    assert [r["op"] for r in sched] == calls
    assert all(r["level"] == 0 for r in sched)
    assert sched[0]["local_hbm_bytes"] == 2 * runs * RUN * 4
    assert sched[0]["local_merge_elems"] == runs * RUN
    for r in sched[1:]:
        assert r["local_hbm_bytes"] == 3 * net.m * RUN * 4
        assert r["local_merge_elems"] == net.m * RUN
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        y = np.asarray(fn(jnp.asarray(x)))
    finally:
        set_tracer(prev)
    np.testing.assert_array_equal(y, np.sort(x))
    levels = [r["args"] for r in tr.records()
              if r["name"] == "engine.exchange_level"]
    assert [lv["op"] for lv in levels] == calls
    check_schema(tr.records())
    check_engine(tr.records())


@pytest.mark.usefixtures("small_vmem")
def test_past_vmem_counters_move_once_per_build():
    x = _keys("ties", SIZES[12])
    fn = Locale().workload("sort", backend="shard_map", local_phase="pallas")
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        for _ in range(2):
            np.asarray(fn(jnp.asarray(x)))
    finally:
        set_tracer(prev)
    assert tr.total("sort.builds") == 1
    assert tr.total("sort.runs") == 12
    assert tr.total("sort.run_merge_substages") == 10
    # within the bound the counters stay still
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        np.asarray(fn(jnp.asarray(x[:RUN])))
    finally:
        set_tracer(prev)
    assert tr.total("sort.builds") == 1
    assert tr.total("sort.runs") == tr.total("sort.run_merge_substages") == 0


def test_past_vmem_path_is_chosen_by_chunk_size_alone():
    run = engine.max_run()
    assert engine.run_len(engine.max_chunk()) == engine.max_chunk()
    assert engine.run_len(engine.max_chunk() + 1) == run
    # two runs fit VMEM in the merge: half of what one local sort holds
    assert 2 * run == engine.max_chunk()
    # the paper's 100M keys: 12 runs of 2^23 keys in a network of 16
    assert run == 1 << 23
    assert -(-100_000_000 // run) == 12
    assert engine.run_network(12).m == 16


def test_past_vmem_on_more_than_one_device_is_refused():
    with pytest.raises(ValueError, match="one device only"):
        exchange_schedule(4 * (engine.max_chunk() + 1024), (4,),
                          LocalisationPolicy())
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax.numpy as jnp
import repro.core.engine as engine
from repro.core import Locale
engine.max_chunk = engine.max_run = lambda: 1024
fn = Locale.auto().workload("sort", backend="shard_map",
                            local_phase="pallas")
assert fn(jnp.arange(4096, 0, -1, dtype=jnp.int32))[0] == 1
try:
    fn(jnp.arange(4 * 2048, dtype=jnp.int32))
except ValueError as e:
    assert "one device only" in str(e), e
    print("REFUSED")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": "src"},
                       cwd=ROOT, timeout=300)
    assert "REFUSED" in r.stdout, r.stdout + r.stderr
