"""VMEM-resident local phase: fused `local_sort` + bitonic `merge_split`.

Property grid pins the two kernels bit-exact against their jnp oracles
(`jnp.sort` rows; `merge_sorted`-then-slice) across duplicates, BIG/inf
sentinel values appearing as *data*, already/reverse-sorted inputs, both
core dtypes and non-power-of-two lengths/leaf counts (the in-VMEM sentinel
padding path).  The engine is then pinned bit-exact under both
``local_phase`` implementations, fast on the 1-device mesh and (slow) on
8-device flat + emulated-pod meshes.  On the CPU the kernels run in the
Pallas interpreter; `test_chip_compile.py` lowers them for a TPU v5e.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.sharding import AxisType

from repro.core import (LOCAL_PHASES, Homing, Locale, LocalisationPolicy,
                        exchange_schedule)
from repro.core.sort import merge_sorted
from repro.kernels import ops
from repro.kernels.bitonic_sort import (BLOCK_ROWS, LANES, SUBLANES, TILE,
                                        block_keys, from_keys, sweep_plan,
                                        to_keys)
from repro.kernels.local_sort import local_sort
from repro.kernels.merge_split import (HALF_CLEAN_ROWS, _reverse,
                                       half_clean_blocks)
from repro.obs import Tracer, set_tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:                 # for the in-process benchmark tests
    sys.path.insert(0, ROOT)
BIGI = int(jnp.iinfo(jnp.int32).max)


def _rows(name: str, C: int, rows: int = 3):
    """One property-grid corner: (rows, C) arrays worth sorting."""
    key = jax.random.key(C * 31 + rows)
    if name == "dups_int":               # heavy duplicates, int32
        return jax.random.randint(key, (rows, C), -4, 4, dtype=jnp.int32)
    if name == "rand_int":
        return jax.random.randint(key, (rows, C), -10**6, 10**6,
                                  dtype=jnp.int32)
    if name == "sentinel_int":           # BIG sentinel present as real data
        x = jax.random.randint(key, (rows, C), -9, 9, dtype=jnp.int32)
        return x.at[:, ::3].set(BIGI)
    if name == "rand_float":
        return jax.random.normal(key, (rows, C), jnp.float32)
    if name == "sentinel_float":         # +/-inf present as real data
        x = jax.random.normal(key, (rows, C), jnp.float32)
        return x.at[:, ::5].set(jnp.inf).at[:, 1::7].set(-jnp.inf)
    if name == "signed_zeros":           # -0.0 and +0.0 tie as values
        x = jax.random.normal(key, (rows, C), jnp.float32)
        return x.at[:, ::3].set(0.0).at[:, 1::4].set(-0.0)
    if name == "sorted":
        return jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (rows, C))
    if name == "reversed":
        return jnp.broadcast_to(jnp.arange(C, 0, -1, dtype=jnp.int32),
                                (rows, C))
    raise AssertionError(name)


def _bits(x):
    """Keys as raw 32-bit patterns (floats compare by bits, not value)."""
    return np.asarray(x).view(np.int32)


GRID_NAMES = ("dups_int", "rand_int", "sentinel_int", "rand_float",
              "sentinel_float", "signed_zeros", "sorted", "reversed")
# C=96 -> 3 leaves of 32 (non-power-of-two leaf count), C=1/5/257 ->
# in-VMEM sentinel padding, C=256 -> the clean power-of-two lane
GRID_C = (1, 5, 96, 256, 257)


# ---------------------------------------------------------------------------
# local_sort: fused leaf sorts + merge tree, one VMEM pass
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", GRID_NAMES)
@pytest.mark.parametrize("C", GRID_C)
def test_local_sort_matches_jnp_sort(name, C):
    x = _rows(name, C)
    out = np.asarray(ops.local_sort(x))
    np.testing.assert_array_equal(out, np.sort(np.asarray(x), axis=-1))
    # a permutation of the input bits: no -0.0/+0.0 or tie is duplicated
    np.testing.assert_array_equal(np.sort(_bits(out), axis=-1),
                                  np.sort(_bits(x), axis=-1))


def test_local_sort_keeps_real_sentinels_with_padding():
    """A BIG-valued *data* element must survive the in-VMEM pad+strip."""
    x = jnp.asarray([[5, BIGI, -3, 1, 2]], jnp.int32)       # C=5 -> pads to 8
    np.testing.assert_array_equal(np.asarray(ops.local_sort(x))[0],
                                  np.asarray([-3, 1, 2, 5, BIGI]))
    xf = jnp.asarray([[jnp.inf, 0.5, -jnp.inf]], jnp.float32)
    np.testing.assert_array_equal(np.asarray(ops.local_sort(xf))[0],
                                  np.asarray([-np.inf, 0.5, np.inf],
                                             np.float32))


# ---------------------------------------------------------------------------
# the register-blocked network: its sweep plan, and sizes past one block
# ---------------------------------------------------------------------------
def _network(L: int):
    """Every (k, j) substage of the bitonic network on L keys, in order."""
    out, k = [], 2
    while k <= L:
        j = k // 2
        while j >= 1:
            out.append((k, j))
            j //= 2
        k *= 2
    return out


def _in_registers(plan, B):
    return sum(len(s) for s in plan if s[0][1] < B)


@pytest.mark.parametrize("block_rows", (8, 32, 64, 128, 256))
def test_sweep_plan_applies_every_substage_once_in_order(block_rows):
    for e in range(TILE.bit_length() - 1, 25):          # one tile .. 2^24
        L = 1 << e
        plan = sweep_plan(L, block_rows)
        assert [sub for sweep in plan for sub in sweep] == _network(L), L
        B = block_keys(L, block_rows)
        for sweep in plan:        # a row sweep: one stride past the block
            strides = [j for _, j in sweep]
            assert (len(sweep) == 1 and strides[0] >= B
                    or max(strides) < B), (L, sweep)


@pytest.mark.parametrize("block_rows, sweeps, iterations, row_iterations, "
                         "in_registers",
                         [(8, 105, None, None, 185),    # one tile a block
                          (64, 66, 39424, 28160, 221),
                          (128, 55, 16640, 11520, 231),
                          (256, 45, 6912, 4608, 240),
                          (512, 36, 2816, 1792, 248)])
def test_sweep_plan_counts_at_2_23_keys(block_rows, sweeps, iterations,
                                        row_iterations, in_registers):
    L = 1 << 23
    plan = sweep_plan(L, block_rows)
    B = block_keys(L, block_rows)
    rows = [s for s in plan if s[0][1] >= B]
    assert len(plan) == sweeps
    assert _in_registers(plan, B) == in_registers
    assert len(_network(L)) == 276
    if iterations is not None:    # a block a block-sweep step, two a row's
        row_iters = len(rows) * (L // (2 * B))
        assert row_iters == row_iterations
        assert (len(plan) - len(rows)) * (L // B) + row_iters == iterations


BLOCK = BLOCK_ROWS * LANES               # keys in one register block


@pytest.mark.parametrize("name, C, rows", [
    ("rand_int", 1 << 15, 2),            # cross-register strides
    ("rand_int", 2 * BLOCK, 2),          # and a row sweep
    ("rand_int", 4 * BLOCK, 1),
    ("dups_int", 2 * BLOCK, 2),
    ("signed_zeros", 2 * BLOCK + 37, 1)])    # float, sentinel tail
def test_local_sort_past_one_register_block(name, C, rows):
    x = _rows(name, C, rows)
    out = local_sort(x)
    # bit-exact: floats in total order, -0.0 before +0.0
    flip = (lambda k: k ^ ((k >> 31) & BIGI)) if x.dtype == jnp.float32 \
        else (lambda k: k)
    np.testing.assert_array_equal(_bits(out),
                                  flip(np.sort(flip(_bits(x)), axis=-1)))


HALF_CLEAN = HALF_CLEAN_ROWS * LANES     # keys in one half-clean block


@jax.jit
@jax.vmap
def _merges(a, b):
    """Row-wise `merge_sorted` of the values, and of the keys (as values)."""
    return (merge_sorted(a, b),
            from_keys(merge_sorted(to_keys(a), to_keys(b)), a.dtype))


def _sorted_rows(name: str, C: int, rows: int, seed: int):
    """Sorted rows from numpy, floats in the keys' total order (-0.0 before
    +0.0): ``tied_zeros`` floats of eight values with both zeros among them,
    ``dups_int`` int32 of eight values, ``rand_int`` int32 of many."""
    g = np.random.default_rng([C, rows, seed])
    if name != "tied_zeros":
        hi = 10**6 if name == "rand_int" else 4
        return np.sort(g.integers(-hi, hi, (rows, C)).astype(np.int32), -1)
    x = (g.integers(-4, 4, (rows, C)) / 2).astype(np.float32)
    x[:, 1::4] = -0.0
    flip = lambda k: k ^ ((k >> 31) & BIGI)     # noqa: E731
    return flip(np.sort(flip(x.view(np.int32)), -1)).view(np.float32)


@pytest.mark.parametrize("name, C", [
    ("tied_zeros", TILE),                # shorter than one half-clean block
    ("tied_zeros", 1 << 12),
    ("tied_zeros", HALF_CLEAN),          # 1, 2 and 4 half-clean blocks
    ("tied_zeros", 2 * HALF_CLEAN),
    ("tied_zeros", 4 * HALF_CLEAN),
    ("dups_int", 2 * BLOCK)])            # two network blocks: a row sweep
def test_merge_split_past_one_register_block(name, C):
    rows = 2
    a = _sorted_rows("rand_int" if name == "dups_int" else name, C, rows, 0)
    b = _sorted_rows(name, C, rows, 1)
    full, keys = (np.asarray(m) for m in _merges(a, b))
    for keep in ([True, False], [False, True]):
        out = np.asarray(ops.merge_split(a, b, jnp.asarray(keep)))
        for r, lo in enumerate(keep):
            half = slice(None, C) if lo else slice(C, None)
            np.testing.assert_array_equal(out[r], full[r, half])
            # bit for bit: the merge of the keys, -0.0 before +0.0
            np.testing.assert_array_equal(_bits(out[r]), _bits(keys[r, half]))


@pytest.mark.parametrize("n", (1, 2, 8, HALF_CLEAN_ROWS // SUBLANES))
def test_blocked_reverse_is_numpy_reverse(n):
    """`_reverse` of an (n, 8, 128) block: the flattened keys back to
    front, the register order by renaming and every tile in registers."""
    shape = (n * SUBLANES, LANES)
    x = jax.random.permutation(jax.random.key(n),
                               jnp.arange(n * TILE, dtype=jnp.int32))

    def kernel(x_ref, o_ref):
        v = x_ref[...].reshape(n, SUBLANES, LANES)
        o_ref[...] = _reverse(v).reshape(shape)

    got = pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(shape,
                                                                jnp.int32),
                         interpret=True)(x.reshape(shape))
    np.testing.assert_array_equal(np.asarray(got).reshape(-1),
                                  np.asarray(x)[::-1])


@pytest.mark.parametrize("L, blocks", [
    (TILE, 1), (1 << 12, 1), (HALF_CLEAN, 1), (2 * HALF_CLEAN, 2),
    (4 * HALF_CLEAN, 4),
    (1 << 23, 256)])                     # a run of the 100M-key sort
def test_half_clean_blocks(L, blocks):
    """The half-clean pass makes one loop iteration per register block of
    ``HALF_CLEAN_ROWS`` rows, or one for a row shorter than a block."""
    assert half_clean_blocks(L) == blocks


@pytest.mark.parametrize("L", (TILE, 1 << 15, 1 << 23))
def test_local_sort_counts_the_sweep_plan(L):
    rows = 2
    tr = Tracer()
    prev = set_tracer(tr)
    try:                                  # trace only: the counts are static
        jax.make_jaxpr(local_sort)(jax.ShapeDtypeStruct((rows, L), jnp.int32))
    finally:
        set_tracer(prev)
    plan = sweep_plan(L)
    assert tr.total("local_sort.sweeps") == rows * len(plan)
    assert (tr.total("local_sort.register_substages")
            == rows * _in_registers(plan, block_keys(L)))


# ---------------------------------------------------------------------------
# merge_split: only the kept half, bit-exact vs merge_sorted + slice
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", GRID_NAMES)
@pytest.mark.parametrize("C", GRID_C)
def test_merge_split_matches_merge_sorted_slice(name, C):
    rows = 4
    a = jnp.sort(_rows(name, C, rows), axis=-1)
    b = jnp.sort(_rows(name, C, rows)[::-1], axis=-1)
    keep = (jnp.arange(rows) % 2) == 0               # mixed per-row flags
    out = np.asarray(ops.merge_split(a, b, keep))
    rest = np.asarray(ops.merge_split(a, b, ~keep))
    for r in range(rows):
        full = np.asarray(merge_sorted(a[r], b[r]))
        expect = full[:C] if bool(keep[r]) else full[C:]
        np.testing.assert_array_equal(out[r], expect,
                                      err_msg=f"{name} C={C} row={r}")
        # the two halves together are a permutation of both runs' bits
        np.testing.assert_array_equal(
            np.sort(_bits(np.concatenate([out[r], rest[r]]))),
            np.sort(_bits(np.concatenate([a[r], b[r]]))))


def test_merge_split_scalar_flag_and_tie_stability():
    """Scalar keep flag broadcasts; duplicate ties split exactly as the
    stable rank merge does (a-elements before equal b-elements)."""
    a = jnp.asarray([[1, 2, 2, 7]], jnp.int32)
    b = jnp.asarray([[2, 2, 3, 7]], jnp.int32)
    full = np.asarray(merge_sorted(a[0], b[0]))
    for keep in (True, False):
        got = np.asarray(ops.merge_split(a, b, jnp.asarray(keep)))[0]
        np.testing.assert_array_equal(got, full[:4] if keep else full[4:])


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="compiled kernels need a TPU; the CPU runs the "
                           "interpreter (test_chip_compile lowers them)")
@pytest.mark.parametrize("name", ("rand_int", "dups_int", "signed_zeros"))
@pytest.mark.parametrize("C", (128, 256, 1000))
def test_kernels_compiled_mode_matches_interpret(name, C):
    """Below one (8, 128) tile and off a power of two: the compiled padding
    paths (HBM pad, in-VMEM sentinel prologue) equal the interpreter."""
    from repro.kernels.local_sort import local_sort
    from repro.kernels.merge_split import merge_split
    x = _rows(name, C)
    got = local_sort(x, interpret=False)
    np.testing.assert_array_equal(_bits(got),
                                  _bits(local_sort(x, interpret=True)))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.sort(np.asarray(x), axis=-1))
    a = jnp.sort(_rows(name, C), axis=-1)
    b = jnp.sort(_rows(name, C)[::-1], axis=-1)
    keep = jnp.asarray([True, False, True])
    np.testing.assert_array_equal(
        _bits(merge_split(a, b, keep, interpret=False)),
        _bits(merge_split(a, b, keep, interpret=True)))


# ---------------------------------------------------------------------------
# the engine under both local_phase implementations
# ---------------------------------------------------------------------------
ENGINE_POLICIES = [LocalisationPolicy(True, True, Homing.LOCAL_CHUNKED),
                   LocalisationPolicy(True, True, Homing.HASH_INTERLEAVED),
                   LocalisationPolicy(False, True, Homing.HASH_INTERLEAVED)]


@pytest.mark.parametrize("local_phase", LOCAL_PHASES)
@pytest.mark.parametrize("policy", ENGINE_POLICIES,
                         ids=lambda p: p.name)
def test_engine_single_device_bit_exact_per_phase(policy, local_phase):
    """1-device mesh, n=1000 => padded chunk, non-power-of-two leaves;
    float keys with signed zeros equal as values and as a bit permutation."""
    mesh = jax.make_mesh((len(jax.devices()),), ("data",),
                         axis_types=(AxisType.Auto,))
    fn = Locale(mesh=mesh, policy=policy).workload(
        "engine", num_workers=8, local_phase=local_phase)
    signed_zeros = _rows("signed_zeros", 777, rows=1)[0]
    for x in (jax.random.randint(jax.random.key(1000), (1000,), -10**5, 10**5,
                                 dtype=jnp.int32),
              jax.random.normal(jax.random.key(513), (513,), jnp.float32),
              signed_zeros):
        x = np.asarray(x)                 # the engine donates its input
        expect = np.sort(x)
        got = np.asarray(fn(jnp.asarray(x)))
        np.testing.assert_array_equal(got, expect,
                                      err_msg=f"{policy.name} {local_phase}")
        # -0.0 and +0.0 tie as values; their bits are kept, not rewritten
        np.testing.assert_array_equal(np.sort(_bits(got)),
                                      np.sort(_bits(x)))


def test_local_phase_validation():
    with pytest.raises(ValueError, match="local_phase"):
        Locale().workload("engine", local_phase="nope")
    # a callable leaf sort cannot run inside the fused kernel
    with pytest.raises(ValueError, match="callable"):
        Locale().workload("engine", local_sort=jnp.sort,
                          local_phase="pallas")
    # the constraint tree has no kernel local phase
    with pytest.raises(ValueError, match="shard_map"):
        Locale().workload("sort", backend="constraint", local_phase="pallas")
    # "reference" is the constraint tree's nature: accepted as a no-op
    fn = Locale().workload("sort", backend="constraint",
                           local_phase="reference", num_workers=4)
    x = jnp.asarray([3, 1, 2], jnp.int32)
    np.testing.assert_array_equal(np.asarray(fn(x)), [1, 2, 3])


@pytest.mark.slow
def test_engine_8dev_and_pods_bit_exact_both_phases():
    """Acceptance: flat 8-device and (2,4,1) emulated-pod meshes, all
    localised policies (incl. hierarchical — the batched merge_split
    replay), pallas vs reference, bit-identical to jnp.sort."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import Homing, Locale, LocalisationPolicy
from repro.launch.mesh import make_host_mesh
flat = Locale.auto()
pods = Locale(mesh=make_host_mesh(n_pods=2, n_data=4, n_model=1),
              axis=("pod", "data"))
grids = [(flat, [LocalisationPolicy(True, True, Homing.LOCAL_CHUNKED),
                 LocalisationPolicy(True, True, Homing.HASH_INTERLEAVED),
                 LocalisationPolicy(False, True, Homing.HASH_INTERLEAVED)]),
         (pods, [LocalisationPolicy.hierarchical(),
                 LocalisationPolicy.hierarchical(inner="hash"),
                 LocalisationPolicy(True, True, Homing.LOCAL_CHUNKED)])]
for locale, pols in grids:
    for pol in pols:
        for phase in ("pallas", "reference"):
            for n, dt in [(1 << 13, jnp.int32), (5000, jnp.float32)]:
                if dt == jnp.int32:
                    x = jax.random.randint(jax.random.key(1), (n,), -10**6,
                                           10**6, dtype=dt)
                else:
                    x = jax.random.normal(jax.random.key(1), (n,), dt)
                expect = np.asarray(jnp.sort(x))
                fn = locale.with_policy(pol).workload(
                    "sort", backend="shard_map", local_phase=phase)
                np.testing.assert_array_equal(np.asarray(fn(x)), expect,
                    err_msg=f"{pol.name} {phase} {n}")
print("PHASES_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": "src"},
                       cwd=ROOT, timeout=900)
    assert "PHASES_OK" in r.stdout, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# exchange_schedule: the local half of the byte model
# ---------------------------------------------------------------------------
def test_schedule_prices_pallas_local_phase_strictly_cheaper():
    n = 1 << 13
    for sizes in [(8,), (2, 4), (4, 2)]:
        pols = [LocalisationPolicy(),
                LocalisationPolicy(True, True, Homing.HASH_INTERLEAVED)]
        if len(sizes) > 1:
            pols.append(LocalisationPolicy.hierarchical())
        for pol in pols:
            pal = exchange_schedule(n, sizes, pol, local_phase="pallas")
            ref = exchange_schedule(n, sizes, pol, local_phase="reference")
            tot = lambda s, k: sum(r[k] for r in s)
            # the collective half of the schedule is phase-independent
            coll = lambda s: [(r["level"], r["op"], r["inter_pod_bytes"],
                               r["intra_pod_bytes"]) for r in s
                              if r["op"] in ("ppermute", "all_gather",
                                             "all_to_all")]
            assert coll(pal) == coll(ref)
            # the local half is strictly cheaper fused: one VMEM round trip
            # for the whole tree, and only the kept half of every split
            assert tot(pal, "local_hbm_bytes") < tot(ref, "local_hbm_bytes")
            assert tot(pal, "local_merge_elems") < \
                tot(ref, "local_merge_elems"), (sizes, pol.name)
            # every merge_split computes exactly half the reference elems
            for rp, rr in zip(pal, ref):
                assert rp["op"] == rr["op"] and rp["level"] == rr["level"]
                if rp["op"] == "merge_split":
                    assert 2 * rp["local_merge_elems"] == \
                        rr["local_merge_elems"]
                # local ops move no collective bytes, and vice versa
                assert (rp["local_hbm_bytes"] == 0) or \
                    (rp["inter_pod_bytes"] == 0 and
                     rp["intra_pod_bytes"] == 0)


def test_schedule_nonlocalised_local_cost_phase_independent():
    """No fused path without ownership: gathers interleave every level."""
    pol = LocalisationPolicy(False, True, Homing.LOCAL_CHUNKED)
    pal = exchange_schedule(1 << 12, (8,), pol, local_phase="pallas")
    ref = exchange_schedule(1 << 12, (8,), pol, local_phase="reference")
    assert pal == ref
    ops_seen = [r["op"] for r in pal]
    assert ops_seen[:2] == ["all_gather", "local_sort"]
    assert ops_seen.count("merge") == 3              # log2(8) tree levels


# ---------------------------------------------------------------------------
# satellites: benchmark capture + regression gate
# ---------------------------------------------------------------------------
def test_bench_kernels_capture_reaches_json_records():
    """run.py's LOCAL capture: kernel rows must reach parse_records (they
    used to be printed uncaptured, so BENCH_kernels.json could never fill)."""
    from benchmarks.run import JSON_FILES, parse_records, run_local
    out = run_local("bench_kernels",
                    ["--only", "local,merge", "--chunks", "1", "--logcs", "6"])
    recs = parse_records(out)
    names = {r["name"] for r in recs}
    assert any(n.startswith("kernel_local_fused_") for n in names), out
    assert any(n.startswith("kernel_merge_split_") for n in names), out
    prefixes = JSON_FILES["BENCH_kernels.json"]
    assert all(any(r["name"].startswith(p) for p in prefixes) for r in recs)


def test_compare_flags_synthetic_regression(tmp_path):
    import json
    base = [{"name": "sort_x", "us": 100.0}, {"name": "sort_y", "us": 80.0},
            {"name": "structure_only", "us": None}]
    new = [{"name": "sort_x", "us": 150.0}, {"name": "sort_y", "us": 70.0},
           {"name": "structure_only", "us": None}]
    bp, np_ = tmp_path / "base.json", tmp_path / "new.json"
    bp.write_text(json.dumps(base))
    np_.write_text(json.dumps(new))
    from benchmarks.compare import main as compare_main
    # 50% regression on sort_x: above a 10% gate -> fail, above 60% -> pass
    assert compare_main([str(bp), str(np_), "--fail-above", "10"]) == 1
    assert compare_main([str(bp), str(np_), "--fail-above", "60"]) == 0
    assert compare_main([str(bp), str(np_)]) == 0    # no gate, report only
