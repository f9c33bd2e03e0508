"""The sort-100m-1chip cell: its reader on hand-made traces whose answers are
known, and its window rehearsed on the CPU through the harness's own
functions, with the engine's VMEM bounds shrunk so that a test-sized input
takes the run path the cell's 100M keys take on the chip."""
import json
from types import SimpleNamespace

import jax
import pytest

import bench.peaks
import repro.core.engine as engine
from bench import calibrate, traffic
from bench import run as bench_run
from bench.systems import sort
from bench.trace import Ev, Trace

CELL = "sort-100m-1chip"


def cell_files():
    spec = bench_run.load_spec()
    return spec, bench_run.cell_of(spec, CELL)


def fake_run(ops, lo, hi, **data):
    trace = Trace({0: [Ev(*e) for e in ops]}, {0: []}, [])
    return SimpleNamespace(
        devices=[SimpleNamespace(id=0, device_kind="TPU v5 lite")],
        data=dict(trace=trace, trace_bounds=(lo, hi), **data))


def test_cell_files_are_found_by_name():
    spec, (cell, entry, config, mix, limits) = cell_files()
    assert config["name"] == cell["config"] == entry["name"]
    assert config["array_size"] == 100_000_000 and config["reduced"] == []
    assert config["source_values"] == {"array_size": config["array_size"]}
    assert mix == traffic.load("uniform-100m")
    assert limits == {"wrong_keys": 0, "misplaced_shares": 0}
    names = [m["name"] for m in spec["per_layer"]
             if CELL in m.get("workloads", [CELL])]
    assert names == ["local_sort_roofline", "device_idle_pct.sort",
                     "run_merge_roofline"]


def test_run_merge_roofline_reads_8_bytes_a_key_a_call():
    """Three calls of ten substages, each substage exactly as long as a
    third of the least bytes take at 819 GB/s: 100% over the 30 events;
    twice as long, 50%.  Events outside the window do not count."""
    keys, calls = 1 << 20, 3
    least_ns = 8 * keys * calls / 819e9 * 1e9
    for scale, want in ((1, 100.0), (2, 50.0)):
        d = scale * least_ns / 30
        ops = [(f"run_merge.{k}", k * d, d) for k in range(30)]
        ops += [("run_merge.99", 40 * d, d), ("local_sort.1", 0, d)]
        r = fake_run(ops, 0, 30 * d, keys_per_chip=keys, traced_calls=calls)
        got = bench_run.load_reader("run_merge_roofline").read(r)
        assert got == pytest.approx(want)


def test_run_merge_roofline_finds_nothing_without_a_run_merge():
    r = fake_run([("local_sort.1", 0, 10)], 0, 10, keys_per_chip=1024,
                 traced_calls=1)
    assert bench_run.load_reader("run_merge_roofline").read(r) is None
    untraced = SimpleNamespace(devices=[], data={})
    assert bench_run.load_reader("run_merge_roofline").read(untraced) is None


@pytest.fixture
def small(monkeypatch):
    """A run of one tile past a local sort of one tile, and a peak table
    entry for the CPU, which has no published one."""
    monkeypatch.setattr(engine, "max_chunk", lambda: 1024)
    monkeypatch.setattr(engine, "max_run", lambda: 1024)
    monkeypatch.setitem(bench.peaks.PEAKS, "cpu",
                        bench.peaks.Peak(1e12, 1e11, 1e9, "test only"))


def make_run(n, trace=False, seconds=1.0):
    spec, (cell, _, config, mix, limits) = cell_files()
    config = dict(config, array_size=n)
    return spec, bench_run.Run(cell, config, mix, limits, seed=2**31 + 15,
                               seconds=seconds, trace=trace,
                               devices=jax.devices()[:1])


@pytest.mark.usefixtures("small")
@pytest.mark.parametrize("trace", [False, True])
def test_window_on_the_run_path_is_correct(trace):
    spec, run = make_run(5 * 1024 + 77, trace=trace)
    res = bench_run.execute(spec, run, sort)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    if not trace:
        assert res["metrics"]["sort_keys_per_s"]["value"] > 0
        assert "setup_s" in res["metrics"]
    assert not run.compiles["window"].get("lower")
    json.dumps(res)


@pytest.mark.usefixtures("small")
def test_control_is_not_correct_on_the_run_path():
    spec, run = make_run(5 * 1024 + 77, seconds=0.5)
    run_sort = lambda r: sort.run(r, sort_fn=calibrate.control_sort)  # noqa
    res = bench_run.execute(spec, run, type("S", (), {"run": run_sort}))
    assert not res["correct"], res["checks"]
    assert res["checks"]["wrong_keys"]["value"] > 0
