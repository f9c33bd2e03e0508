"""The sort's program spans: the two readers on hand-made records whose
answers are known, and traced rehearsals of both sort cells on the CPU,
with the program's tracer installed around the cell, that read them and
label idle time by them."""
from types import SimpleNamespace

import pytest

from bench import run as bench_run
from bench.systems import sort
from test_correctness import cpu_peaks, execute  # noqa: F401 (fixture)

EPOCH = 100.0            # the tracer's epoch on the perf_counter clock (s)


def span(name, ts_us, dur_us, call, **args):
    return {"kind": "span", "name": name, "ts": ts_us, "dur": dur_us,
            "args": dict(args, call=call)}


def one_call(call, ts, prepare, dispatch, unpad, build=False):
    """The spans of one engine.sort call, its children back to back after
    2 us of the parent's own stamping."""
    t1 = ts + prepare + 2
    t2 = t1 + dispatch
    return [span("sort.prepare", ts, prepare, call),
            span("sort.dispatch", t1, dispatch, call, build=build),
            span("sort.unpad", t2, unpad, call),
            span("engine.sort", ts, prepare + 2 + dispatch + unpad, call)]


def fake_run(records, lo_s, hi_s):
    """A traced run whose profiler clock is the perf_counter clock in ns."""
    capture = SimpleNamespace(perf_to_ns=lambda t: t * 1e9)
    return SimpleNamespace(capture=capture, data={
        "tracer": (EPOCH, records), "trace_bounds": (lo_s * 1e9, hi_s * 1e9)})


def read(metric, run):
    return bench_run.load_reader(metric).read(run)


def test_readers_skip_the_build_and_calls_outside_the_capture():
    records = (one_call(1, 0, 50, 4_000_000, 10, build=True)     # warm-up
               + one_call(2, 5_000_000, 30, 60, 10)              # before
               + one_call(3, 10_000_000, 40, 100, 20)            # inside
               + one_call(4, 10_001_000, 20, 80, 40)             # inside
               + one_call(5, 30_000_000, 500, 500, 500))         # after
    run = fake_run(records, EPOCH + 9.0, EPOCH + 20.0)
    # calls 3 and 4: (40+100+20 + 20+80+40) us / 2, the parents' own 2 us
    # left out
    assert read("sort.host_ms", run) == pytest.approx(0.150)
    assert read("sort.build_s", run) == pytest.approx(4.0)


def test_readers_read_nothing_without_the_program_spans():
    """A program with only the engine.sort span, or no tracer at all."""
    bare = [span("engine.sort", 10_000_000, 100, 1)]
    run = fake_run(bare, EPOCH, EPOCH + 20.0)
    assert read("sort.host_ms", run) is None
    assert read("sort.build_s", run) is None
    untraced = SimpleNamespace(capture=None, data={})
    assert read("sort.host_ms", untraced) is None
    assert read("sort.build_s", untraced) is None


def with_program_tracer(run):
    """Drive a sort cell with the program's tracer installed, and hand its
    records and spans to the readers as a traced run would."""
    from repro.obs import Tracer, set_tracer
    tracer = Tracer()
    untraced = set_tracer(tracer)
    try:
        sort.run(run)
    finally:
        set_tracer(untraced)
    records = tracer.records()
    run.data["tracer"] = (tracer.epoch, records)
    run.host_spans = [(r["name"], tracer.epoch + r["ts"] / 1e6,
                       r["dur"] / 1e6) for r in records if r["kind"] == "span"]


@pytest.mark.parametrize("name", ["sort-1chip", "sort-4chip"])
def test_traced_sort_cell_reads_its_program_spans(name):
    system = type("S", (), {"run": staticmethod(with_program_tracer)})
    run, res = execute(name, system, trace=True)
    assert res["correct"], res["checks"]
    assert read("sort.host_ms", run) > 0
    assert read("sort.build_s", run) > 0
    names = {r["name"] for r in run.data["tracer"][1]}
    assert {"engine.sort", "sort.prepare", "sort.dispatch", "sort.unpad",
            "sort.builds"} <= names
    labels = [k for k, _ in res["breakdown"]["idle_gaps"]]
    assert any(k.startswith("sort.") for k in labels), labels
