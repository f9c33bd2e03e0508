"""The reduction from a profiler trace to busy time, kernel times, idle gaps
and roofline shares: on hand-made traces whose answers are known, and the
loader on a small trace recorded through `Capture` (``data/``)."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import peaks
from bench import run as bench_run
from bench import trace as T
from bench.trace import Ev, Trace

DATA = Path(__file__).resolve().parent / "data"


def tr(ops=(), modules=(), host=(), dev=0):
    return Trace({dev: [Ev(*e) for e in ops]},
                 {dev: [Ev(*e) for e in modules]}, [Ev(*e) for e in host])


def test_busy_is_the_union_of_op_intervals():
    t = tr(ops=[("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 32, 1)])
    assert T.busy_ns(t, 0, 0, 50) == 20
    assert T.busy_ns(t, 0, 8, 33) == 10      # clipped to the window
    assert T.gaps(t, 0, 0, 50) == [(15, 30), (35, 50)]
    assert T.busy_ns(t, 1, 0, 50) == 0       # a device with no ops


def test_kernel_and_program_time_by_name():
    t = tr(ops=[("local_sort.1", 0, 10), ("fusion.3", 10, 2),
                ("local_sort.7", 20, 10), ("merge_split", 40, 4)],
           modules=[("jit__step(1)", 0, 12), ("jit__page(2)", 20, 10)])
    assert T.op_ns(t, 0, lambda n: n == "local_sort") == (20, 2)
    assert T.op_ns(t, 0, lambda n: n == "merge_split", 0, 42) == (2, 1)
    assert T.module_ns(t, 0, lambda n: n == "jit__step") == (12, 1)
    assert T.program_name("jit__page(2)") == "jit__page"
    assert T.base_name("collective-permute-done.12") == \
        "collective-permute-done"


def test_a_tpu_op_event_is_named_by_its_instruction():
    """A TPU trace names an op event by its whole HLO text; the reduction
    keeps the instruction's name, which the readers match."""
    text = ("%local_sort.1 = s32[1,65536,128]{2,1,0:T(8,128)} custom-call("
            "s32[1,65536,128]{2,1,0:T(8,128)S(1)} %copy.3), "
            'custom_call_target="tpu_custom_call"')
    assert T.op_name(text) == "local_sort.1"
    assert T.base_name(T.op_name(text)) == "local_sort"
    assert T.op_name("local_sort.1") == "local_sort.1"
    assert T.op_name("%x.2") == "%x.2"


def test_self_time_takes_nested_ops_from_their_parent():
    t = tr(ops=[("while.1", 0, 100, "m"), ("fusion.2", 10, 30, "m"),
                ("fusion.3", 50, 20, "m"), ("copy.4", 200, 5, "m")])
    st = T.self_times(t.ops[0], 0, 1000)
    assert st == {"m/while.1": 50, "m/fusion.2": 30, "m/fusion.3": 20,
                  "m/copy.4": 5}


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    t = tr(ops=[("x", 0, 10), ("y", 50, 10)],
           host=[("bench.burst", 0, 100), ("serve.refill", 10, 20),
                 ("sched.form_wave", 15, 5)])
    idle = T.idle_by_host(t, 0, 0, 120)
    assert idle == {"serve.refill": 15, "sched.form_wave": 5,
                    "untraced host (bench.burst)": 60, T.UNTRACED: 20}
    assert sum(idle.values()) == 120 - T.busy_ns(t, 0, 0, 120)


def test_breakdown_lists_ops_and_idle_gaps_averaged_over_chips():
    t = Trace({0: [Ev("a", 0, 10)], 1: [Ev("a", 0, 30)]}, {0: [], 1: []},
              [Ev("bench.sort_call", 0, 40)])
    b = T.breakdown(t, [0, 1], 0, 40)
    assert b["device_ops"] == [["a", 20e-9]]
    assert b["idle_gaps"] == [["untraced host (bench.sort_call)", 20e-9]]


def test_an_unknown_device_kind_raises():
    assert peaks.peak("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def fake_run(trace, lo, hi, devs, **data):
    return SimpleNamespace(
        devices=[SimpleNamespace(id=d, device_kind="TPU v5 lite")
                 for d in devs],
        data=dict(trace=trace, trace_bounds=(lo, hi), **data))


def test_rooflines_reach_100_percent_only_at_the_peak():
    """A kernel event exactly as long as its bytes take at 819 GB/s reads
    100%; any longer one reads less.  The readers divide work from shapes
    by measured time, so no clipping is needed or done."""
    keys = 1 << 20
    least_ns = 8 * keys / 819e9 * 1e9
    for dur, want in ((least_ns, 100.0), (2 * least_ns, 50.0)):
        t = tr(ops=[("local_sort.1", 0, dur)])
        r = fake_run(t, 0, dur, [0], keys_per_chip=keys)
        got = bench_run.load_reader("local_sort_roofline").read(r)
        assert got == pytest.approx(want)
    ms_least = 12 * keys / 819e9 * 1e9
    t = tr(ops=[("merge_split.2", 0, ms_least), ("merge_split.3",
                                                 ms_least, ms_least)])
    r = fake_run(t, 0, 2 * ms_least, [0], keys_per_chip=keys)
    assert bench_run.load_reader("merge_split_roofline").read(r) == \
        pytest.approx(100.0)


def test_readers_that_find_nothing_return_nothing():
    t = tr(ops=[("fusion.1", 0, 10)])
    r = fake_run(t, 0, 10, [0], keys_per_chip=1024, traced_calls=1)
    for name in ("local_sort_roofline", "merge_split_roofline",
                 "sort.collective_ms"):
        assert bench_run.load_reader(name).read(r) is None
    untraced = SimpleNamespace(devices=[], data={})
    for name in ("device_idle_pct.sort", "serve.prefill_share_pct",
                 "decode_step_roofline", "sched.prefix_hit_pct"):
        assert bench_run.load_reader(name).read(untraced) is None


def test_collective_time_per_call_is_the_mean_over_chips():
    t = Trace({0: [Ev("collective-permute-start.1", 0, 1_000_000),
                   Ev("collective-permute-done.1", 1_000_000, 3_000_000)],
               1: [Ev("all-to-all.4", 0, 2_000_000)]}, {0: [], 1: []}, [])
    r = fake_run(t, 0, 10_000_000, [0, 1], traced_calls=2)
    assert bench_run.load_reader("sort.collective_ms").read(r) == \
        pytest.approx(1.5)


def test_a_recorded_trace_gives_the_host_annotations():
    """A profiler trace recorded through `Capture` around two sort calls
    (on the CPU, which has no TPU planes): the loader finds the clock mark
    and both call annotations, in order, and no device ops."""
    t = T.reduce_xplane(str(DATA / "cpu_sort.xplane.pb"))
    names = [e.name for e in t.host]
    assert names == [T.CLOCK_MARK, "bench.sort_call", "bench.sort_call"]
    assert all(e.dur >= 0 for e in t.host)
    assert t.host[1].start >= t.host[0].start + t.host[0].dur
    assert t.ops == {} and t.modules == {}


RECORD = """
import sys
import jax
from bench import trace as T
cap = T.Capture(sys.argv[1])
cap.start()
with jax.profiler.TraceAnnotation("bench.sort_call"):
    jax.numpy.ones(8).block_until_ready()
cap.stop()
tr = cap.read()
lo, hi = cap.bounds_ns
print([e.name for e in tr.host], lo < tr.host[-1].start < hi)
"""


def test_capture_finds_its_annotations_under_any_interpreter_name(tmp_path):
    """The host's line in a trace is named after the thread, which takes the
    name the interpreter was started by (``python3`` under the benchmark's
    command): the capture finds its annotations whatever that name is."""
    import os
    import subprocess
    import sys
    exe = tmp_path / "benchpy3"
    os.symlink(os.path.realpath(sys.executable), exe)
    root = Path(__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root)] + sys.path))
    out = subprocess.run([str(exe), "-c", RECORD, str(tmp_path / "trace")],
                         cwd=root, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == \
        f"[{T.CLOCK_MARK!r}, 'bench.sort_call'] True"
