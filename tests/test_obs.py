"""Observability layer tests: tracelog primitives, metrics registry, and
trace reconciliation against real (seeded, bursty) serving runs.

Fast tier: tracer/metrics unit tests, pure-scheduler reconciliation, the
committed corrupt-trace fixture, the supervisor's structured failure
event, and a 1-device traced serve whose identities `reconcile` proves.
The 8-device traced serve (flat and pod meshes) runs in a subprocess and
is marked slow, like every other multi-device test.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.obs import NULL_TRACER, Tracer, get_tracer, set_tracer
from repro.obs.reconcile import ReconcileError, reconcile
from repro.obs.tracelog import SCHEMA, read_jsonl, to_chrome
from repro.runtime.scheduler import Scheduler
from repro.runtime.server import DecodeServer, Request

from helpers import tiny

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORRUPT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "data", "corrupt_trace.jsonl")


# ---------------------------------------------------------------------------
# tracelog primitives
# ---------------------------------------------------------------------------
def test_tracer_spans_nest_and_counters_accumulate(tmp_path):
    path = str(tmp_path / "t.jsonl")
    tr = Tracer(path, run="unit")
    with tr.span("outer", cat="t", a=1) as sp:
        tr.count("bytes", 10, cat="t")
        tr.count("bytes", 5, cat="t")
        with tr.span("inner", cat="t"):
            tr.gauge("depth", 3, cat="t")
        sp.set(b=2)
        sp.event("mark", x=1)
    tr.close()
    recs = tr.records()
    byname = {r["name"]: r for r in recs}
    assert recs[0]["name"] == "trace.meta"
    assert recs[0]["args"]["schema"] == SCHEMA
    assert recs[0]["args"]["run"] == "unit"
    # spans emit at exit: inner closes before outer, with parent links
    assert byname["inner"]["parent"] == "outer"
    assert byname["outer"]["parent"] is None
    assert byname["outer"]["args"] == {"a": 1, "b": 2}
    assert byname["outer"]["dur"] >= byname["inner"]["dur"] >= 0
    assert byname["mark"]["args"]["parent"] == "outer"
    # counters carry increment and running total
    counters = [r for r in recs if r["name"] == "bytes"]
    assert [c["value"] for c in counters] == [10, 5]
    assert [c["total"] for c in counters] == [10, 15]
    assert tr.total("bytes") == 15
    # the streaming sink wrote the same records the memory list holds
    assert read_jsonl(path) == recs


def test_null_tracer_is_free_and_global_default():
    assert not NULL_TRACER.enabled
    with NULL_TRACER.span("x", cat="t", a=1) as sp:
        sp.set(b=2).event("y")
        NULL_TRACER.count("c", 5)
        NULL_TRACER.gauge("g", 1)
    assert NULL_TRACER.records() == []
    assert get_tracer() is NULL_TRACER       # process default is off
    tr = Tracer()
    assert set_tracer(tr) is NULL_TRACER
    assert get_tracer() is tr
    assert set_tracer(None) is tr            # None resets
    assert get_tracer() is NULL_TRACER


def test_chrome_export_shape():
    tr = Tracer()
    with tr.span("s", cat="c"):
        tr.event("e", cat="c")
    tr.count("n", 2)
    tr.gauge("g", 7)
    ev = to_chrome(tr.records())["traceEvents"]
    phases = {e["name"]: e["ph"] for e in ev}
    assert phases["s"] == "X" and phases["e"] == "i"
    assert phases["n"] == "C" and phases["g"] == "C"
    assert all({"name", "ph", "ts", "pid", "tid"} <= set(e) for e in ev)
    json.dumps(ev)                           # everything serialises


# ---------------------------------------------------------------------------
# pure-scheduler reconciliation (no jax): seeded bursty stream
# ---------------------------------------------------------------------------
def bursty(n, sessions=4, seed=0, slots=8, pad=8):
    rng = np.random.RandomState(seed)
    w = 1.0 / (1.0 + np.arange(sessions))
    w /= w.sum()
    return [Request(rid=i,
                    prompt=(rng.randint(1, 7, rng.randint(2, pad + 1))
                            .astype(np.int32)),
                    max_new=int(12 if rng.rand() < 0.3 else 3),
                    session=f"s{rng.choice(sessions, p=w)}",
                    t_arrive=float(i // (2 * slots)) * 12.0)
            for i in range(n)]


def drive(sch, reqs, pad=8):
    for r in reqs:
        sch.submit(r)
    now = 0.0
    while sch.has_work():
        now = sch.clock(now)
        wave = sch.form_wave(now)
        if not wave:
            continue
        active = [r for _, r in wave]
        cost = pad + max(r.max_new for r in active)
        for r in active:
            r.out = list(range(r.max_new))
            r.done = True
        sch.complete(wave, now, cost)
        now += cost


@pytest.mark.parametrize("policy", ["fifo", "homed"])
def test_reconcile_pure_scheduler(policy):
    tr = Tracer(policy=policy)
    sch = Scheduler(8, owners=(0, 0, 1, 1, 2, 2, 3, 3), policy=policy,
                    bytes_per_token=4, page_size=2, page_capacity=8,
                    prompt_pad=8, tracer=tr)
    drive(sch, bursty(40, seed=3))
    summary = sch.emit_summary()
    assert summary["served"] == 40 and summary["waves"] > 1
    report = reconcile(tr.records())
    assert report["segments"] == 1 and report["served"] == 40
    if policy == "homed":
        assert summary["relayout_bytes"] > 0      # identities were non-vacuous
    assert summary["pages_attached"] >= 0


def test_reconcile_survives_forced_invalidation():
    """pool events carry actual refs deltas, so the acquire-release-
    invalidate ledger balances even after a mid-flight evacuation."""
    from repro.runtime.ft import evacuate_home
    tr = Tracer()
    sch = Scheduler(4, owners=(0, 0, 1, 1), policy="homed",
                    bytes_per_token=4, page_size=2, page_capacity=8,
                    prompt_pad=8, tracer=tr)
    reqs = bursty(20, seed=5, slots=4)
    for r in reqs[:10]:
        sch.submit(r)
    now = sch.clock(0.0)
    wave = sch.form_wave(now)
    for _, r in wave:
        r.out = [1]
        r.done = True
    # evacuate home 0 while its first wave is still in flight
    rec = evacuate_home(sch, home=0)
    sch.complete(wave, now + 4.0, 4.0)
    for r in reqs[10:]:
        sch.submit(r)
    drive(sch, [])
    sch.emit_summary()
    assert any(r["name"] == "ft.evacuate" for r in tr.records())
    assert rec["pages_dropped"] >= 0
    reconcile(tr.records())                       # identities still hold


def test_reconcile_rejects_broken_identities():
    tr = Tracer()
    sch = Scheduler(4, owners=(0, 0, 1, 1), policy="homed",
                    bytes_per_token=4, prompt_pad=8, tracer=tr)
    drive(sch, bursty(16, seed=7, slots=4))
    sch.emit_summary()
    good = tr.records()
    reconcile(good)

    def corrupt(mutate):
        recs = [json.loads(json.dumps(r)) for r in good]
        mutate(recs)
        with pytest.raises(ReconcileError):
            reconcile(recs)

    # drop one charge event -> an off-home decode goes unpaid
    corrupt(lambda rs: rs.remove(
        next(r for r in rs if r["name"] == "sched.charge")))
    # inflate the summary's byte counter -> I-bytes
    def inflate(rs):
        s = next(r for r in rs if r["name"] == "sched.summary")
        s["args"]["relayout_bytes"] += 64
    corrupt(inflate)
    # drop a placement -> served / waves identities break
    corrupt(lambda rs: rs.remove(
        next(r for r in rs if r["name"] == "sched.place")))
    # malformed record kind -> schema rejection
    def badkind(rs):
        rs[1]["kind"] = "mystery"
    corrupt(badkind)
    # scheduler events with no closing summary -> dangling segment
    corrupt(lambda rs: rs.remove(
        next(r for r in rs if r["name"] == "sched.summary")))


def test_committed_corrupt_fixture_is_rejected():
    """The committed fixture is a real trace whose summary claims fewer
    relayout bytes than its own charge events add up to — the validator
    must prove it wrong, and the CLI must exit nonzero."""
    records = read_jsonl(CORRUPT)
    with pytest.raises(ReconcileError, match="I-bytes"):
        reconcile(records)
    from repro.launch.tracelog import main as tracelog_main
    assert tracelog_main([CORRUPT, "--validate"]) == 1
    assert tracelog_main([CORRUPT]) == 0          # summary mode still reads


# ---------------------------------------------------------------------------
# engine budget stamping
# ---------------------------------------------------------------------------
def test_engine_sort_stamps_analytic_schedule():
    from repro.core.engine import make_engine_fn
    from repro.core.localisation import LocalisationPolicy
    tr = Tracer()
    set_tracer(tr)
    try:
        fn = make_engine_fn(None, LocalisationPolicy())
        x = np.random.RandomState(0).randint(0, 997, 128).astype(np.int32)
        y = np.asarray(fn(x))
    finally:
        set_tracer(None)
    assert (y == np.sort(x)).all()
    spans = [r for r in tr.records() if r["name"] == "engine.sort"]
    assert len(spans) == 1
    levels = [r for r in tr.records()
              if r["name"] == "engine.exchange_level"]
    assert levels and all(lv["args"]["call"] == spans[0]["args"]["call"]
                          for lv in levels)
    reconcile_engine_only(tr.records())
    # corrupt one stamped level -> I-engine catches the lie
    bad = [json.loads(json.dumps(r)) for r in tr.records()]
    next(r for r in bad if r["name"] == "engine.exchange_level"
         )["args"]["local_hbm_bytes"] += 1
    with pytest.raises(ReconcileError, match="I-engine"):
        reconcile_engine_only(bad)


def reconcile_engine_only(records):
    from repro.obs.reconcile import check_engine, check_schema
    check_schema(records)
    check_engine(records)


def engine_sorts(tracer, *xs):
    """Sort each of ``xs`` through one engine fn with ``tracer`` installed
    as the global tracer (None: tracing off)."""
    from repro.core.engine import make_engine_fn
    from repro.core.localisation import LocalisationPolicy
    fn = make_engine_fn(None, LocalisationPolicy())
    prev = set_tracer(tracer)
    try:
        return [np.asarray(fn(x)) for x in xs]
    finally:
        set_tracer(prev)


@pytest.fixture(scope="module")
def traced_engine_sorts():
    """Two calls of the same shape under one tracer: the first builds."""
    x = np.random.RandomState(1).randint(-50, 50, 100).astype(np.int32)
    tr = Tracer()
    ys = engine_sorts(tr, x, x)
    return tr, x, ys


@pytest.mark.parametrize("n", [100, 128])
def test_engine_sort_off_path_records_nothing(traced_engine_sorts, n):
    """Tracing off: the same result as the traced path, and a tracer that
    was installed before records nothing more, not even a build."""
    tr, _, _ = traced_engine_sorts
    held = len(tr.records())
    x = np.random.RandomState(n).randint(-50, 50, n).astype(np.int32)
    (off,) = engine_sorts(None, x)
    (on,) = engine_sorts(Tracer(), x)
    assert off.dtype == x.dtype and (off == np.sort(x)).all()
    assert (off == on).all()
    assert get_tracer() is NULL_TRACER
    assert len(tr.records()) == held


def test_engine_sort_spans_cover_the_host_path(traced_engine_sorts):
    tr, x, ys = traced_engine_sorts
    assert all((y == np.sort(x)).all() for y in ys)
    recs = tr.records()
    sorts = [r for r in recs if r["name"] == "engine.sort"]
    assert len(sorts) == 2
    for top in sorts:
        cid = top["args"]["call"]
        kids = sorted((r for r in recs if r["kind"] == "span"
                       and r["parent"] == "engine.sort"
                       and r["args"]["call"] == cid), key=lambda r: r["ts"])
        assert [r["name"] for r in kids] == ["sort.prepare", "sort.dispatch",
                                             "sort.unpad"]
        t, end = top["ts"], top["ts"] + top["dur"]
        for r in kids:
            assert t <= r["ts"] and r["ts"] + r["dur"] <= end
            t = r["ts"] + r["dur"]
    reconcile_engine_only(recs)


def test_engine_sort_marks_the_call_that_builds(traced_engine_sorts):
    import jax
    tr, x, _ = traced_engine_sorts
    recs = tr.records()
    builds = [r["args"]["build"] for r in recs
              if r["name"] == "sort.dispatch"]
    assert builds == [True, False]
    counts = [r for r in recs if r["name"] == "sort.builds"]
    assert len(counts) == 1 and tr.total("sort.builds") == 1
    assert counts[0]["args"]["n"] >= len(x)
    assert counts[0]["args"]["sizes"] == [len(jax.devices())]
    # the build happened inside the first call's dispatch
    first = next(r for r in recs if r["name"] == "sort.dispatch")
    assert first["ts"] <= counts[0]["ts"] <= first["ts"] + first["dur"]


def test_tracer_epoch_is_on_the_perf_counter_clock():
    import time
    tr = Tracer()
    t0 = time.perf_counter()
    with tr.span("s"):
        pass
    t1 = time.perf_counter()
    (s,) = [r for r in tr.records() if r["name"] == "s"]
    start = tr.epoch + s["ts"] / 1e6
    assert t0 <= start <= start + s["dur"] / 1e6 <= t1


# ---------------------------------------------------------------------------
# supervisor fleet events
# ---------------------------------------------------------------------------
def test_supervisor_hung_restart_budget_emits_failure_event(tmp_path):
    from repro.runtime.ft import Supervisor
    hangy = (
        "import os, sys, time\n"
        "d = sys.argv[1]\n"
        "n = len(os.listdir(d))\n"
        "open(os.path.join(d, str(n)), 'w').write('x')\n"
        "print('beat', flush=True)\n"
        "if n < 2:\n"
        "    os.close(1); os.close(2)\n"
        "    time.sleep(30)\n"
        "print('DONE')\n")
    d = tmp_path / "attempts"
    d.mkdir()
    tr = Tracer()
    out = Supervisor(cmd=[sys.executable, "-c", hangy, str(d)],
                     max_restarts=2, heartbeat_timeout_s=1.0,
                     tracer=tr).run()
    assert not out["ok"] and out["reason"] == "hung_restart_budget"
    attempts = [r for r in tr.records() if r["name"] == "ft.attempt"]
    assert [a["args"]["hung"] for a in attempts] == [True, True, False]
    results = [r for r in tr.records() if r["name"] == "ft.result"]
    assert len(results) == 1
    assert results[0]["args"]["ok"] is False
    assert results[0]["args"]["reason"] == "hung_restart_budget"
    assert results[0]["args"]["hangs"] == 2


# ---------------------------------------------------------------------------
# traced serve, 1 device (fast) and 8 devices (slow subprocess)
# ---------------------------------------------------------------------------
def test_traced_serve_single_device_reconciles(tmp_path):
    cfg = tiny("qwen3-0.6b", layers=1)
    from repro.models.model import LM
    import jax
    params = LM(cfg).init(jax.random.key(0))
    path = str(tmp_path / "serve.jsonl")
    tr = Tracer(path, policy="homed")
    srv = DecodeServer(cfg, params, batch_slots=2, max_len=32,
                       scheduler="homed", prompt_pad=6, tracer=tr)
    for r in bursty(6, sessions=2, seed=1, slots=2, pad=6):
        r.max_new = min(r.max_new, 5)
        srv.submit(r)
    served = srv.run()
    assert len(served) == 6 and all(r.done for r in served)
    summary = srv.scheduler.emit_summary()
    tr.close()
    records = read_jsonl(path)
    report = reconcile(records)
    assert report["segments"] == 1 and report["served"] == 6
    # the serve-layer spans landed in the same stream
    names = {r["name"] for r in records}
    assert {"serve.refill", "serve.decode", "sched.form_wave",
            "sched.route", "sched.place"} <= names
    # summary event == canonical dict (one rendering path)
    ev = next(r for r in records if r["name"] == "sched.summary")
    assert ev["args"]["served"] == summary["served"] == 6


_TRACED_SERVE_8DEV = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
from repro.configs import get_config, reduce_config
from repro.configs.base import ShapeSpec
from repro.launch.mesh import make_host_mesh
from repro.models.model import LM
from repro.obs import Tracer
from repro.obs.reconcile import reconcile
from repro.runtime.server import DecodeServer, Request
from repro.sharding.partition import make_plan

MESH = {mesh!r}
cfg = reduce_config(get_config("qwen3-0.6b"), layers=1)
params = LM(cfg).init(jax.random.key(0))
if MESH == "flat":
    mesh = make_host_mesh(n_data=8, n_model=1)
else:
    mesh = make_host_mesh(n_pods=2, n_data=2, n_model=2)
plan = make_plan(mesh, cfg, ShapeSpec("serve", 32, 16, "decode"))

rng = np.random.RandomState(0)
w = 1.0 / (1.0 + np.arange(4)); w /= w.sum()
tr = Tracer(mesh=MESH, policy="homed")
srv = DecodeServer(cfg, params, batch_slots=16, max_len=32, plan=plan,
                   scheduler="homed", prompt_pad=6, tracer=tr)
for i in range(24):
    srv.submit(Request(
        rid=i,
        prompt=rng.randint(0, cfg.vocab_size,
                           rng.randint(2, 7)).astype(np.int32),
        max_new=int(12 if rng.rand() < 0.3 else 3),
        session=f"s{{rng.choice(4, p=w)}}",
        t_arrive=float(i // 16)))
served = srv.run()
assert len(served) == 24
summary = srv.scheduler.emit_summary()
report = reconcile(tr.records())
assert report["segments"] == 1 and report["served"] == 24
assert summary["relayout_bytes"] > 0       # cross-home charges reconciled
if MESH != "flat":
    assert summary["inter_pod_bytes"] >= 0
print("TRACED_SERVE_OK", MESH, report["served"],
      summary["relayout_bytes"])
"""


@pytest.mark.slow
@pytest.mark.parametrize("mesh", ["flat", "pods"])
def test_traced_serve_8dev_reconciles(mesh):
    r = subprocess.run(
        [sys.executable, "-c", _TRACED_SERVE_8DEV.format(mesh=mesh)],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=ROOT)
    assert "TRACED_SERVE_OK" in r.stdout, r.stdout + r.stderr
