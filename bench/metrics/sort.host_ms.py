"""``sort.host_ms``: the host's own time per sort call, in ms, the mean over
the calls that start inside the profiler window and do not build the
program: the sum of the program's ``sort.prepare``, ``sort.dispatch`` and
``sort.unpad`` spans of each call.  The ``engine.sort`` span's self time,
which is the tracer stamping the call's exchange levels, is left out, so
this reads the program and not its tracer.  A run without these spans
reads nothing."""
from bench.metrics._sort_spans import PARTS, sort_calls


def read(run):
    tracer = run.data.get("tracer")
    if tracer is None or run.capture is None:
        return None
    epoch, records = tracer
    lo, hi = run.data["trace_bounds"]
    ms = []
    for spans in sort_calls(records).values():
        if "engine.sort" not in spans or any(p not in spans for p in PARTS):
            continue
        if spans["sort.dispatch"]["args"].get("build"):
            continue
        start = run.capture.perf_to_ns(epoch + spans["engine.sort"]["ts"]
                                       / 1e6)
        if lo <= start < hi:
            ms.append(sum(spans[p]["dur"] for p in PARTS) / 1e3)
    return sum(ms) / len(ms) if ms else None
