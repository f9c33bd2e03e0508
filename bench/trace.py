"""The profiler trace: capture it, reduce it to plain events, and read the
device's busy time, kernel times and idle gaps from it.

A trace is reduced to a `Trace`: for each device, its XLA op events and its
program (module) events; and the host spans, all in nanoseconds on the
profiler's clock.  Everything below works on that plain form, so the test
reads a small recorded trace without a chip.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import time
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

#: the host annotation whose position calibrates host clocks to the trace
CLOCK_MARK = "bench.clock"
#: the label of idle device time that no host span covers
UNTRACED = "untraced host"

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"\.\d+$")


class Ev(NamedTuple):
    name: str
    start: int            # ns on the profiler's clock
    dur: int              # ns
    module: str = ""      # the program an op ran in ("" for host spans)


class Trace(NamedTuple):
    ops: Dict[int, List[Ev]]        # device id -> XLA op events
    modules: Dict[int, List[Ev]]    # device id -> program events
    host: List[Ev]                  # host spans (annotations, program spans)


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------
class Capture:
    """A profiler window with its host clock calibrated to the trace.

    ``perf_to_ns(t)`` maps a ``time.perf_counter()`` reading to the trace's
    clock, through the `CLOCK_MARK` annotation made as the window opens.
    """

    def __init__(self, directory: str):
        self.dir = directory
        self._mark_perf = None
        self._offset_ns = None
        self.trace: Optional[Trace] = None
        self.start_perf = self.stop_perf = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(CLOCK_MARK):
            pass
        self._mark_perf = (t0 + time.perf_counter()) / 2
        self.start_perf = time.perf_counter()

    def stop(self) -> None:
        import jax
        self.stop_perf = time.perf_counter()
        jax.profiler.stop_trace()

    def read(self, extra_host: Iterable[Tuple[str, float, float]] = ()
             ) -> Trace:
        """Reduce the captured window; ``extra_host`` adds host spans given
        as (name, perf_counter start, seconds), such as the program's own
        tracer spans.  The raw files are deleted once read."""
        paths = sorted(glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        tr = reduce_xplane(paths[-1])
        mark = [e for e in tr.host if e.name == CLOCK_MARK]
        if not mark:
            raise RuntimeError(f"no {CLOCK_MARK!r} annotation in the trace")
        self._offset_ns = (mark[0].start + mark[0].dur / 2
                           - self._mark_perf * 1e9)
        host = list(tr.host) + [
            Ev(n, int(self.perf_to_ns(t)), int(s * 1e9))
            for n, t, s in extra_host]
        host.sort(key=lambda e: e.start)
        shutil.rmtree(self.dir, ignore_errors=True)
        self.trace = Trace(tr.ops, tr.modules, host)
        return self.trace

    def perf_to_ns(self, t: float) -> float:
        return t * 1e9 + self._offset_ns

    @property
    def bounds_ns(self) -> Tuple[float, float]:
        return self.perf_to_ns(self.start_perf), self.perf_to_ns(
            self.stop_perf)


def reduce_xplane(path: str) -> Trace:
    """Device op and program events of every TPU plane, and the host's
    annotations, from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    ops: Dict[int, List[Ev]] = {}
    modules: Dict[int, List[Ev]] = {}
    host: List[Ev] = []
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = [Ev(op_name(e.name), int(e.start_ns),
                                   int(e.duration_ns), _module_of(e))
                                for e in line.events]
                elif line.name == "XLA Modules":
                    modules[dev] = [Ev(e.name, int(e.start_ns),
                                       int(e.duration_ns))
                                    for e in line.events]
        elif plane.name.startswith("/host:"):
            # a host line is named after its thread, which takes the name
            # the interpreter was started by (python, python3, ...)
            for line in plane.lines:
                host += [Ev(e.name, int(e.start_ns), int(e.duration_ns))
                         for e in line.events if e.name.startswith("bench.")]
    for d in ops:
        ops[d].sort(key=lambda e: e.start)
    for d in modules:
        modules[d].sort(key=lambda e: e.start)
    host.sort(key=lambda e: e.start)
    return Trace(ops, modules, host)


def op_name(event_name: str) -> str:
    """An op event's instruction name: a TPU trace names each op by its HLO
    text (``%local_sort.1 = s32[...] custom-call(...)``), another backend
    by the name alone (``local_sort.1``)."""
    if event_name.startswith("%") and " = " in event_name:
        return event_name[1:].split(" = ", 1)[0]
    return event_name


def _module_of(event) -> str:
    for k, v in event.stats:
        if k == "hlo_module":
            return str(v)
    return ""


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------
def clip(evs: Iterable[Ev], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The events' intervals cut to [lo, hi], empty ones dropped."""
    out = []
    for e in evs:
        a, b = max(e.start, lo), min(e.start + e.dur, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge overlapping intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(trace: Trace, dev: int, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] in which some op ran on device ``dev``."""
    return sum(b - a for a, b in union(clip(trace.ops.get(dev, ()), lo, hi)))


def gaps(trace: Trace, dev: int, lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The idle intervals of device ``dev`` inside [lo, hi]."""
    out, t = [], lo
    for a, b in union(clip(trace.ops.get(dev, ()), lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def base_name(name: str) -> str:
    """An op's name without XLA's numeric suffix (``local_sort.1``)."""
    return _SUFFIX.sub("", name)


def op_ns(trace: Trace, dev: int, match, lo: float = float("-inf"),
          hi: float = float("inf")) -> Tuple[float, int]:
    """(ns, count) of the ops on ``dev`` whose base name ``match`` accepts,
    cut to [lo, hi]."""
    cut = clip([e for e in trace.ops.get(dev, ())
                if match(base_name(e.name))], lo, hi)
    return sum(b - a for a, b in cut), len(cut)


def module_ns(trace: Trace, dev: int, match, lo: float = float("-inf"),
              hi: float = float("inf")) -> Tuple[float, int]:
    """(ns, count) of the programs on ``dev`` whose name ``match`` accepts."""
    cut = clip([e for e in trace.modules.get(dev, ())
                if match(program_name(e.name))], lo, hi)
    return sum(b - a for a, b in cut), len(cut)


def program_name(module_event_name: str) -> str:
    """``jit__step(12345)`` -> ``jit__step``."""
    return module_event_name.split("(", 1)[0]


def self_times(evs: List[Ev], lo: float, hi: float) -> Dict[str, float]:
    """ns by op name with nested ops' time taken from their parent."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []          # [end, name, start, child ns]

    def close(until: float) -> None:
        while stack and stack[-1][0] <= until:
            end, name, start, child = stack.pop()
            out[name] += (end - start) - child
            if stack:
                stack[-1][3] += end - start

    for e in sorted(evs, key=lambda e: (e.start, -e.dur)):
        a, b = max(e.start, lo), min(e.start + e.dur, hi)
        if b <= a:
            continue
        close(a)
        label = f"{e.module}/{e.name}" if e.module else e.name
        stack.append([b, label, a, 0.0])
    close(float("inf"))
    return out


def idle_by_host(trace: Trace, dev: int, lo: float, hi: float
                 ) -> Dict[str, float]:
    """Idle ns of device ``dev`` in [lo, hi], each piece labelled by the
    innermost host span that covers it (`UNTRACED` where none does)."""
    spans = [(e.start, e.start + e.dur, e.name) for e in trace.host
             if e.name != CLOCK_MARK]
    spans.sort()
    starts = [s[0] for s in spans]
    longest = max((s[1] - s[0] for s in spans), default=0)
    out: Dict[str, float] = defaultdict(float)
    for a, b in gaps(trace, dev, lo, hi):
        i0 = bisect.bisect_left(starts, a - longest)
        i1 = bisect.bisect_right(starts, b)
        cover = [s for s in spans[i0:i1] if s[1] > a and s[0] < b]
        cuts = sorted({a, b} | {t for s in cover for t in s[:2]
                                if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            mid = (x + y) / 2
            inner = [s for s in cover if s[0] <= mid < s[1]]
            # the innermost span is the one that started last; the
            # benchmark's own annotations mark where the program has none
            label = max(inner)[2] if inner else UNTRACED
            if label.startswith("bench."):
                label = f"{UNTRACED} ({label})"
            out[label] += y - x
    return out


def top(d: Dict[str, float], n: int = 10, scale: float = 1e-9
        ) -> List[List]:
    """The ``n`` largest entries as [[name, value * scale], ...]."""
    return [[k, v * scale] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(trace: Trace, devices: List[int], lo: float, hi: float
              ) -> dict:
    """The ten device ops that took most time and the ten host activities
    that left the device idle longest, each averaged over ``devices``."""
    ops: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    for d in devices:
        for k, v in self_times(trace.ops.get(d, []), lo, hi).items():
            ops[k] += v / len(devices)
        for k, v in idle_by_host(trace, d, lo, hi).items():
            idle[k] += v / len(devices)
    return {"device_ops": top(ops), "idle_gaps": top(idle)}
