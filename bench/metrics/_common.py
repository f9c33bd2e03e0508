"""What the per-layer readers share: the traced window and the peaks."""
from __future__ import annotations

from bench.peaks import peak


def traced(run):
    """(trace, lo, hi, device ids) of a traced run, or None."""
    tr = run.data.get("trace")
    if tr is None:
        return None
    lo, hi = run.data["trace_bounds"]
    return tr, lo, hi, [d.id for d in run.devices]


def peaks(run):
    return peak(run.devices[0].device_kind)
