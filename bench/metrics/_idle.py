"""The device's idle share of a traced window, averaged over chips."""
from bench.metrics._common import traced
from bench.trace import busy_ns


def idle_pct(run):
    t = traced(run)
    if t is None:
        return None
    tr, lo, hi, devs = t
    if hi <= lo:
        return None
    busy = sum(busy_ns(tr, d, lo, hi) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / (hi - lo))
