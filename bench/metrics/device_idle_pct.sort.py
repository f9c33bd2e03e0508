"""``device_idle_pct.sort``: 1 minus the union of the device's op intervals
over the traced window of sort calls, averaged over chips."""
from bench.metrics._idle import idle_pct


def read(run):
    return idle_pct(run)
