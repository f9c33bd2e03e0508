"""``sort.collective_ms``: device time of the collective ops (collective
permutes, all-to-all, all-gather, all-reduce, reduce-scatter, with their
start and done halves) per sort call, the mean over chips.  One chip runs
no collective."""
import re

from bench.metrics._common import traced
from bench.trace import op_ns

COLLECTIVE = re.compile(r"^(collective-permute|all-to-all|all-gather|"
                        r"all-reduce|reduce-scatter)(-start|-done)?$")


def read(run):
    t = traced(run)
    calls = run.data.get("traced_calls", 0)
    if t is None or not calls:
        return None
    tr, lo, hi, devs = t
    ns = count = 0
    for d in devs:
        a, c = op_ns(tr, d, lambda name: bool(COLLECTIVE.match(name)), lo, hi)
        ns, count = ns + a, count + c
    if not count:
        return None
    return ns / len(devs) / calls / 1e6
