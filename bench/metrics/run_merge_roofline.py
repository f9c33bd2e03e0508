"""``run_merge_roofline``: the in-chip run merge's share of its roofline.
A sort call whose chunk is past VMEM merges its sorted runs in HBM; any
merge of HBM-resident runs, whatever network implements it, reads every
key at least once and writes it once: 8 bytes a key a call at the chip's
HBM bandwidth.  Device time is that of the ``run_merge`` kernel events in
the traced window (every substage of the traced calls), over every chip.
A chunk that fits VMEM runs no run merge, and the reader finds nothing."""
from bench.metrics._common import peaks, traced
from bench.trace import op_ns

KERNEL = "run_merge"
BYTES_PER_KEY = 8


def read(run):
    t = traced(run)
    if t is None:
        return None
    tr, lo, hi, devs = t
    ns = events = 0
    for d in devs:
        a, c = op_ns(tr, d, lambda name: name == KERNEL, lo, hi)
        ns, events = ns + a, events + c
    if not events or ns <= 0:
        return None
    least_s = (BYTES_PER_KEY * run.data["keys_per_chip"]
               * run.data["traced_calls"] * len(devs)
               / peaks(run).hbm_bytes_per_s)
    return 100.0 * least_s / (ns * 1e-9)
