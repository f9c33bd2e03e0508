"""``merge_split_roofline``: the merge-split kernel's share of its roofline.
Each call reads its own sorted run and its partner's (two chunks of 32-bit
keys) and writes the kept half (one chunk): 12 bytes per kept key at the
chip's HBM bandwidth.  Device time is that of the ``merge_split`` kernel
events in the trace, over every chip.  One chip runs no merge-split."""
from bench.metrics._common import peaks, traced
from bench.trace import op_ns

KERNEL = "merge_split"
BYTES_PER_KEPT_KEY = 12


def read(run):
    t = traced(run)
    if t is None:
        return None
    tr, lo, hi, devs = t
    ns = calls = 0
    for d in devs:
        a, c = op_ns(tr, d, lambda name: name == KERNEL, lo, hi)
        ns, calls = ns + a, calls + c
    if not calls or ns <= 0:
        return None
    least_s = (BYTES_PER_KEPT_KEY * run.data["keys_per_chip"] * calls
               / peaks(run).hbm_bytes_per_s)
    return 100.0 * least_s / (ns * 1e-9)
