"""``local_sort_roofline``: the fused local-sort kernel's share of its
roofline.  Its least time is its bytes at the chip's HBM bandwidth: each
call reads its chip's chunk of 32-bit keys once and writes it once, 8 bytes
a key, whatever network of passes sorts them in VMEM.  The vector unit has
no published peak, so no compute bound is asserted.  Device time is that of
the ``local_sort`` kernel events in the trace, over every chip."""
from bench.metrics._common import peaks, traced
from bench.trace import op_ns

KERNEL = "local_sort"
BYTES_PER_KEY = 8


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
    least_s = (BYTES_PER_KEY * run.data["keys_per_chip"] * calls
               / peaks(run).hbm_bytes_per_s)
    return 100.0 * least_s / (ns * 1e-9)
