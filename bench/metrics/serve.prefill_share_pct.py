"""``serve.prefill_share_pct``: device time of the server's prefill
programs (page-stepped prefill ``_page``, pool attach ``_attach``, cache
reset ``_reset``) over the device's busy time, in the traced bursts."""
from bench.metrics._common import traced
from bench.trace import busy_ns, module_ns

PREFILL = {"jit__page", "jit__attach", "jit__reset"}


def read(run):
    t = traced(run)
    if t is None:
        return None
    tr, lo, hi, devs = t
    busy = sum(busy_ns(tr, d, lo, hi) for d in devs)
    pre = sum(module_ns(tr, d, lambda n: n in PREFILL, lo, hi)[0]
              for d in devs)
    if busy <= 0:
        return None
    return 100.0 * pre / busy
