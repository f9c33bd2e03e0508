"""``decode_step_roofline``: the decode step program's (``_step``) share of
its roofline, over the traced bursts.

The least time of the steps is the larger of two bounds: their bytes at the
chip's HBM bandwidth (the matmul weights once per step, plus for every
token a step produced the K/V of its row up to its own position) and their
FLOPs at the chip's bf16 peak.  The larger of the two sums bounds the sum of
each step's larger bound from below, so the share cannot pass 100% by this
count.  Device time is that of the ``jit__step`` program events."""
from bench import model_work
from bench.metrics._common import peaks, traced
from bench.trace import module_ns

STEP = "jit__step"


def read(run):
    t = traced(run)
    if t is None:
        return None
    tr, lo, hi, devs = t
    ns = steps = 0
    for d in devs:
        a, c = module_ns(tr, d, lambda n: n == STEP, lo, hi)
        ns, steps = ns + a, steps + c
    if not steps or ns <= 0:
        return None
    m, pk = run.data["model"], peaks(run)
    bursts = set(run.data["traced_bursts"])
    kvb = model_work.kv_bytes_per_position(m)
    kv = flops = 0
    for r in run.data["requests"]:
        if r["burst"] not in bursts:
            continue
        for i in range(1, len(r["times"])):       # token 0 comes from prefill
            pos = r["plen"] + i - 1
            kv += kvb * (pos + 1)
            flops += model_work.token_flops(m, pos)
    steps /= len(devs)
    least_s = max((steps * model_work.weight_bytes(m) + kv)
                  / pk.hbm_bytes_per_s, flops / pk.bf16_flops)
    return 100.0 * least_s / (ns / len(devs) * 1e-9)
