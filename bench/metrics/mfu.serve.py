"""``mfu.serve``: the whole serving step's share of the chip's bf16 peak.
Forward FLOPs of every prompt and generated token of the window (2 per
multiply-add of the matmul weights, head included, plus attention at each
token's position; prompt tokens count whether computed or attached from
the pool) over the window's seconds times the peak of every chip.  A
prompt counts when its first token comes inside the window, a generated
token when it is appended inside the window."""
from bench import model_work
from bench.metrics._common import peaks


def read(run):
    if "requests" not in run.data:
        return None
    m = run.data["model"]
    t0, t1 = run.data["window"]
    flops = 0
    for r in run.data["requests"]:
        times, P = r["times"], r["plen"]
        if times and t0 <= times[0] <= t1:
            flops += sum(model_work.token_flops(m, q) for q in range(P))
        flops += sum(model_work.token_flops(m, P + i - 1)
                     for i in range(1, len(times)) if t0 <= times[i] <= t1)
    peak = peaks(run).bf16_flops * len(run.devices)
    return 100.0 * flops / ((t1 - t0) * peak)
