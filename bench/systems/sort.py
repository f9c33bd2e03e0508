"""Sort cells: the paper's distributed sort through
``Locale.workload("sort", backend="shard_map")`` with the fused Pallas
local phase, on a flat mesh over the cell's chips.

Set-up makes the mix's inputs on the devices from the seed, each chip
making its own share, and warms the sort.  The window sorts a device copy
of input ``i mod inputs`` call after call (the sort donates its input),
keeping the mix's ``ahead`` calls dispatched beyond the one it waits for,
so that the chip stays fed while the host stands still.  When ``seconds``
have passed it dispatches nothing more, waits for every call it sent, and
reads the clock after that wait: all keys sent, over all that time.
A reservoir drawn from the seed keeps the outputs of a few calls; once the
window has closed they are compared, whole, with numpy's sort of the same
input, and every output must lie in equal contiguous shares, one on each
chip, in mesh order.
"""
from __future__ import annotations

import time
from collections import deque

import numpy as np

from bench import traffic


def make_sort(cfg: dict, devices):
    import jax
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.core import Homing, Locale, LocalisationPolicy
    mesh = jax.make_mesh((len(devices),), ("data",), devices=devices,
                         axis_types=(AxisType.Auto,))
    pol = cfg["policy"]
    locale = Locale(mesh=mesh, axis="data", policy=LocalisationPolicy(
        pol["localised"], pol["static_mapping"], Homing(pol["homing"])))
    fn = locale.workload("sort", backend=cfg["backend"],
                         local_phase=cfg["local_phase"])
    return fn, NamedSharding(mesh, P("data"))


def run(ctx, sort_fn=None) -> None:
    """Set up, run the window, check: fills ``ctx``.  ``sort_fn`` replaces
    the program's sort (the tests plant faults through it)."""
    import jax
    import jax.numpy as jnp

    cfg, mix, seed = ctx.config, ctx.mix, ctx.seed
    devices = ctx.devices
    if len(devices) != cfg["chips"]:
        raise ValueError(f"{cfg['name']} runs on {cfg['chips']} chips, "
                         f"the cell gives {len(devices)}")
    n = cfg["array_size"]

    t = time.perf_counter()
    fn, sharding = make_sort(cfg, devices)
    if sort_fn is not None:
        fn = sort_fn
    inputs = jax.block_until_ready(
        traffic.sort_inputs(mix, seed, n, sharding))
    ctx.setup["init_s"] = time.perf_counter() - t

    t = time.perf_counter()
    for i in range(mix["warmup"]["calls"]):
        jax.block_until_ready(fn(jnp.copy(inputs[i % len(inputs)])))
    ctx.setup["warmup_s"] = time.perf_counter() - t

    g = traffic.rng(seed, traffic.TAG_CHECK)
    keep, K = [], mix["check"]["samples"]
    ahead = mix["ahead"]["calls"]
    traced = mix["trace"]["calls"] if ctx.trace else 0
    flight = deque()
    done = 0

    def finish():
        """Wait for the oldest call in flight; keep it in a reservoir
        sample of the outputs, drawn from the seed."""
        nonlocal done
        i, y = flight.popleft()
        jax.block_until_ready(y)
        if len(keep) < K:
            keep.append((i, y))
        else:
            j = int(g.integers(0, done + 1))
            if j < K:
                keep[j] = (i, y)
        done += 1

    ctx.open_window()
    deadline = ctx.t0 + ctx.seconds
    calls = 0
    while time.perf_counter() < deadline:
        if calls == 0 and traced:
            ctx.capture_begin()
        with ctx.annotate("bench.sort_call"):
            flight.append((calls, fn(jnp.copy(inputs[calls % len(inputs)]))))
        if calls == traced - 1:
            jax.block_until_ready(flight[-1][1])
            ctx.capture_end()
        calls += 1
        if len(flight) > ahead:
            finish()
    while flight:
        finish()
    window_s = time.perf_counter() - ctx.t0
    ctx.close_window()

    ctx.attempted, ctx.failed = calls, 0
    ctx.metric("sort_keys_per_s", calls * n / window_s, "keys/s")
    ctx.info.update(calls=calls, keys_per_call=n, window_s=window_s)
    ctx.data.update(keys_per_chip=n // len(devices),
                    traced_calls=min(traced, calls))
    ctx.read_memory()
    check(ctx, inputs, keep, n, devices)


def shares(y, n: int, devices) -> int:
    """How many chips hold their own contiguous share of ``y``, of n/chips
    keys, in mesh order."""
    share = n // len(devices)
    ok = 0
    for s in y.addressable_shards:
        lo = s.index[0].start or 0
        d = lo // share if share else 0
        if (s.data.shape == (share,) and d < len(devices)
                and s.device == devices[d]):
            ok += 1
    return ok


def check(ctx, inputs, keep, n: int, devices) -> None:
    t = time.perf_counter()
    ref = {}
    wrong = misplaced = 0
    for i, y in keep:
        k = i % len(inputs)
        if k not in ref:
            ref[k] = np.sort(np.asarray(inputs[k]))
        got = np.asarray(y)
        wrong += int(np.sum(got != ref[k])) if got.shape == ref[k].shape \
            else n
        misplaced += len(devices) - shares(y, n, devices)
    ctx.info.update(checked_calls=len(keep),
                    reference_s=time.perf_counter() - t)
    ctx.compare("wrong_keys", wrong)
    ctx.compare("misplaced_shares", misplaced)
