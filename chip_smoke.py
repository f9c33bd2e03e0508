"""Bring-up check on a TPU: the sort engine and the decode server, each driven
once through the entry points a user calls, with every result checked.

    python chip_smoke.py              # one chip: the phases below
    python chip_smoke.py --chips 4    # four chips: the multi-chip paths only

One chip:

  * ``sort-fused``     — `Locale.auto(case 8).workload("sort",
    backend="shard_map")` with the fused Pallas local phase, at the largest
    chunk the local-sort kernel holds in VMEM; bit-exact against `jnp.sort`.
  * ``sort-padded``    — the same at `odd_size` of that chunk: a key count
    that is neither a power of two nor a whole number of 128-key rows, so
    the kernel's padding paths run (HBM pad and in-VMEM sentinels).
  * ``sort-reference`` — the same workload with ``local_phase="reference"``
    and 64 workers (Pallas leaf sorts + the rank-merge tree) at 2^26 keys.
  * ``serve``          — qwen3-0.6b as published (bf16, random weights from
    a seed) through `DecodeServer` with the ``homed`` scheduler, built as
    `repro.launch.serve` builds it, answering 8 requests; then, for two
    requests, cached-decode logits against `LM.forward` over prompt +
    output, the server's greedy tokens equal to the forward argmax, and
    (as information) the bf16 forward against a float32 one.

Four chips (``--chips 4``): the sort on a flat 4-chip mesh for case 8 and
case 7 (hash homing: the all-to-all relayout runs; at 3/4 of the keys, so
the merge-split kernel pads) and the hierarchical policy on a ("pod", "data")
2x2 mesh — the only paths that run the merge-split kernel and the
ppermute network — each bit-exact against `jnp.sort`; and the decode
server over 4 homes, whose tokens must equal those of the same requests
served on one chip in this process (see `serve_homes_phase`).  Sorted
keys and KV slots must land on all four devices.

Every phase prints one ``phase <name> {json}`` line.  The last line is
``{"ok": true, "device": {...}}``.  The script exits non-zero, printing no
result, when JAX finds no TPU, when ``src/repro`` is missing, when a check
fails, or when a phase raises.  Times are wall-clock seconds, taken after
each program's compile (timed apart), and are information, not metrics.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

#: seed of the sorted keys
SEED = 0
#: keys of the reference-phase sort and its worker count (leaves of 2^20)
REFERENCE_KEYS = 1 << 26
REFERENCE_WORKERS = 64
#: keys per chip in the four-chip sorts: the merge-split kernel holds two
#: chunks in VMEM, the local sort one
KEYS_PER_CHIP_4 = 1 << 23
SERVE_ARCH = "qwen3-0.6b"
SERVE_REQUESTS = 8
SERVE_SLOTS = 4
#: requests whose logits are checked against the forward pass
PARITY_REQUESTS = 2
#: bf16 cached-decode logits vs the bf16 forward pass: the RMS of the
#: difference over the RMS of the forward logits (about five bf16 epsilons
#: of 2^-8).  The server's greedy tokens must equal the forward argmax.
LOGIT_RTOL = 2e-2


class CheckFailed(AssertionError):
    """A result disagreed with its reference."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


def emit(name: str, **fields) -> None:
    print(f"phase {name} {json.dumps(fields, sort_keys=True)}", flush=True)


def peak_bytes(devices) -> list:
    """``peak_bytes_in_use`` of each device so far (None where unreported)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def _timed(fn, *args):
    import jax
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t


# ---------------------------------------------------------------------------
# sort
# ---------------------------------------------------------------------------
def sort_phase(name: str, locale, n: int, *, expect_devices: int = 1,
               warm_call: bool = True,
               **workload_kw) -> dict:
    """Sort n seeded int32 keys through ``locale.workload("sort",
    backend="shard_map", **workload_kw)``; bit-exact against `jnp.sort`.

    ``warm_call=False`` times only the first call after the compile (the
    reference phase at 2^26 keys takes minutes per call on a v5e)."""
    import jax
    import jax.numpy as jnp
    info = jnp.iinfo(jnp.int32)
    fn = locale.workload("sort", backend="shard_map", **workload_kw)
    x = jax.random.randint(jax.random.key(SEED), (n,), info.min, info.max,
                           dtype=jnp.int32)
    expect = jax.block_until_ready(jnp.sort(x))
    t = time.perf_counter()
    fn.lower(x).compile()
    compile_s = time.perf_counter() - t
    y, first_s = _timed(fn, jnp.copy(x))         # the sort donates its input
    times = dict(first_call_s=first_s)
    if warm_call:
        y, times["warm_s"] = _timed(fn, jnp.copy(x))
    check(bool(jnp.array_equal(y, expect)),
          f"{name}: sort of {n} keys differs from jnp.sort")
    devices = sorted({s.device.id for s in y.addressable_shards
                      if s.data.size})
    check(len(devices) == expect_devices,
          f"{name}: sorted keys sit on devices {devices}, want "
          f"{expect_devices} devices")
    out = dict(n=n, bit_exact=True, compile_s=compile_s, **times,
               keys_per_s=n / times.get("warm_s", first_s), devices=devices,
               policy=locale.policy.name,
               peak_bytes_in_use=peak_bytes(jax.devices()))
    emit(name, **out)
    return out


def odd_size(n: int) -> int:
    """A key count near ``3n/4``: not a power of two, not a multiple of 128
    (for any n >= 512 that is a power of two), so every chunk pads."""
    return 3 * n // 4 + 1


def case_policy(case: int):
    """The paper's Table-1 case as a `LocalisationPolicy`."""
    from repro.configs.paper_sort import CASES
    from repro.core import Homing, LocalisationPolicy
    c = CASES[case]
    return LocalisationPolicy(c.localised, c.static_mapping, Homing(c.homing))


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def load_model(arch: str, reduced: bool):
    """(cfg, model, params) as `repro.launch.serve` builds them."""
    import jax
    from repro.launch.serve import PARAM_SEED, serve_config
    from repro.models.model import LM
    cfg = serve_config(arch, reduced)
    model = LM(cfg)
    return cfg, model, jax.jit(model.init)(jax.random.key(PARAM_SEED))


def serve(cfg, params, plan, *, requests: int = SERVE_REQUESTS,
          slots: int = SERVE_SLOTS):
    """Serve the launcher's request stream with the homed scheduler.

    Returns (server, requests by rid, wall seconds)."""
    from repro.launch.serve import MAX_LEN, synthetic_requests
    from repro.runtime.server import DecodeServer
    srv = DecodeServer(cfg, params, batch_slots=slots, max_len=MAX_LEN,
                       plan=plan, scheduler="homed", prompt_pad=16)
    for req in synthetic_requests(cfg, requests, slots=slots, max_new=8,
                                  sessions=4):
        srv.submit(req)
    t = time.perf_counter()
    served = sorted(srv.run(), key=lambda r: r.rid)
    secs = time.perf_counter() - t
    check(len(served) == requests and all(r.done and r.out for r in served),
          f"server answered {len(served)} of {requests} requests")
    return srv, served, secs


def cast(cfg, params, dtype: str):
    """(cfg, params) in ``dtype``: activations and floating weights."""
    import jax
    import jax.numpy as jnp
    return (cfg.replace(dtype=dtype, param_dtype=dtype),
            jax.tree.map(lambda a: a.astype(dtype)
                         if jnp.issubdtype(a.dtype, jnp.floating) else a,
                         params))


def parity(cfg, model, params, req) -> dict:
    """Cached decode vs forward for one served request (same dtype)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.serve import MAX_LEN
    from repro.models.model import LM
    P, out = len(req.prompt), list(req.out)
    toks = jnp.asarray(np.concatenate([req.prompt, out])[None], jnp.int32)
    fwd = jax.jit(lambda p, t: model.forward(p, {"tokens": t})[0])
    ref = np.asarray(fwd(params, toks)[0, P - 1:P - 1 + len(out)], np.float32)
    prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t},
                                                 max_len=MAX_LEN))
    step = jax.jit(model.decode_step)
    last, caches = prefill(params, toks[:, :P])
    got = [np.asarray(last[0], np.float32)]
    for i, tok in enumerate(out[:-1]):
        lg, caches = step(params, caches,
                          {"tokens": jnp.asarray([[tok]], jnp.int32)},
                          jnp.int32(P + i))
        got.append(np.asarray(lg[0], np.float32))
    got = np.stack(got)
    diff = got - ref
    rel_rms = float(np.sqrt(np.mean(diff ** 2) / np.mean(ref ** 2)))
    max_abs = float(np.abs(diff).max())
    check(rel_rms <= LOGIT_RTOL,
          f"request {req.rid}: cached-decode logits differ from the forward "
          f"pass by relative RMS {rel_rms:.3g} > {LOGIT_RTOL}")
    exact = int(np.sum(ref.argmax(axis=-1) == np.asarray(out)))
    check(exact == len(out),
          f"request {req.rid}: server tokens {out} differ from the forward "
          f"argmax {ref.argmax(axis=-1).tolist()}")
    cfg32, p32 = cast(cfg, params, "float32")
    m32 = LM(cfg32)
    ref32 = np.asarray(jax.jit(lambda p, t: m32.forward(p, {"tokens": t})[0])(
        p32, toks)[0, P - 1:P - 1 + len(out)])
    return dict(rid=req.rid, prompt_len=P, tokens=len(out),
                rel_rms=rel_rms, max_abs=max_abs, tokens_exact=exact,
                max_abs_vs_f32=float(np.abs(ref - ref32).max()))


def serve_phase(name: str, arch: str = SERVE_ARCH, reduced: bool = False,
                parity_requests: int = PARITY_REQUESTS) -> dict:
    """One-chip serve through `DecodeServer` plus the parity checks."""
    import jax
    from repro.sharding.partition import NULL_PLAN
    cfg, model, params = load_model(arch, reduced)
    _, served, cold_s = serve(cfg, params, NULL_PLAN)
    _, again, warm_s = serve(cfg, params, NULL_PLAN)
    check([r.out for r in again] == [r.out for r in served],
          "a second server over the same requests gave other tokens")
    longest = sorted(served, key=lambda r: -len(r.out))[:parity_requests]
    checks = [parity(cfg, model, params, r) for r in longest]
    tokens = sum(len(r.out) for r in served)
    out = dict(arch=arch, dtype=cfg.dtype, layers=cfg.num_layers,
               d_model=cfg.d_model, vocab=cfg.vocab_size,
               requests=len(served), tokens=tokens, first_run_s=cold_s,
               warm_run_s=warm_s, tokens_per_s=tokens / warm_s,
               logit_rtol=LOGIT_RTOL, parity=checks,
               peak_bytes_in_use=peak_bytes(jax.devices()))
    emit(name, **out)
    return out


def serve_homes_phase(name: str, homes: int, arch: str = SERVE_ARCH,
                      reduced: bool = False) -> dict:
    """The server over ``homes`` devices against one chip, same process.

    In float32 (the weights cast exactly from the seeded bf16 ones) the
    tokens must equal those of the one-chip server with the same slots.  In
    bf16 each home decodes one slot where that server decodes four, and a
    batch of one tiles the matmuls otherwise, so bf16 rounding may break a
    near-tie the other way.  The phase therefore also serves one chip with
    a single slot: the bf16 tokens over ``homes`` must equal those, and how
    many agree between one and four slots on one chip is information."""
    import jax
    from repro.launch.serve import MAX_LEN, build_plan
    from repro.sharding.partition import NULL_PLAN
    cfg, model, params = load_model(arch, reduced)
    agree, differ = {}, {}
    for dtype in dict.fromkeys(("float32", cfg.dtype)):
        c, p = cast(cfg, params, dtype)
        srv, served, secs = serve(c, p, build_plan(None, SERVE_SLOTS,
                                                   MAX_LEN, c))
        _, single, single_s = serve(c, p, NULL_PLAN)
        _, one_slot, _ = serve(c, p, NULL_PLAN, slots=1)
        same = lambda xs, ys: sum(x.out == y.out for x, y in zip(xs, ys))
        agree[dtype] = dict(one_chip=same(served, single),
                            one_chip_1slot=same(served, one_slot),
                            one_chip_1slot_vs_4slots=same(one_slot, single))
        want = single if dtype == "float32" else one_slot
        differ[dtype] = {a.rid: (a.out, b.out) for a, b in zip(served, want)
                         if a.out != b.out}
        if dtype == "float32":
            homed, run_s, one_chip_s = srv, secs, single_s
            request_homes = sorted({r.home for r in served})
    check(not any(differ.values()),
          f"{homes}-home tokens differ from the one-chip tokens (rid: "
          f"(homes, one chip)): {differ}; requests equal: {agree}")
    owners = sorted(set(homed.locale.owners(SERVE_SLOTS)))
    check(len(owners) == homes, f"slots homed on {owners}, want {homes}")
    check(len(request_homes) == homes,
          f"requests decoded on homes {request_homes}")
    B = SERVE_SLOTS
    cache = jax.jit(lambda: homed.locale.pin_tree(
        model.init_cache(B, MAX_LEN), dim=1, size=B))()
    for leaf in jax.tree.leaves(cache):
        if leaf.ndim > 1 and leaf.shape[1] == B:
            devs = {s.device.id for s in leaf.addressable_shards}
            rows = {s.data.shape[1] for s in leaf.addressable_shards}
            check(len(devs) == homes and rows == {B // homes},
                  f"KV cache leaf {leaf.shape} sits on devices "
                  f"{sorted(devs)} with {rows} slots each")
    out = dict(arch=arch, homes=homes, requests=len(served),
               requests_equal=agree, run_s=run_s,
               one_chip_run_s=one_chip_s, slot_homes=list(
                   homed.locale.owners(SERVE_SLOTS)),
               request_homes=request_homes,
               peak_bytes_in_use=peak_bytes(jax.devices()))
    emit(name, **out)
    return out


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------
def one_chip(fused_keys=None, reference_keys: int = REFERENCE_KEYS,
             reduced: bool = False) -> None:
    """The one-chip phases, on the first device only."""
    import jax
    from repro.core import Locale
    from repro.kernels.local_sort import max_chunk
    case8 = Locale.auto(case_policy(8), devices=jax.devices()[:1])
    # the largest chunk the fused local sort holds in VMEM
    fused_keys = fused_keys or max_chunk()
    sort_phase("sort-fused", case8, fused_keys)
    sort_phase("sort-padded", case8, odd_size(fused_keys))
    sort_phase("sort-reference", case8, reference_keys, warm_call=False,
               local_phase="reference", num_workers=REFERENCE_WORKERS)
    serve_phase("serve", reduced=reduced)


def four_chips(chips: int, keys_per_chip: int = KEYS_PER_CHIP_4,
               reduced: bool = False) -> None:
    """The multi-chip paths and what each is compared with."""
    from repro.core import Locale, LocalisationPolicy
    from repro.launch.mesh import make_host_mesh
    n = chips * keys_per_chip
    # case 7 at 3n/4: whole 128-key rows on every chip, but no power of two,
    # so the merge-split kernel pads (a count the chips do not divide evenly
    # takes minutes to compile)
    for case, keys in ((8, n), (7, 3 * n // 4)):
        sort_phase(f"sort-case{case}-{chips}chip",
                   Locale.auto(case_policy(case)), keys, expect_devices=chips)
    pods = Locale(mesh=make_host_mesh(n_pods=2, n_data=chips // 2,
                                      n_model=1),
                  axis=("pod", "data"),
                  policy=LocalisationPolicy.hierarchical())
    sort_phase(f"sort-hier-2x{chips // 2}", pods, n, expect_devices=chips)
    serve_homes_phase(f"serve-{chips}homes", chips, reduced=reduced)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every one-chip phase; 4: the multi-chip paths")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: {SRC / 'repro'} not found — run from a checkout "
              f"of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found — JAX sees {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    print(f"# compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 1:
        one_chip()
    else:
        four_chips(args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
