"""Paper Fig. 4 — memory-striping analogue: source-width of the fetch phase.

TILEPro64 striping spreads pages over 1-4 memory controllers. The pod
analogue: the workers' chunk-fill (localise) pulls from an input striped
over `width` source devices — width 1 is the single-controller hot spot,
width 8 is fully striped. We time the reshard itself (the memory-fetch
phase); the compute phase is locality-cached and unaffected, matching the
paper's conclusion that striping is transparent once caching is on.

Both the striped source and the chunk-fill target are `Locale`s: the fetch
is literally `target_locale.put(...)`.

The ``--pipeline`` section is the acceptance benchmark for the *generation*
half of striping (the ROADMAP's remaining item): `data.SyntheticLM` with
``striped=True`` generates each batch stripe for its home device
(per-device callbacks under `Locale.make`), vs the ``striped=False`` oracle
that builds the full host array first and places it afterwards.  The
embedding family is where striping pays most — the host oracle materialises
the whole ``(B, S, D)`` array before a single byte reaches a device.
"""
import argparse

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.core import Locale
from benchmarks.common import timeit


def bench_pipeline(logb: int):
    """striped vs host-built batch generation, token + embedding families."""
    from repro.configs import get_config, reduce_config
    from repro.data import SyntheticLM

    B = 1 << logb
    mesh = (jax.make_mesh((len(jax.devices()),), ("data",),
                          axis_types=(AxisType.Auto,))
            if len(jax.devices()) > 1 else None)
    cases = [("tokens", reduce_config(get_config("qwen3-0.6b")), 128),
             ("embeds", reduce_config(get_config("musicgen-medium")), 128)]
    for label, cfg, S in cases:
        for striped in (False, True):
            ds = SyntheticLM(cfg, B, S, seed=3, mesh=mesh, striped=striped)
            step = [0]

            def make_batch():
                step[0] += 1           # fresh step: no row-cache reuse
                return jax.block_until_ready(
                    jax.tree.leaves(ds.batch(step[0])))

            t = timeit(make_batch, warmup=1, iters=3)
            mode = "striped" if striped else "host"
            print(f"striping_pipeline_{label}_{mode},{t:.0f},"
                  f"B{B}_S{S}_born_on_{'chunk' if striped else 'host'}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--logn", type=int, default=22)
    ap.add_argument("--logb", type=int, default=9,
                    help="log2 global batch for the --pipeline section")
    ap.add_argument("--pipeline", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run the striped-generation acceptance section")
    args = ap.parse_args(argv)
    n = 1 << args.logn
    devs = jax.devices()
    n_dev = len(devs)
    print("name,us_per_call,derived")
    if n_dev == 1:
        print("striping_skipped,,single_device")
        return
    target = Locale.auto()
    for w in dict.fromkeys(w for w in (1, 2, 4, n_dev) if w <= n_dev):
        src = Locale.auto(devices=devs[:w])

        def make():
            placed = src.put(jnp.arange(n, dtype=jnp.int32))
            return placed.data

        def fetch(x):
            return target.put(x).data   # workers fill their chunks

        x = make()
        t = timeit(lambda: fetch(x), warmup=1, iters=3)
        print(f"striping_width{w},{t:.0f},fetch_from_{w}_controllers")
    if args.pipeline:
        bench_pipeline(args.logb)


if __name__ == "__main__":
    main()
