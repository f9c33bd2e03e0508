"""Kernel-level localisation (Fig-1, TPU-native): VMEM reuse arithmetic.

interpret-mode wall times are Python emulation (not TPU perf) — the honest
derived metric is the HBM-traffic ratio: the localised kernel reads+writes
each chunk once regardless of R, the non-localised path streams the full
array every pass. derived = modelled HBM-bytes ratio (== Fig-1 asymptote).

The ``local``/``merge`` sections benchmark the engine's VMEM-resident local
phase (the sort's own Fig-1 argument):

  * ``kernel_local_*`` — leaf-sort-only kernel vs the FUSED local_sort
    kernel (leaves + whole merge tree in one VMEM pass) vs the reference
    jnp local phase (leaf kernel + HBM-materialising vmapped rank merges),
    swept over chunk sizes.  derived = modelled HBM bytes ratio
    (reference streams the chunk once per tree level, fused touches it
    once: ratio = 1 + log2(leaves)).
  * ``kernel_merge_*`` — the bitonic merge_split kernel (computes ONLY
    the kept half) vs merge-everything-discard-half.  derived = modelled
    HBM ratio 7/3 and merged-elems ratio 2.
"""
import argparse

import jax
import jax.numpy as jnp

from repro.core.sort import merge_sorted
from repro.kernels import ops, ref
from benchmarks.common import timeit

CHUNKS, L = 8, 2048
SECTIONS = ("copy", "attention", "sort", "local", "merge")

_merge_rows = jax.vmap(merge_sorted)


def bench_copy():
    x = jax.random.normal(jax.random.key(0), (CHUNKS, L), jnp.float32)
    for reps in (8, 64):
        t_loc = timeit(lambda: ops.localised_copy(x, reps))
        t_ref = timeit(lambda: jax.jit(
            lambda y: ref.localised_copy_ref(y, reps))(x))
        bytes_localised = 2 * x.size * 4                 # one read + one write
        bytes_streamed = 2 * x.size * 4 * reps           # per-pass streaming
        print(f"kernel_localised_copy_reps{reps},{t_loc:.0f},"
              f"hbm_ratio={bytes_streamed / bytes_localised:.0f}x")
        print(f"kernel_streaming_ref_reps{reps},{t_ref:.0f},")


def bench_attention():
    # flash attention: VMEM-blocked vs dense-materialised scores
    B, H, S, hd = 1, 4, 1024, 64
    q = jax.random.normal(jax.random.key(1), (B, H, S, hd), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(2), (B, H, S, hd), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(3), (B, H, S, hd), jnp.bfloat16)
    t_flash = timeit(lambda: ops.flash_attention(q, k, v, causal=True),
                     iters=1)
    t_dense = timeit(lambda: jax.jit(
        lambda a, b, c: ref.attention_ref(a, b, c, causal=True))(q, k, v))
    dense_hbm = B * H * S * S * 4 * 2          # scores materialised r+w (f32)
    flash_hbm = 3 * B * H * S * hd * 2 + B * H * S * hd * 2
    print(f"kernel_flash_attention_s{S},{t_flash:.0f},"
          f"score_hbm_saved={dense_hbm / flash_hbm:.1f}x")
    print(f"kernel_dense_attention_s{S},{t_dense:.0f},")


def bench_sort():
    # bitonic local sort of 1024-key rows (one leaf per row)
    xs = jax.random.randint(jax.random.key(4), (8, 1024), 0, 1 << 30,
                            dtype=jnp.int32)
    t_bit = timeit(lambda: ops.local_sort(xs), iters=1)
    t_ref = timeit(lambda: jax.jit(ref.sort_ref)(xs))
    print(f"kernel_bitonic_sort_8x1024,{t_bit:.0f},"
          f"interpret_mode={jax.default_backend() != 'tpu'}")
    print(f"kernel_jnp_sort_8x1024,{t_ref:.0f},")


def bench_local(chunks: int, logcs, leaves: int):
    """Fused VMEM-resident local phase vs leaf-only vs reference jnp tree."""
    for logc in logcs:
        C = 1 << logc
        leaf = max(1, C // leaves)
        w = C // leaf                               # leaves per chunk
        x = jax.random.randint(jax.random.key(5), (chunks, C), 0, 1 << 30,
                               dtype=jnp.int32)

        @jax.jit
        def reference(y):
            # today's engine reference path: Pallas leaf sort, then the
            # HBM-materialising Python merge-tree of vmapped rank merges
            runs = ops.local_sort(y.reshape(chunks * w, leaf))
            runs = runs.reshape(chunks, w, leaf)
            while runs.shape[1] > 1:
                runs = jax.vmap(_merge_rows)(runs[:, 0::2], runs[:, 1::2])
            return runs.reshape(chunks, C)

        # interpret-mode wall clocks are noisy at small chunks: best-of-10
        t_leaf = timeit(lambda: ops.local_sort(x.reshape(chunks * w, leaf)),
                        iters=10)
        t_fused = timeit(lambda: ops.local_sort(x), iters=10)
        t_ref = timeit(lambda: reference(x), iters=10)
        hbm_fused = 2 * chunks * C * 4              # one VMEM round trip
        hbm_ref = hbm_fused * (1 + max(0, w.bit_length() - 1))
        print(f"kernel_local_leaf_only_c{C},{t_leaf:.0f},leaf={leaf}")
        print(f"kernel_local_fused_c{C},{t_fused:.0f},"
              f"hbm_saved={hbm_ref / hbm_fused:.0f}x;"
              f"speedup={t_ref / max(t_fused, 1e-9):.2f}")
        print(f"kernel_local_reference_c{C},{t_ref:.0f},"
              f"tree_levels={w.bit_length() - 1}")


def bench_merge(chunks: int, logcs):
    """bitonic merge_split (kept half only) vs merge-and-discard-half."""
    keep = (jnp.arange(chunks) % 2) == 0
    for logc in logcs:
        C = 1 << logc
        a = jnp.sort(jax.random.randint(jax.random.key(6), (chunks, C), 0,
                                        1 << 30, dtype=jnp.int32), axis=-1)
        b = jnp.sort(jax.random.randint(jax.random.key(7), (chunks, C), 0,
                                        1 << 30, dtype=jnp.int32), axis=-1)

        @jax.jit
        def discard_half(u, v, k):
            merged = _merge_rows(u, v)              # (chunks, 2C) to HBM
            return jnp.where(k[:, None], merged[:, :C], merged[:, C:])

        t_split = timeit(lambda: ops.merge_split(a, b, keep), iters=10)
        t_full = timeit(lambda: discard_half(a, b, keep), iters=10)
        print(f"kernel_merge_split_c{C},{t_split:.0f},"
              f"hbm_saved={7 / 3:.2f}x;elems_saved=2x;"
              f"speedup={t_full / max(t_split, 1e-9):.2f}")
        print(f"kernel_merge_discard_c{C},{t_full:.0f},")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"comma list of sections to run ({','.join(SECTIONS)})")
    ap.add_argument("--chunks", type=int, default=8,
                    help="rows (device chunks) per local/merge case")
    ap.add_argument("--logcs", type=lambda s: [int(c) for c in s.split(",")],
                    default=[10, 12, 14],
                    help="comma list of log2 chunk sizes for local/merge")
    ap.add_argument("--leaves", type=int, default=8,
                    help="leaves per chunk in the local-phase cases")
    args = ap.parse_args(argv)
    only = set((args.only or ",".join(SECTIONS)).split(","))
    unknown = only - set(SECTIONS)
    if unknown:
        raise SystemExit(f"unknown sections {sorted(unknown)}; "
                         f"want a subset of {SECTIONS}")
    print("name,us_per_call,derived")
    if "copy" in only:
        bench_copy()
    if "attention" in only:
        bench_attention()
    if "sort" in only:
        bench_sort()
    if "local" in only:
        bench_local(args.chunks, args.logcs, args.leaves)
    if "merge" in only:
        bench_merge(args.chunks, args.logcs)


if __name__ == "__main__":
    main()
