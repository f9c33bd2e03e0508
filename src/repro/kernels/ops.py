"""Jitted public wrappers for the Pallas kernels.

Each kernel runs compiled on a TPU and in the Pallas interpreter on every
other backend (`repro.kernels.resolve_interpret`).
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import local_sort as _ls
from repro.kernels import localised_copy as _lc
from repro.kernels import merge_split as _ms
from repro.core.sort import merge_sorted


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k)


@jax.jit
def chunked_sort(x):
    """Full 1-D sort: Pallas local sort per chunk + rank-merge tree."""
    runs = local_sort(x)
    while runs.shape[0] > 1:
        runs = jax.vmap(merge_sorted)(runs[0::2], runs[1::2])
    return runs[0]


@jax.jit
def local_sort(x):
    """Fused local phase: leaf sorts + the whole merge tree, one VMEM pass."""
    return _ls.local_sort(x)


@jax.jit
def merge_split(a, b, keep_low):
    """Bitonic merge-split: only the kept half is computed/written."""
    return _ms.merge_split(a, b, keep_low)


@partial(jax.jit, static_argnames=("reps",))
def localised_copy(x, reps: int):
    return _lc.localised_copy(x, reps)
