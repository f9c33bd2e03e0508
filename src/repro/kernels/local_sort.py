"""Fused VMEM-resident local phase: leaf sorts + merge tree in ONE kernel.

The engine's reference local phase runs the Pallas leaf sort, then a Python
``while runs.shape[0] > 1`` loop of vmapped searchsorted rank merges — every
tree level materialises the full chunk to HBM and reads it back.  The paper's
Algorithm 2 keeps each worker's `input_cpy` cache-resident for the *entire*
local phase, not just the leaves; this kernel is that discipline for real:

  * one grid step per chunk: the chunk is copied HBM->VMEM once (one DMA
    into a single VMEM buffer),
  * the bitonic leaf stages AND all log2(#leaves) merge-tree levels run
    on-chip (the merge levels are the high-`k` stages of the same bitonic
    network — a bitonic merge of two sorted leaves is exactly stage 2*leaf),
  * the fully sorted run is copied back once.

HBM traffic: 2*chunk*itemsize total, vs 2*chunk*itemsize*(1 + log2(w)) for
the reference tree — the Fig-1 amortisation argument applied to the sort's
own local phase.

Sentinel padding is folded into the kernel: a chunk whose length is not a
power of two (of at least one tile) is extended to one with `KEY_MAX`
sentinels as the network's first pass loads each register block — the tail
rows are never copied from HBM, and the mask is written with full aligned
tiles.  Only a chunk that is not a whole number of 128-key rows is padded
in HBM first (small chunks, never the engine's power-of-two ones).

VMEM: one buffer of `padded_len(C)` int32 keys per grid step — the
inputs and outputs stay in HBM (`pl.ANY`) and move by DMA.  `max_chunk`
is the largest chunk that fits `VMEM_BYTES_PER_CORE`.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import (VMEM_BYTES_PER_CORE, VMEM_HEADROOM,
                           resolve_interpret, vmem_bytes)
from repro.kernels.bitonic_sort import (KEY_MAX, LANES, block_keys, from_keys,
                                        padded_len, sort_network, sweep_plan,
                                        to_keys)
from repro.obs.tracelog import get_tracer


def max_chunk() -> int:
    """Largest power-of-two chunk of 32-bit keys one local sort holds."""
    return 1 << ((VMEM_BYTES_PER_CORE - VMEM_HEADROOM) // 4).bit_length() - 1


def _kernel(x_hbm, o_hbm, w, *, C: int):
    i = pl.program_id(0)
    rows_in = x_hbm.shape[1]
    data = w if rows_in == w.shape[0] else w.at[pl.ds(0, rows_in)]
    pltpu.sync_copy(x_hbm.at[i], data)
    prologue = None
    if C < w.shape[0] * LANES:
        def prologue(v, idx):
            return jnp.where(idx < C, v, KEY_MAX)
    sort_network(w, prologue)
    pltpu.sync_copy(data, o_hbm.at[i])


def _count_sweeps(rows: int, L: int) -> None:
    """Count the network's VMEM sweeps and the substages it applies inside
    a register block (no VMEM round trip between them), for all ``rows``
    grid steps.  Runs when the call is traced."""
    tr = get_tracer()
    if not tr.enabled:
        return
    plan, B = sweep_plan(L), block_keys(L)
    tr.count("local_sort.sweeps", rows * len(plan), cat="kernel", L=L)
    tr.count("local_sort.register_substages",
             rows * sum(len(s) for s in plan if s[0][1] < B),
             cat="kernel", L=L)


def local_sort(x, *, interpret: Optional[bool] = None):
    """Sort each row of x: (rows, C) -> (rows, C), any C >= 1.

    One grid step per row; the whole row (a device chunk: its leaves and the
    full local merge tree) stays in VMEM between the single read and the
    single write-back.  int32 and float32 keys; floats sort in total order
    (``-0.0`` before ``+0.0``), bit-exact as a permutation of the input.
    """
    rows, C = x.shape
    L = padded_len(C)
    _count_sweeps(rows, L)
    keys = to_keys(x)
    Cr = -(-C // LANES) * LANES
    if Cr != C:
        keys = jnp.pad(keys, ((0, 0), (0, Cr - C)), constant_values=KEY_MAX)
    keys = keys.reshape(rows, Cr // LANES, LANES)
    out = pl.pallas_call(
        partial(_kernel, C=C),
        grid=(rows,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(keys.shape, jnp.int32),
        scratch_shapes=[pltpu.VMEM((L // LANES, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(L * 4)),
        interpret=resolve_interpret(interpret),
        name="local_sort",
    )(keys)
    return from_keys(out.reshape(rows, Cr)[:, :C], x.dtype)
