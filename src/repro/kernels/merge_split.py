"""Bitonic merge-split kernel: compute only the half you keep.

The engine's block bitonic network exchanges full chunks with a partner and
keeps either the low or the high half of the merged 2C run.  The reference
`_merge_split` merges *everything* (`merge_sorted` -> 2C elements written to
HBM) and then discards half — 2x the merge compute and >2x the HBM traffic
of what the result actually needs.

This kernel never forms the 2C run.  For sorted ``a`` and ``b``, the C
elementwise minima of ``a[i]`` and ``b[C-1-i]`` are exactly the low half of
the merge and the C maxima the high half, each a bitonic sequence (Batcher's
half-cleaner).  So per row it DMAs the two runs into VMEM, makes one pass
that keeps the min or the max against ``b`` read back to front, then sorts
the kept bitonic half with the last stage of the bitonic network: log2(C)
half-cleaner substages.  O(C log C) on-chip work, 2C reads and C writes of
HBM, no 2C intermediate.

The back-to-front pass is register-blocked (`half_clean_blocks`), as the
network's block sweeps are: each loop iteration loads ``HALF_CLEAN_ROWS``
rows of ``w`` and the mirrored block of ``b`` as ``(n, 8, 128)`` values,
reverses ``b``'s block whole (the register order by renaming, every tile by
the same XOR-partner rotations at once — Mosaic has no ``rev``), and stores
the min or the max.  So the chain of 10 rotation levels runs over n
independent registers, not one.

The result equals ``merge_sorted(a, b)[:C]`` / ``[C:]`` as values, ties and
sentinels included; keys that compare equal but differ in bits (``-0.0`` and
``+0.0``) come out in total order rather than merge order.

The keep flag is a per-row scalar read from SMEM.  The batched form is the
hierarchical engine's cross-pod replay unit: row r merges pod r's chunk with
its partner pod's chunk under its own keep flag.

`run_merge` is the same merge-split over the rows of one array, each row's
partner row read from an SMEM table: one substage of the block bitonic
network that merges a chunk's VMEM-sized runs in HBM (the engine's local
phase past one `local_sort`).  Its pallas_call has its own name, so a
device trace tells it from the cross-chip merge-split.  `max_run` is the
longest run whose pair fits `VMEM_BYTES_PER_CORE`.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import (VMEM_BYTES_PER_CORE, VMEM_HEADROOM,
                           resolve_interpret, vmem_bytes)
from repro.kernels.bitonic_sort import (KEY_MAX, KEY_MIN, LANES, SUBLANES,
                                        TILE, _pairs, _unpairs, block_keys,
                                        from_keys, merge_stage, padded_len,
                                        to_keys)

HALF_CLEAN_ROWS = 256            # rows the half-clean pass holds in registers


def half_clean_blocks(L: int) -> int:
    """Loop iterations of the half-clean pass over a row of L keys: one
    register block of ``HALF_CLEAN_ROWS`` rows (or the whole row) each."""
    return L // block_keys(L, HALF_CLEAN_ROWS)


def _reverse(v):
    """Reverse the keys of block ``v`` (n, 8, 128): i -> i ^ (n * TILE - 1).

    Registers t and t ^ d swap places by renaming (leading-dim reshapes);
    inside every tile, key i takes key i ^ j by two rotations and a select,
    for each bit j of the tile index.
    """
    d = v.shape[0] // 2
    while d:
        a, b = _pairs(v, d)
        v = _unpairs(b, a)
        d //= 2
    idx = (jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1))
    j = 1
    while j < TILE:
        axis, s, n = (2, j, LANES) if j < LANES else (1, j // LANES, SUBLANES)
        v = jnp.where((idx & j) == 0, pltpu.roll(v, n - s, axis),
                      pltpu.roll(v, s, axis))
        j *= 2
    return v


def max_run() -> int:
    """Longest power-of-two run of 32-bit keys whose merge-split holds both
    runs in VMEM."""
    return 1 << ((VMEM_BYTES_PER_CORE - VMEM_HEADROOM) // 8).bit_length() - 1


def _kernel(keep_ref, a_hbm, b_hbm, o_hbm, w, bw):
    i = pl.program_id(0)
    pltpu.sync_copy(a_hbm.at[i], w)
    pltpu.sync_copy(b_hbm.at[i], bw)
    _split(w, bw, keep_ref[i] != 0)
    pltpu.sync_copy(w, o_hbm.at[i])


def _run_kernel(partner_ref, keep_ref, x_hbm, o_hbm, w, bw):
    i = pl.program_id(0)
    pltpu.sync_copy(x_hbm.at[i], w)
    pltpu.sync_copy(x_hbm.at[partner_ref[i]], bw)
    _split(w, bw, keep_ref[i] != 0)
    pltpu.sync_copy(w, o_hbm.at[i])


def _split(w, bw, keep_low):
    """Leave in ``w`` the kept half of the merge of sorted runs ``w`` and
    ``bw``, sorted."""
    _half_clean(w, bw, keep_low)
    merge_stage(w, w.shape[0] * LANES)


def _half_clean(w, bw, keep_low):
    """Keep in ``w`` the min (``keep_low``) or the max of ``w[i]`` and
    ``bw[L-1-i]``, a register block an iteration: block t of ``w`` pairs
    with block ``nb-1-t`` of ``bw``, reversed."""
    rows = w.shape[0]
    nb = half_clean_blocks(rows * LANES)
    br = rows // nb
    shape = (br // SUBLANES, SUBLANES, LANES)

    def body(t, carry):
        r = pl.multiple_of(t * br, br)
        rb = pl.multiple_of((nb - 1 - t) * br, br)
        a = w[pl.ds(r, br), :].reshape(shape)
        b = _reverse(bw[pl.ds(rb, br), :].reshape(shape))
        w[pl.ds(r, br), :] = jnp.where(keep_low, jnp.minimum(a, b),
                                       jnp.maximum(a, b)).reshape(br, LANES)
        return carry

    jax.lax.fori_loop(0, nb, body, 0)


def _merge_split_keys(a, b, keep, interpret: bool):
    rows, L = a.shape
    shape = (rows, L // LANES, LANES)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _kernel,
        grid=(rows,),
        in_specs=[smem, hbm, hbm],
        out_specs=hbm,
        out_shape=jax.ShapeDtypeStruct(shape, jnp.int32),
        scratch_shapes=[pltpu.VMEM(shape[1:], jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(2 * L * 4)),
        interpret=interpret,
        name="merge_split",
    )(keep, a.reshape(shape), b.reshape(shape)).reshape(rows, L)


def merge_split(a, b, keep_low, *, interpret: Optional[bool] = None):
    """Row-wise merge-split. a, b: (rows, C) sorted rows; keep_low: per-row
    (or scalar, broadcast) flag — True keeps the low half of the merged 2C
    run, False the high half.  Returns (rows, C), equal to
    ``merge_sorted(a[r], b[r])[:C]`` / ``[C:]``.
    """
    rows, C = a.shape
    assert b.shape == (rows, C), (a.shape, b.shape)
    keep = jnp.asarray(keep_low)
    if keep.ndim == 0:
        keep = keep[None]
    if keep.ndim != 1 or keep.shape[0] not in (1, rows):
        raise ValueError(
            f"keep_low must be a scalar or a length-{rows} vector of "
            f"per-row flags (one per merge-split row); got shape "
            f"{jnp.shape(keep_low)} for a/b of shape {(rows, C)}")
    keep = jnp.broadcast_to(keep, (rows,))
    ka, kb = to_keys(a), to_keys(b)
    L = padded_len(C)
    pad = L - C
    if pad:
        # a low half survives sentinels appended past the runs, a high half
        # sentinels placed before them; both keep the rows sorted
        k = keep[:, None]

        def fit(v):
            return jnp.where(k, jnp.pad(v, ((0, 0), (0, pad)),
                                        constant_values=KEY_MAX),
                             jnp.pad(v, ((0, 0), (pad, 0)),
                                     constant_values=KEY_MIN))
        ka, kb = fit(ka), fit(kb)
    out = _merge_split_keys(ka, kb, keep.astype(jnp.int32),
                            resolve_interpret(interpret))
    if pad:
        out = jnp.where(keep[:, None], out[:, :C], out[:, pad:])
    return from_keys(out, a.dtype)


def run_merge(runs, partner, keep_low, *, interpret: Optional[bool] = None):
    """One substage of a merge-split network over the rows of ``runs``.

    runs: (rows, L) int32 keys, every row sorted, L a power of two of at
    least one tile; partner, keep_low: static per-row tables (numpy).  Row
    r of the result is the low (keep_low[r]) or high half of the merge of
    rows r and partner[r], as `merge_split` gives it; nothing is gathered
    in HBM, the kernel reads the partner row by DMA.
    """
    rows, L = runs.shape
    if runs.dtype != jnp.int32 or L != padded_len(L):
        raise ValueError(f"run_merge takes int32 keys in rows of a power "
                         f"of two of at least {TILE}; got {runs.dtype} "
                         f"rows of {L}")
    shape = (rows, L // LANES, LANES)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _run_kernel,
        grid=(rows,),
        in_specs=[smem, smem, hbm],
        out_specs=hbm,
        out_shape=jax.ShapeDtypeStruct(shape, jnp.int32),
        scratch_shapes=[pltpu.VMEM(shape[1:], jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(2 * L * 4)),
        interpret=resolve_interpret(interpret),
        name="run_merge",
    )(jnp.asarray(partner, jnp.int32), jnp.asarray(keep_low, jnp.int32),
      runs.reshape(shape)).reshape(rows, L)
