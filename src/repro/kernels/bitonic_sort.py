"""Bitonic sorting network over a VMEM-resident block of int32 keys.

The network runs in place on a VMEM ref of shape ``(R, 128)``: key ``i``
sits at row ``i // 128``, lane ``i % 128``, so every (8, 128) tile is one
vector register and ``R * 128`` is a power of two of at least one tile.
Mosaic has no gather and no ``rev``, so each compare-exchange substage
(partner ``i XOR j``) is built from what the chip does natively:

  * ``j < 1024`` (inside one tile): two ``pltpu.roll`` rotations (lanes for
    ``j < 128``, sublanes otherwise) bring ``v[i + j]`` and ``v[i - j]``
    into place, and a select on ``i & j`` picks the partner;
  * ``j >= 1024`` (whole tiles): the two partner registers are min/max'ed
    as they stand — no data movement at all.

The network is register-blocked (`sweep_plan`).  A sweep loads one block
of ``BLOCK_ROWS`` rows at a time as one ``(BLOCK_ROWS / 8, 8, 128)`` value
of registers, applies every substage it holds, and stores the block back.
A block is closed under every stride below its size, so one *block sweep*
applies all of a stage's strides ``j < BLOCK_ROWS * 128``: the first sweep
runs every stage ``k <= BLOCK_ROWS * 128`` whole, and each later stage
ends in one.  Only strides past a block take a *row sweep* of their own,
which pairs two ``BLOCK_ROWS``-row halves.  Every loop iteration so carries
64 independent registers (or register pairs), where the chain of load,
rotate, select, min/max and store bounds a loop over a few.  For 2^23 keys
that is 36 sweeps and 2,816 loop iterations (1,792 of them in row sweeps).

The block height is measured: on a TPU v5e the 2^23-key network took
25.2, 13.9, 8.2, 5.6, 5.1 and 5.0 ms with blocks of 32, 64, 128, 256, 512
and 1024 rows, and its first compile 1.4, 1.7, 2.1, 4-6, 11-15 and 34 s.
With 512 rows the first sweep takes 2.8 ms, each later block sweep
0.24 ms, each row sweep 0.006-0.016 ms and the two DMAs 0.21 ms.

A stage's direction (ascending where ``i & k == 0``) is a vector mask for
``k < 1024``.  Past that, whole registers share it, and those that sort
descending are complemented (``~x`` reverses the int32 order) for the
stage, which then runs ascending: picked at trace time for ``k`` inside the
block, by one run-time flag per block past it.  No full-block index stays
live: masks come from one tile's iota.

Keys are int32.  `to_keys` / `from_keys` map float32 onto int32 keys whose
signed order is the float total order (``-0.0`` before ``+0.0``), so the
network is an exact permutation of the input bits; min/max on the floats
themselves would not be.  The sentinel `KEY_MAX` sorts after every key.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
TILE = SUBLANES * LANES          # keys in one (8, 128) vector register
BLOCK_ROWS = 512                 # rows a sweep keeps in registers (64 tiles)
KEY_MAX = int(jnp.iinfo(jnp.int32).max)
KEY_MIN = int(jnp.iinfo(jnp.int32).min)
KEY_DTYPES = (jnp.dtype(jnp.int32), jnp.dtype(jnp.float32))

Sweep = Tuple[Tuple[int, int], ...]      # the (k, j) substages of one sweep


def to_keys(x):
    """Order-preserving map of int32/float32 values onto int32 keys."""
    if x.dtype == jnp.int32:
        return x
    if x.dtype != jnp.float32:
        raise TypeError(f"sort keys must be one of {KEY_DTYPES}, got {x.dtype}")
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return b ^ ((b >> 31) & KEY_MAX)      # negative floats: flip magnitude


def from_keys(k, dtype):
    """Inverse of `to_keys` (the magnitude flip is an involution)."""
    if jnp.dtype(dtype) == jnp.int32:
        return k
    return jax.lax.bitcast_convert_type(k ^ ((k >> 31) & KEY_MAX), dtype)


def padded_len(C: int) -> int:
    """Network length for C keys: a power of two, at least one tile."""
    return max(TILE, 1 << max(0, (C - 1).bit_length()))


def _halving(j: int):
    while j >= 1:
        yield j
        j //= 2


def block_keys(L: int, block_rows: int = BLOCK_ROWS) -> int:
    """Keys in one register block of an L-key network; a sweep whose stride
    reaches this far is a row sweep."""
    return min(L, block_rows * LANES)


def _stage_plan(k: int, B: int) -> List[Sweep]:
    """Stage ``k`` for blocks of B keys: a row sweep per stride past a
    block, then one block sweep for the rest."""
    return ([((k, j),) for j in _halving(k // 2) if j >= B]
            + [tuple((k, j) for j in _halving(min(k, B) // 2))])


def sweep_plan(L: int, block_rows: int = BLOCK_ROWS) -> List[Sweep]:
    """The VMEM sweeps that sort L keys, in order, each as its substages.

    Together they apply every substage (k, j) of the bitonic network once,
    in the network's order.  A sweep whose stride reaches past a block of
    ``block_rows`` rows is a row sweep (one stride); every other sweep
    holds a whole block in registers.
    """
    B = block_keys(L, block_rows)
    plan = [tuple((1 << e, j) for e in range(1, B.bit_length())
                  for j in _halving((1 << e) // 2))]
    k = 2 * B
    while k <= L:
        plan += _stage_plan(k, B)
        k *= 2
    return plan


def _exchange(v, lower, keep_min, j: int):
    """Compare-exchange of every register of block ``v`` (n, 8, 128) with
    partner ``i ^ j`` (j < TILE): ``lower`` marks ``i & j == 0`` in a tile;
    the min is kept where ``keep_min``."""
    if j < LANES:
        axis, s, n = 2, j, LANES
    else:
        axis, s, n = 1, j // LANES, SUBLANES
    partner = jnp.where(lower, pltpu.roll(v, n - s, axis),   # v[i + j]
                        pltpu.roll(v, s, axis))              # v[i - j]
    return jnp.where(keep_min, jnp.minimum(v, partner),
                     jnp.maximum(v, partner))


def _pairs(v, d: int):
    """Registers ``t`` and ``t + d`` of block ``v`` (n, 8, 128), t & d == 0."""
    u = v.reshape(v.shape[0] // (2 * d), 2, d, SUBLANES, LANES)
    return u[:, 0], u[:, 1]


def _unpairs(a, b):
    """Inverse of `_pairs`."""
    return jnp.stack([a, b], axis=1).reshape(-1, SUBLANES, LANES)


def _complement_descending(v, k: int, base, B: int, L: int):
    """Complement (``~x`` reverses the int32 order) every key of block ``v``
    that stage ``k`` sorts descending, where that is whole registers: so
    the stage runs ascending on them.  An involution."""
    if k < TILE or k >= L:
        return v
    if k >= B:                           # one direction for the block
        return v ^ jnp.where((base & k) == 0, 0, -1).astype(jnp.int32)
    asc, desc = _pairs(v, k // TILE)
    return _unpairs(asc, ~desc)


def _block_pass(w, substages: Sweep, prologue=None):
    """One block sweep: every block of ``w`` is loaded into registers once,
    goes through ``substages`` (all strides inside a block) and is stored."""
    rows = w.shape[0]
    L = rows * LANES
    br = min(rows, BLOCK_ROWS)
    B = br * LANES
    tile = (jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1))

    def body(b, carry):
        r0 = pl.multiple_of(b * br, br)
        v = w[pl.ds(r0, br), :].reshape(br // SUBLANES, SUBLANES, LANES)
        base = r0 * LANES                # index of the block's first key
        if prologue is not None:
            reg = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = prologue(v, base + reg * TILE + tile)
        for n, (k, j) in enumerate(substages):
            if n == 0 or substages[n - 1][0] != k:
                v = _complement_descending(v, k, base, B, L)
            if j >= TILE:                # partner register t ^ (j / TILE)
                a, c = _pairs(v, j // TILE)
                v = _unpairs(jnp.minimum(a, c), jnp.maximum(a, c))
            else:
                lower = (tile & j) == 0
                keep = lower == ((tile & k) == 0) if k < TILE else lower
                v = _exchange(v, lower, keep, j)
            if n + 1 == len(substages) or substages[n + 1][0] != k:
                v = _complement_descending(v, k, base, B, L)
        w[pl.ds(r0, br), :] = v.reshape(br, LANES)
        return carry

    jax.lax.fori_loop(0, rows // br, body, 0)


def _row_pass(w, k: int, j: int):
    """Compare-exchange with partner ``i ^ j`` for a stride past a block:
    each iteration pairs two ``BLOCK_ROWS``-row halves."""
    rows = w.shape[0]
    s = j // LANES                       # partner row distance
    br = BLOCK_ROWS                      # s >= br: j reaches past a block
    per = (s // br).bit_length() - 1     # log2(blocks per half-group)

    def body(q, carry):
        lo = pl.multiple_of(((q >> per) * 2 * s) + (q & ((1 << per) - 1)) * br,
                            br)
        a = w[pl.ds(lo, br), :]
        b = w[pl.ds(lo + s, br), :]
        mn, mx = jnp.minimum(a, b), jnp.maximum(a, b)
        if k < rows * LANES:
            asc = ((lo * LANES) & k) == 0    # one direction per 2j-group
            mn, mx = jnp.where(asc, mn, mx), jnp.where(asc, mx, mn)
        w[pl.ds(lo, br), :] = mn
        w[pl.ds(lo + s, br), :] = mx
        return carry

    jax.lax.fori_loop(0, rows // (2 * br), body, 0)


def _run(w, plan: List[Sweep], prologue=None):
    B = block_keys(w.shape[0] * LANES, BLOCK_ROWS)
    for n, sweep in enumerate(plan):
        if sweep[0][1] >= B:
            (k, j), = sweep
            _row_pass(w, k, j)
        else:
            _block_pass(w, sweep, prologue if n == 0 else None)


def merge_stage(w, k: int):
    """Stage ``k`` of the network: turns bitonic runs of k into sorted runs.

    With ``k == len(w)`` this sorts any bitonic sequence ascending (every
    ``idx & k`` is 0), which is all a merge-split needs.
    """
    L = w.shape[0] * LANES
    _run(w, _stage_plan(k, block_keys(L, BLOCK_ROWS)))


def sort_network(w, prologue=None):
    """Sort the keys of VMEM ref ``w`` (R, 128) ascending, in place.

    ``prologue(v, idx)`` is applied to every register as the first sweep
    loads it (the local sort masks its sentinel tail there).
    """
    _run(w, sweep_plan(w.shape[0] * LANES, BLOCK_ROWS), prologue)
