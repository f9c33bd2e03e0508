"""Bitonic sorting network over a VMEM-resident block of int32 keys.

The network runs in place on a VMEM ref of shape ``(R, 128)``: key ``i``
sits at row ``i // 128``, lane ``i % 128``, so every (8, 128) tile is one
vector register and ``R * 128`` is a power of two of at least one tile.
Mosaic has no gather and no ``rev``, so each compare-exchange substage
(partner ``i XOR j``) is built from what the chip does natively:

  * ``j < 1024`` (inside one tile): two ``pltpu.roll`` rotations (lanes for
    ``j < 128``, sublanes otherwise) bring ``v[i + j]`` and ``v[i - j]``
    into place, and a select on ``i & j`` picks the partner;
  * ``j >= 1024`` (whole tiles): the two partner row blocks are loaded
    separately, min/max'ed and stored back — no data movement at all.

Substages inside a tile are fused: a pass loads a register block once and
applies every in-tile substage of a stage before storing it, so a stage
costs one VMEM pass per cross-tile stride plus one.

Keys are int32.  `to_keys` / `from_keys` map float32 onto int32 keys whose
signed order is the float total order (``-0.0`` before ``+0.0``), so the
network is an exact permutation of the input bits; min/max on the floats
themselves would not be.  The sentinel `KEY_MAX` sorts after every key.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
TILE = SUBLANES * LANES          # keys in one (8, 128) vector register
BLOCK_ROWS = 32                  # rows a pass keeps in registers (4 tiles)
KEY_MAX = int(jnp.iinfo(jnp.int32).max)
KEY_MIN = int(jnp.iinfo(jnp.int32).min)
KEY_DTYPES = (jnp.dtype(jnp.int32), jnp.dtype(jnp.float32))


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


def _exchange(v, idx, j: int, k: int):
    """Compare-exchange of a register block with partner ``idx ^ j`` (j < TILE).

    ``idx`` holds each element's key index; stage ``k`` sorts ascending
    where ``idx & k == 0`` and descending elsewhere.
    """
    if j < LANES:
        axis, s, n = 1, j, LANES
    else:
        axis, s, n = 0, j // LANES, v.shape[0]
    lower = (idx & j) == 0
    partner = jnp.where(lower, pltpu.roll(v, n - s, axis),   # v[i + j]
                        pltpu.roll(v, s, axis))              # v[i - j]
    asc = (idx & k) == 0
    return jnp.where(lower == asc, jnp.minimum(v, partner),
                     jnp.maximum(v, partner))


def _tile_pass(w, substages, prologue=None):
    """One VMEM pass applying in-tile substages ``[(k, j), ...]`` in order."""
    rows = w.shape[0]
    tb = min(rows, BLOCK_ROWS)
    local = (jax.lax.broadcasted_iota(jnp.int32, (tb, LANES), 0) * LANES
             + jax.lax.broadcasted_iota(jnp.int32, (tb, LANES), 1))

    def body(b, carry):
        r0 = pl.multiple_of(b * tb, tb)
        v = w[pl.ds(r0, tb), :]
        idx = r0 * LANES + local
        if prologue is not None:
            v = prologue(v, idx)
        for k, j in substages:
            v = _exchange(v, idx, j, k)
        w[pl.ds(r0, tb), :] = v
        return carry

    jax.lax.fori_loop(0, rows // tb, body, 0)


def _row_pass(w, k: int, j: int):
    """Compare-exchange with partner ``i ^ j`` for a stride of whole tiles."""
    rows = w.shape[0]
    s = j // LANES                       # partner row distance, >= SUBLANES
    tb = min(s, BLOCK_ROWS)
    per = (s // tb).bit_length() - 1     # log2(blocks per half-group)

    def body(q, carry):
        lo = pl.multiple_of(((q >> per) * 2 * s) + (q & ((1 << per) - 1)) * tb,
                            tb)
        a = w[pl.ds(lo, tb), :]
        b = w[pl.ds(lo + s, tb), :]
        asc = ((lo * LANES) & k) == 0    # one direction per 2j-group
        mn, mx = jnp.minimum(a, b), jnp.maximum(a, b)
        w[pl.ds(lo, tb), :] = jnp.where(asc, mn, mx)
        w[pl.ds(lo + s, tb), :] = jnp.where(asc, mx, mn)
        return carry

    jax.lax.fori_loop(0, rows // (2 * tb), body, 0)


def merge_stage(w, k: int):
    """Stage ``k`` of the network: turns bitonic runs of k into sorted runs.

    With ``k == len(w)`` this sorts any bitonic sequence ascending (every
    ``idx & k`` is 0), which is all a merge-split needs.
    """
    for j in _halving(k // 2):
        if j < TILE:
            break
        _row_pass(w, k, j)
    _tile_pass(w, [(k, j) for j in _halving(min(k, TILE) // 2)])


def sort_network(w, prologue=None):
    """Sort the keys of VMEM ref ``w`` (R, 128) ascending, in place.

    ``prologue(v, idx)`` is applied to every register block as the first
    pass loads it (the local sort masks its sentinel tail there).
    """
    L = w.shape[0] * LANES
    first = [(k, j) for k in (1 << e for e in range(1, TILE.bit_length()))
             for j in _halving(k // 2)]
    _tile_pass(w, first, prologue)
    k = 2 * TILE
    while k <= L:
        merge_stage(w, k)
        k *= 2
