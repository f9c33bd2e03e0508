"""Explicit `shard_map` execution engine for the distributed merge sort.

The constraint backend (`core/sort.py`, backend="constraint") only *hints*
layouts with `with_sharding_constraint` and leaves collective choice to the
XLA SPMD partitioner — exactly the "leave it to the scheduler" baseline the
paper argues against.  This engine instead implements Algorithms 1-3
literally, per device:

  1. chunk ownership comes from `chunk_bounds` (paper step 1/2) — after BIG
     padding every device owns one equal, contiguous logical chunk;
  2. the worker->core map is the mesh order, fixed at trace time (step 3 —
     the engine *is* the static mapping; `policy.static_mapping` has no
     runtime-chosen analogue here and is ignored);
  3. the per-device local sort runs the Pallas `local_sort` kernel inside
     each shard — the VMEM-resident `input_cpy` of Algorithm 2;
  4. the log2(m)-level merge tree exchanges runs with *explicit* collectives
     chosen by `LocalisationPolicy`:

       localised      — one-shot relayout into the locally-homed chunk
                        layout (`lax.all_to_all` when the input is
                        hash-interleaved, free when chunk-contiguous), then a
                        block-wise bitonic merge-split network: log2(m)
                        stages, stage i making i+1 pairwise chunk exchanges
                        with device d XOR 2^j via `lax.ppermute` —
                        neighbour-only traffic, O(n/m) memory per device,
                        data never re-homed.
       non-localised  — intermediate runs stay pinned to the *input* homing
                        between levels, so every level re-reads the whole
                        array remotely (`lax.all_gather`, the full exchange
                        the paper charges to hash-for-home), merges, and
                        scatters its own home shard back.  Under
                        hash-interleaving every element of a worker's run
                        lives on another device — the per-level all-to-all
                        of Table 1 cases 1/3.

Two distance classes (the NUCA gradient of a multi-pod deployment — fast
ICI within a pod, slow DCN across pods) enter through `axis`: a *tuple* of
mesh axes, outer (pod) axes first, linearised row-major so device
d = pod * n_inner + inner owns logical chunk d.  Merge-split strides that
stay below the inner-axis size toggle only the inner index — those
exchanges run as intra-pod `ppermute`s on the fast axis.  Strides at or
above it toggle only pod bits; how they cross the slow link is the
policy's `outer` knob:

  outer=None          — flat: cross-pod substages are the same pairwise
                        chunk `ppermute`s, just routed over the pod axis
                        (stride-many DCN round trips per top stage).
  outer="hash"/
  "replicate"         — hierarchical: each top stage's cross-pod substages
                        collapse into ONE `all_gather` over the pod axes
                        (the n_pods chunks at my inner index), and every
                        pod replays the stage's cross-pod merge-splits
                        locally on the gathered copies — one DCN collective
                        per top level, merge work replicated, ownership
                        never migrating across pods.  Only the top
                        log2(n_pods) levels touch DCN at all.

The engine returns the same logical sorted array as `jnp.sort`, placed
chunk-contiguous when localised and in the input homing otherwise.

The *local* half of each device's work — the leaf sorts, the local merge
tree and the merge-split of every network substage — has two
implementations, selected by ``local_phase``:

  "pallas"     — the VMEM-resident production path: `kernels.local_sort`
                 fuses the leaf sorts and the whole local merge tree into
                 ONE pallas_call (chunk read from HBM once, written once),
                 and `kernels.merge_split` computes only the *kept* half of
                 every compare-exchange (a bitonic half-cleaner: C outputs
                 from 2C inputs, never materialising the discarded half).
  "reference"  — the jnp oracle: per-leaf Pallas sort, then a Python loop
                 of HBM-materialising vmapped rank merges, and
                 merge-everything-discard-half at every network substage.

A "pallas" chunk larger than one `local_sort` holds in VMEM (`run_len`)
streams past VMEM instead: ONE `local_sort` call sorts it as VMEM-sized
runs, one grid row each, and the block bitonic network of the exchange —
with the runs standing in for the devices — merges them in HBM, one
batched `kernels.merge_split.run_merge` call per substage (`_sort_runs`).
The path is chosen by the chunk's size alone, on one device only.

``local_phase=None`` auto-selects: "pallas" for the default
``local_sort="bitonic"``, "reference" when a callable leaf sort is given
(a callable can't be fused into the kernel).  The non-localised path's
merge levels are interleaved with all_gathers, so only its leaf sort is a
kernel; its merge tree is always the reference form.
"""
from __future__ import annotations

import functools
import itertools
import math
from dataclasses import dataclass
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh
from jax.sharding import PartitionSpec as P

from repro.core.homing import Axis, Homing, axis_tuple
from repro.core.localisation import LocalisationPolicy, chunk_bounds
from repro.core.sort import (check_pad_outside_trace, merge_sorted,
                             pad_to_multiple, sort_entry, sort_prepare,
                             sort_unpad)
from repro.kernels.bitonic_sort import KEY_MAX, from_keys, to_keys
from repro.kernels.local_sort import local_sort as _local_sort_kernel
from repro.kernels.local_sort import max_chunk
from repro.kernels.merge_split import max_run
from repro.kernels.merge_split import merge_split as _merge_split_kernel
from repro.kernels.merge_split import run_merge as _run_merge_kernel
from repro.obs.tracelog import get_tracer

#: engine.sort span ids — groups a span's engine.exchange_level events
_SORT_CALLS = itertools.count(1)

AXIS = "data"

_merge_rows = jax.vmap(merge_sorted)

LocalSort = Union[str, Callable]

LOCAL_PHASES = ("pallas", "reference")


def resolve_local_phase(local_phase: Optional[str],
                        local_sort: LocalSort) -> str:
    """The ``local_phase`` contract, shared by the engine and the schedule.

    None auto-selects: "pallas" (the fused-kernel production path) when the
    leaf sort is the default "bitonic", "reference" when a callable leaf
    sort was supplied — an arbitrary callable cannot run inside the fused
    kernel, so it implies the jnp oracle path.
    """
    if local_phase is None:
        return "pallas" if local_sort == "bitonic" else "reference"
    if local_phase not in LOCAL_PHASES:
        raise ValueError(f"unknown local_phase {local_phase!r}; "
                         f"want one of {LOCAL_PHASES} (or None = auto)")
    if local_phase == "pallas" and callable(local_sort):
        raise ValueError(
            "local_phase='pallas' runs the whole local phase inside the "
            "fused Pallas kernels; a callable local_sort only applies to "
            "local_phase='reference'")
    return local_phase


def _axes_sizes(mesh: Mesh, axes: Tuple[str, ...]) -> Tuple[int, ...]:
    sizes = tuple(mesh.shape[a] for a in axes)
    for a, s in zip(axes, sizes):
        assert (s & (s - 1)) == 0, f"axis {a!r} size {s} not a power of 2"
    return sizes


def _axis_name(axes: Tuple[str, ...]):
    """The collective axis-name argument: bare name or tuple (linearised)."""
    return axes[0] if len(axes) == 1 else axes


def engine_granule(m: int, num_workers: Optional[int],
                   hash_homed: bool) -> int:
    """The engine's padding granule: the chunk must split into per-device
    leaves, and (when relaying out of the interleaved homing) into one
    all-to-all block per peer device.  The one definition shared by
    `shard_map_sort` (in-trace no-op re-pad), `make_engine_fn` (the eager
    pad that must match it) and `exchange_schedule` (the byte model)."""
    w = num_workers or m
    assert w % m == 0 and (w & (w - 1)) == 0, (w, m)
    return m * math.lcm(w // m, m if hash_homed else 1)


def _stride_axis(axes: Tuple[str, ...], sizes: Tuple[int, ...],
                 j: int) -> Tuple[str, int]:
    """Which mesh axis bit j of the linearised device index lives on.

    Row-major linearisation with power-of-two sizes means stride 2^j over
    the combined index toggles exactly one bit of exactly one axis's local
    index: returns (axis_name, local_stride).
    """
    bit = j
    for a, s in zip(reversed(axes), reversed(sizes)):
        la = s.bit_length() - 1
        if bit < la:
            return a, 1 << bit
        bit -= la
    raise ValueError(f"stride 2^{j} exceeds the {math.prod(sizes)}-device space")


def _leaf_sort(rows, local_sort: LocalSort):
    """Sort each leaf row. rows: (k, leaf) -> (k, leaf) row-sorted.

    local_sort="bitonic" runs one kernel grid step per leaf, entirely in
    VMEM; non-power-of-two leaves are sentinel-padded *inside* the kernel's
    VMEM scratch (`kernels.local_sort`), so no padded copy ever touches HBM
    — the old path concatenated up to 2x sentinel tail per call.  A callable
    is applied as `local_sort(rows, axis=-1)`.
    """
    if callable(local_sort):
        return local_sort(rows, axis=-1)
    if local_sort != "bitonic":
        raise ValueError(f"unknown local_sort {local_sort!r}")
    return _local_sort_kernel(rows)


def _merge_split(run, other, chunk: int, keep_low):
    """One compare-exchange of the block bitonic network: merge, keep half.

    The reference form: merges the full 2*chunk run and discards half — 2x
    the merge compute and HBM traffic of the kept result.  The "pallas"
    local phase replaces it with `kernels.merge_split`, which computes only
    the kept half (the same values; equal keys in total order).
    """
    both = merge_sorted(run, other)                  # (2*chunk,)
    return jnp.where(keep_low, both[:chunk], both[chunk:])


# ---------------------------------------------------------------------------
# the exchange network, as data
# ---------------------------------------------------------------------------
#
# The merge-split network's structure — which device exchanges with which,
# over which mesh axis, keeping which half — used to live only inside the
# traced `_localised_shard` loop, where nothing could inspect it.  It is now
# built once as a plain descriptor (`exchange_network`) that BOTH the runtime
# (the shard_map body below iterates it) and the static analyzer
# (`repro.analysis.netverify`, rule R6) consume, so "the schedule the engine
# runs" and "the schedule the analyzer certifies" cannot drift apart.

@dataclass(frozen=True)
class NetExchange:
    """One pairwise compare-exchange substage: a ppermute + merge-split.

    `partner`/`keep_low` are the device-space view over all m linearised
    devices (partner[d] = d XOR 2^substage; keep_low[d] = low-half iff the
    bitonic direction bit says so); `axis`/`axis_stride`/`perm` are the
    on-axis routing the runtime hands to `lax.ppermute`.
    """
    stage: int                      # merge stage i (sorts runs of 2^(i+1))
    substage: int                   # j: global device-index bit toggled
    axis: str                       # mesh axis the ppermute runs over
    axis_stride: int                # stride on that axis's local index
    stride: int                     # global linearised stride == 2^substage
    perm: Tuple[Tuple[int, int], ...]   # on-axis (src, dst) pairs
    partner: Tuple[int, ...]        # device-space partner map (involution)
    keep_low: Tuple[bool, ...]      # device-space keep flag


@dataclass(frozen=True)
class NetReplay:
    """One cross-pod substage replayed locally per pod (hierarchical path).

    `pod_partner`/`pod_keep_low` index pod space (what the replay loop
    actually uses on the gathered rows); `partner`/`keep_low` are the
    equivalent device-space view — identical formulas to `NetExchange`,
    because toggling pod bit (substage - log_inner) of q toggles exactly
    bit `substage` of d = q * m_inner + inner.
    """
    stage: int
    substage: int
    stride: int                     # global stride == 2^substage >= m_inner
    pod_partner: Tuple[int, ...]
    pod_keep_low: Tuple[bool, ...]
    partner: Tuple[int, ...]
    keep_low: Tuple[bool, ...]


@dataclass(frozen=True)
class NetGatherReplay:
    """One hierarchical top stage: ONE all_gather over the pod axes, then
    the stage's cross-pod substages replayed per pod on the gathered rows,
    each device finally keeping its own pod's chunk."""
    stage: int
    axes: Union[str, Tuple[str, ...]]   # outer (pod) axes gathered over
    replays: Tuple[NetReplay, ...]


@dataclass(frozen=True)
class ExchangeNetwork:
    """The localised engine's full exchange plan for one (policy, mesh).

    `levels` holds `NetExchange` / `NetGatherReplay` entries in execution
    order; `substages()` flattens to the device-space compare-exchange
    sequence (the thing the 0-1 principle certifies).  `relayout` records
    whether the plan starts with the hash-homing all_to_all.
    """
    axes: Tuple[str, ...]
    sizes: Tuple[int, ...]
    m: int
    hier: bool
    relayout: bool
    levels: Tuple[Union[NetExchange, NetGatherReplay], ...]

    def substages(self):
        """Device-space compare-exchanges (NetExchange | NetReplay), in order."""
        for lv in self.levels:
            if isinstance(lv, NetGatherReplay):
                for rp in lv.replays:
                    yield rp
            else:
                yield lv


def _keep_low(m: int, i: int, j: int) -> np.ndarray:
    """Bitonic keep flags over device space: device d keeps the low half of
    the merged pair iff its low/high role (bit j) matches the run's
    direction (bit i+1)."""
    d = np.arange(m)
    ascending = ((d >> (i + 1)) & 1) == 0
    is_low = ((d >> j) & 1) == 0
    return is_low == ascending


def exchange_network(policy: LocalisationPolicy, sizes: Sequence[int],
                     axes: Optional[Sequence[str]] = None) -> ExchangeNetwork:
    """The merge-split network descriptor for one (policy, mesh-slice).

    `sizes` are the sort-axis sizes in axis order, inner (ICI) last —
    the same contract as `exchange_schedule`; `axes` the matching mesh axis
    names (synthesised as ax0.. when only the shape matters, e.g. for
    certification).  Raises ValueError for non-localised policies (their
    all_gather levels have no merge-split network to describe) and for a
    hierarchical policy on a single-axis shape — identical validation to
    `shard_map_sort`, so a descriptor exists exactly when the engine would
    run the network.
    """
    sizes = tuple(int(s) for s in sizes)
    if axes is None:
        axes = tuple(f"ax{k}" for k in range(len(sizes)))
    axes = tuple(axes)
    if len(axes) != len(sizes):
        raise ValueError(f"axes {axes!r} do not match sizes {sizes!r}")
    for a, s in zip(axes, sizes):
        if s < 1 or (s & (s - 1)) != 0:
            raise ValueError(f"axis {a!r} size {s} not a power of 2")
    if not policy.localised:
        raise ValueError(
            f"policy {policy.name!r} is non-localised: every level is an "
            f"all_gather full exchange — there is no merge-split network")
    hier = policy.outer is not None
    if hier and len(sizes) < 2:
        raise ValueError(
            f"hierarchical policy {policy.name!r} needs (pod, ..., inner) "
            f"axis sizes, got {sizes!r} — same contract as shard_map_sort")
    m = math.prod(sizes)
    m_inner = sizes[-1]
    log_inner = m_inner.bit_length() - 1
    n_pods = m // m_inner
    d = np.arange(m)
    levels: List[Union[NetExchange, NetGatherReplay]] = []
    for i in range(m.bit_length() - 1):
        j0 = i
        if hier and i >= log_inner:
            q = np.arange(n_pods)
            replays = []
            for j in range(i, log_inner - 1, -1):
                t = 1 << (j - log_inner)            # pod-index stride
                pod_keep = ((((q >> (j - log_inner)) & 1) == 0)
                            == (((q >> (i + 1 - log_inner)) & 1) == 0))
                replays.append(NetReplay(
                    stage=i, substage=j, stride=1 << j,
                    pod_partner=tuple(int(p) for p in q ^ t),
                    pod_keep_low=tuple(bool(b) for b in pod_keep),
                    partner=tuple(int(p) for p in d ^ (1 << j)),
                    keep_low=tuple(bool(b) for b in _keep_low(m, i, j))))
            levels.append(NetGatherReplay(
                stage=i, axes=_axis_name(axes[:-1]), replays=tuple(replays)))
            j0 = log_inner - 1
        for j in range(j0, -1, -1):
            ax, t = _stride_axis(axes, sizes, j)
            na = sizes[axes.index(ax)]
            levels.append(NetExchange(
                stage=i, substage=j, axis=ax, axis_stride=t, stride=1 << j,
                perm=tuple((a, a ^ t) for a in range(na)),
                partner=tuple(int(p) for p in d ^ (1 << j)),
                keep_low=tuple(bool(b) for b in _keep_low(m, i, j))))
    return ExchangeNetwork(
        axes=axes, sizes=sizes, m=m, hier=hier,
        relayout=policy.homing == Homing.HASH_INTERLEAVED,
        levels=tuple(levels))


def run_len(chunk: int) -> int:
    """Keys in each run of the "pallas" local phase for a chunk of 32-bit
    keys: the whole chunk where one `local_sort` holds it in VMEM, else the
    longest run of which `run_merge` holds two."""
    return chunk if chunk <= max_chunk() else max_run()


def run_network(runs: int) -> ExchangeNetwork:
    """The block bitonic network that merges ``runs`` sorted runs of one
    chunk: the exchange network over a power of two of runs, each run in a
    device's place (sentinel runs fill the power of two)."""
    p = 1 << max(0, (runs - 1).bit_length())
    return exchange_network(LocalisationPolicy(), (p,))


def _sort_runs(x):
    """Sort a chunk past one `local_sort`'s VMEM: runs of `run_len` keys (the
    last one padded with sentinels), formed by ONE `local_sort` call, then
    merged in HBM by `run_network`, one `run_merge` call per substage."""
    chunk = x.shape[0]
    run = run_len(chunk)
    runs = -(-chunk // run)
    net = run_network(runs)
    tr = get_tracer()
    tr.count("sort.runs", runs, cat="engine", run=run)
    tr.count("sort.run_merge_substages", len(net.levels), cat="engine",
             runs=net.m)
    keys = jnp.pad(to_keys(x), (0, runs * run - chunk),
                   constant_values=KEY_MAX)
    keys = _local_sort_kernel(keys.reshape(runs, run))
    keys = jnp.pad(keys, ((0, net.m - runs), (0, 0)),
                   constant_values=KEY_MAX)
    for ex in net.levels:
        keys = _run_merge_kernel(keys, np.asarray(ex.partner),
                                 np.asarray(ex.keep_low))
    return from_keys(keys.reshape(-1)[:chunk], x.dtype)


def _localised_shard(xloc, *, m: int, chunk: int, w_per_dev: int,
                     hash_homed: bool, local_sort: LocalSort,
                     axes: Tuple[str, ...], sizes: Tuple[int, ...],
                     net: "ExchangeNetwork", local_phase: str):
    """Per-device body, localised: one-shot relayout + merge-split tree."""
    name = _axis_name(axes)
    if hash_homed:
        # Algorithm 2's memcpy: one explicit all-to-all turns my interleaved
        # column into my contiguous chunk (order scrambled; the sort fixes it).
        blocks = xloc.reshape(m, chunk // m)     # block j goes to device j
        mine = jax.lax.all_to_all(blocks, name, 0, 0).reshape(-1)
    else:
        mine = xloc                       # already the locally-homed chunk
    if local_phase == "pallas" and run_len(chunk) < chunk:
        run = _sort_runs(mine)            # one device only (`_check_runs`)
    elif local_phase == "pallas":
        # Algorithm 2 for the whole local phase: ONE pallas_call copies my
        # chunk into VMEM, runs the leaf stages AND the full local merge
        # tree on-chip, and writes the sorted run back once.
        run = _local_sort_kernel(mine.reshape(1, chunk))[0]
    else:
        runs = _leaf_sort(mine.reshape(w_per_dev, chunk // w_per_dev),
                          local_sort)
        while runs.shape[0] > 1:          # merge my own leaves, no traffic
            runs = _merge_rows(runs[0::2], runs[1::2])
        run = runs[0]
    # block-wise bitonic merge-split network over the hypercube: stage i
    # sorts runs of 2^(i+1) blocks; each substage swaps the full chunk with
    # device d XOR 2^j, merges, and keeps the low or high half.  Per-device
    # memory stays at chunk size — no device ever materialises more than a
    # pod's worth of chunks — and the sorted array ends naturally distributed
    # in ownership order (compare-exchange -> merge-split block sorting is
    # exact by the 0-1 principle, given sorted blocks).  The structure —
    # who exchanges with whom, keeping which half — comes from the
    # `exchange_network` descriptor, the same object `repro.analysis`'s
    # rule R6 certifies; the loop below only routes it.
    d = jax.lax.axis_index(name)          # linearised (pod-major) device id
    m_inner = sizes[-1]
    log_inner = m_inner.bit_length() - 1
    for lv in net.levels:
        if isinstance(lv, NetGatherReplay):
            # hierarchical top level: ONE all_gather over the pod axes pulls
            # the n_pods chunks at my inner index; this stage's cross-pod
            # substages (they toggle only pod bits, so everything they read
            # sits in the gathered set) are replayed locally for every pod,
            # then I keep my own pod's chunk.  One DCN collective replaces
            # (stage - log_inner + 1) pairwise DCN hops.
            pods = jax.lax.all_gather(run, lv.axes, axis=0)  # (n_pods, chunk)
            for rp in lv.replays:
                partner = pods[np.asarray(rp.pod_partner)]
                keep_low = jnp.asarray(np.asarray(rp.pod_keep_low))
                if local_phase == "pallas":
                    # batched merge-split replay: row q keeps only its half
                    pods = _merge_split_kernel(pods, partner, keep_low)
                else:
                    merged = _merge_rows(pods, partner)  # (n_pods, 2*chunk)
                    pods = jnp.where(keep_low[:, None], merged[:, :chunk],
                                     merged[:, chunk:])
            run = jnp.take(pods, d >> log_inner, axis=0)
        else:
            other = jax.lax.ppermute(run, lv.axis, list(lv.perm))
            keep_low = jnp.asarray(np.asarray(lv.keep_low))[d]
            if local_phase == "pallas":
                run = _merge_split_kernel(run[None], other[None],
                                          keep_low)[0]
            else:
                run = _merge_split(run, other, chunk, keep_low)
    return run


def _unlocalised_shard(xloc, *, m: int, chunk: int, w: int,
                       hash_homed: bool, local_sort: LocalSort,
                       axes: Tuple[str, ...]):
    """Per-device body, non-localised: runs stay home-pinned between levels.

    Every level gathers the whole array (each worker's reads are remote —
    under hash homing literally every element comes from another device),
    does the level's merges, and writes back only its own home shard.  The
    merge work is replicated across devices: without ownership there is no
    cheap way to partition it, which is the paper's point.  On a pod mesh
    every one of these gathers is a full cross-pod exchange — the DCN bill
    the hierarchical policy exists to avoid.
    """
    name = _axis_name(axes)
    d = jax.lax.axis_index(name)

    if hash_homed:
        def gather(col):                          # (chunk, 1) -> (n_p,)
            full = jax.lax.all_gather(col, name, axis=1, tiled=True)
            return full.reshape(-1)

        def scatter(full):                        # (n_p,) -> (chunk, 1)
            return jax.lax.dynamic_slice(
                full.reshape(chunk, m), (0, d), (chunk, 1))
    else:
        def gather(blk):                          # (chunk,) -> (n_p,)
            return jax.lax.all_gather(blk, name, axis=0, tiled=True)

        def scatter(full):                        # (n_p,) -> (chunk,)
            return jax.lax.dynamic_slice(full, (d * chunk,), (chunk,))

    n_p = chunk * m
    full = gather(xloc)                           # leaves: remote read
    runs = _leaf_sort(full.reshape(w, n_p // w), local_sort)
    xloc = scatter(runs.reshape(-1))
    for _ in range(w.bit_length() - 1):
        full = gather(xloc)                       # per-level full exchange
        runs = full.reshape(runs.shape[0], -1)
        runs = _merge_rows(runs[0::2], runs[1::2])
        xloc = scatter(runs.reshape(-1))
    return xloc


def _check_runs(chunk: int, m: int, policy: LocalisationPolicy,
                local_phase: str) -> None:
    """The run path past VMEM sorts one device's chunk; across devices the
    merge-split network would hold two such chunks in VMEM."""
    if (m > 1 and policy.localised and local_phase == "pallas"
            and run_len(chunk) < chunk):
        raise ValueError(
            f"a {chunk}-key chunk per device is past one local sort's VMEM "
            f"({max_chunk()} keys): the pallas local phase streams past VMEM "
            f"on one device only, and this mesh has {m}; use more devices "
            f"or local_phase='reference'")


def shard_map_sort(x, mesh: Mesh,
                   policy: LocalisationPolicy = LocalisationPolicy(),
                   num_workers: Optional[int] = None,
                   local_sort: LocalSort = "bitonic", axis: Axis = AXIS,
                   local_phase: Optional[str] = None):
    """Sort a 1-D array with the explicit shard_map engine (traceable).

    ``local_phase`` selects the per-device compute implementation (see the
    module docstring): "pallas" = fused VMEM-resident kernels, "reference" =
    the jnp oracle path, None = auto by ``local_sort``.
    """
    local_phase = resolve_local_phase(local_phase, local_sort)
    axes = axis_tuple(axis)
    sizes = _axes_sizes(mesh, axes)
    n = x.shape[0]
    m = math.prod(sizes)
    w = num_workers or m
    w_per_dev = w // m
    hash_homed = policy.homing == Homing.HASH_INTERLEAVED
    hier = policy.outer is not None
    if hier and len(axes) < 2:
        raise ValueError(
            f"hierarchical policy {policy.name!r} needs a (pod, ..., inner) "
            f"axis tuple, got {axis!r} — use a flat policy on one axis")

    granule = engine_granule(m, num_workers, hash_homed)
    check_pad_outside_trace(n, granule, mesh, axes, "shard_map_sort")
    x = pad_to_multiple(x, granule)
    n_p = x.shape[0]
    bounds = chunk_bounds(n_p, m)                  # ownership, paper step 1
    chunk = bounds[0][1] - bounds[0][0]
    assert all(hi - lo == chunk for lo, hi in bounds)

    _check_runs(chunk, m, policy, local_phase)

    spec_axis = axes[0] if len(axes) == 1 else axes   # P entry: name | tuple
    if hash_homed:
        # logical element i*m + d sits in row i of device d's column
        xin = x.reshape(chunk, m)
        in_spec = P(None, spec_axis)
    else:
        xin = x
        in_spec = P(spec_axis)

    if policy.localised:
        body = partial(_localised_shard, m=m, chunk=chunk,
                       w_per_dev=w_per_dev, hash_homed=hash_homed,
                       local_sort=local_sort, axes=axes, sizes=sizes,
                       net=exchange_network(policy, sizes, axes),
                       local_phase=local_phase)
        out_spec = P(spec_axis)                    # chunk-contiguous output
    else:
        body = partial(_unlocalised_shard, m=m, chunk=chunk, w=w,
                       hash_homed=hash_homed, local_sort=local_sort,
                       axes=axes)
        out_spec = in_spec                         # output stays home-pinned

    y = jax.shard_map(body, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
                      check_vma=False)(xin)
    if y.ndim == 2:                                # interleaved view -> logical
        y = y.reshape(-1)
    return y[:n]


def exchange_schedule(n: int, sizes: Sequence[int],
                      policy: LocalisationPolicy,
                      num_workers: Optional[int] = None,
                      itemsize: int = 4,
                      local_phase: Optional[str] = None) -> List[Dict]:
    """The engine's full execution plan as per-level byte counts (Fig 9).

    `sizes` are the sort-axis sizes in axis order, inner (ICI) last — e.g.
    (2, 4) for a ("pod", "data") mesh slice.  Returns one record per
    collective *and* per local compute step, in execution order.  Every
    record carries ``level`` (0 = relayout/leaves, k = merge level k),
    ``op``, ``inter_pod_bytes`` / ``intra_pod_bytes`` (collective traffic,
    0 for local ops), ``local_hbm_bytes`` (HBM read+write traffic of the
    local compute, 0 for collectives) and ``local_merge_elems`` (merge
    output elements materialised — the "compute only what you keep" count).
    All totals are summed across devices; bytes are hardware-independent
    facts of the schedule, the measurable form of both halves of the
    paper's argument (exchange locality AND cache-resident local phase).

    ``local_phase`` prices the local records ("pallas" = fused one-pass
    kernels + kept-half merge-splits, "reference" = HBM-materialising tree
    + merge-everything-discard-half; None = "pallas", the engine default).
    The collective records are identical under both phases.  Local cost
    model, per device and per step (B = chunk bytes, C = chunk elems,
    T = log2(w_per_dev) local tree levels):

      local_sort   pallas:    2B traffic (one VMEM round trip), C elems
                   reference: 2B*(1+T) traffic (leaves + every tree level
                              re-materialised), C*(1+T) elems
      merge_split  pallas:    3B traffic (read both runs, write kept half),
                              C elems
                   reference: 7B traffic (read 2B, write the 2C merge,
                              re-read it, write the kept half), 2C elems

    A "pallas" chunk past one local sort's VMEM (`run_len`) is priced as
    what `_sort_runs` runs, all at level 0: one ``local_sort`` record for
    run formation (2 bytes of traffic per key of the R runs, the last one
    sentinel-padded), then one ``run_merge`` record per substage of
    `run_network` (every one of its P runs, sentinel runs included, reads
    its own run and its partner's and writes the kept half: 3 bytes of
    traffic per key).

    Must mirror the shard_map bodies above; the structure tests pin the
    collective records to the lowered HLO's collective counts.
    """
    sizes = tuple(sizes)
    m = math.prod(sizes)
    m_inner = sizes[-1]
    n_pods = m // m_inner
    w = num_workers or m
    hash_homed = policy.homing == Homing.HASH_INTERLEAVED
    hier = policy.outer is not None
    local_phase = resolve_local_phase(local_phase, "bitonic")
    if hier and len(sizes) < 2:
        raise ValueError(
            f"hierarchical policy {policy.name!r} needs (pod, ..., inner) "
            f"axis sizes, got {sizes!r} — same contract as shard_map_sort")
    granule = engine_granule(m, num_workers, hash_homed)
    n_p = n + (-n) % granule
    chunk = n_p // m                                # one chunk, in elements
    B = chunk * itemsize                            # one chunk, in bytes
    log_inner = m_inner.bit_length() - 1
    pallas = local_phase == "pallas"
    out: List[Dict] = []

    def rec(level, op, inter, intra, hbm=0, elems=0):
        out.append({"level": level, "op": op,
                    "inter_pod_bytes": inter, "intra_pod_bytes": intra,
                    "local_hbm_bytes": hbm, "local_merge_elems": elems})

    def merge_split_rec(level, rows):
        """One network substage: every device merge-splits `rows` runs."""
        rec(level, "merge_split", 0, 0,
            hbm=(3 if pallas else 7) * m * rows * B,
            elems=(1 if pallas else 2) * m * rows * chunk)

    if not policy.localised:
        # leaf gather + one full gather per merge level: every device
        # re-reads everything it doesn't hold, at every level.  The local
        # work (each device sorts/merges the whole gathered array) is
        # always the reference tree — its levels are interleaved with the
        # gathers, so there is nothing for the fused kernel to keep
        # resident; ``local_phase`` changes nothing here.
        for lvl in range(w.bit_length()):
            rec(lvl, "all_gather",
                m * (m - m_inner) * B, m * (m_inner - 1) * B)
            rec(lvl, "local_sort" if lvl == 0 else "merge", 0, 0,
                hbm=2 * m * n_p * itemsize, elems=m * n_p)
        return out

    if hash_homed:
        # one-shot relayout: each device sends m-1 of its m chunk-blocks
        rec(0, "all_to_all",
            m * (m - m_inner) * (B // m), m * (m_inner - 1) * (B // m))
    tree = max(0, (w // m).bit_length() - 1)        # local merge-tree levels
    _check_runs(chunk, m, policy, local_phase)
    run = run_len(chunk) if pallas else chunk
    if run < chunk:
        runs = -(-chunk // run)
        rec(0, "local_sort", 0, 0, hbm=2 * m * runs * run * itemsize,
            elems=m * runs * run)
        net = run_network(runs)
        for _ in net.levels:
            rec(0, "run_merge", 0, 0, hbm=3 * m * net.m * run * itemsize,
                elems=m * net.m * run)
    else:
        rec(0, "local_sort", 0, 0,
            hbm=2 * n_p * itemsize * (1 if pallas else 1 + tree),
            elems=n_p * (1 if pallas else 1 + tree))
    for i in range(m.bit_length() - 1):
        j0 = i
        if hier and i >= log_inner:
            rec(i + 1, "all_gather", m * (n_pods - 1) * B, 0)
            for _ in range(i, log_inner - 1, -1):
                # cross-pod substage replayed per pod on the gathered rows
                merge_split_rec(i + 1, n_pods)
            j0 = log_inner - 1
        for j in range(j0, -1, -1):
            cross = (1 << j) >= m_inner
            rec(i + 1, "ppermute", m * B if cross else 0,
                0 if cross else m * B)
            merge_split_rec(i + 1, 1)
    return out


#: exchange_schedule op name -> HLO collective opcode
SCHEDULE_TO_HLO = {"all_to_all": "all-to-all", "all_gather": "all-gather",
                   "ppermute": "collective-permute"}


def collective_census(n: int, sizes: Sequence[int],
                      policy: LocalisationPolicy,
                      num_workers: Optional[int] = None,
                      itemsize: int = 4,
                      local_phase: Optional[str] = None) -> Dict[str, Dict]:
    """The analytic collective budget, keyed by HLO opcode.

    Folds `exchange_schedule`'s per-level records into per-device totals:
    ``{hlo_kind: {"count": executions, "wire_bytes": bytes sent per
    device}}``.  Schedule bytes are summed across devices; the per-device
    wire share (total / m) is exactly what the SPMD module's collectives
    move, so rule R1 can diff this dict against the lowered HLO's census
    with zero tolerance on counts and near-zero on bytes.
    """
    m = math.prod(tuple(sizes))
    out: Dict[str, Dict] = {}
    for r in exchange_schedule(n, sizes, policy, num_workers=num_workers,
                               itemsize=itemsize, local_phase=local_phase):
        kind = SCHEDULE_TO_HLO.get(r["op"])
        if kind is None:
            continue                       # local compute record
        e = out.setdefault(kind, {"count": 0, "wire_bytes": 0.0})
        e["count"] += 1
        e["wire_bytes"] += (r["inter_pod_bytes"] + r["intra_pod_bytes"]) / m
    return out


def make_engine_fn(mesh: Optional[Mesh], policy: LocalisationPolicy,
                   num_workers: Optional[int] = None,
                   local_sort: LocalSort = "bitonic", axis: Axis = AXIS,
                   local_phase: Optional[str] = None):
    """Jitted engine sort for one Table-1 case; input donated (step 5).

    With the global tracer on, each call records an ``engine.sort`` span
    holding the host work of the call as three children, all stamped with
    the call's id: ``sort.prepare`` (array coercion, NaN guard, padding),
    ``sort.dispatch`` (the jitted call until it returns: the enqueue, and
    on a call that builds the program also its trace, lowering and compile
    or cache load; ``build`` says which) and ``sort.unpad`` (the strip).
    The ``sort.builds`` counter moves each time JAX traces the program.
    """
    resolve_local_phase(local_phase, local_sort)    # fail fast, not at trace
    if mesh is None:
        a = axis if isinstance(axis, str) else axis[-1]
        mesh = jax.make_mesh((len(jax.devices()),), (a,),
                             axis_types=(AxisType.Auto,))
        axis = a
    axes = axis_tuple(axis)
    sizes = _axes_sizes(mesh, axes)
    m = math.prod(sizes)
    hash_homed = policy.homing == Homing.HASH_INTERLEAVED
    granule = engine_granule(m, num_workers, hash_homed)

    fn = partial(shard_map_sort, mesh=mesh, policy=policy,
                 num_workers=num_workers, local_sort=local_sort,
                 axis=axis, local_phase=local_phase)

    @functools.wraps(shard_map_sort)
    def build(x, *a, **kw):
        # the body runs only while JAX traces: one count per program build
        get_tracer().count("sort.builds", cat="engine", n=int(x.shape[0]),
                           sizes=list(sizes))
        return fn(x, *a, **kw)

    jitted = jax.jit(build, donate_argnums=(0,))
    entry = sort_entry(jitted, granule)

    @functools.wraps(entry)
    def traced(x, *a, **kw):
        tr = get_tracer()
        if not tr.enabled:
            return entry(x, *a, **kw)
        cid = next(_SORT_CALLS)
        # the span stamps everything the reconciler needs to recompute
        # exchange_schedule(n, sizes, policy) and check the stamped
        # per-level budgets against it — the trace carries the analytic
        # byte budget right next to the scheduler's observed charges
        with tr.span("engine.sort", cat="engine", call=cid,
                     sizes=list(sizes), num_workers=num_workers,
                     local_phase=local_phase,
                     policy={"localised": policy.localised,
                             "static_mapping": policy.static_mapping,
                             "homing": policy.homing.name,
                             "outer": policy.outer}) as sp:
            with tr.span("sort.prepare", cat="engine", call=cid):
                x, n = sort_prepare(x, granule)
            itemsize = jnp.dtype(x.dtype).itemsize
            sp.set(n=n, itemsize=itemsize)
            for lr in exchange_schedule(n, sizes, policy,
                                        num_workers=num_workers,
                                        itemsize=itemsize,
                                        local_phase=local_phase):
                sp.event("engine.exchange_level", call=cid, **lr)
            with tr.span("sort.dispatch", cat="engine", call=cid) as dp:
                builds = tr.total("sort.builds")
                y = jitted(x, *a, **kw)
                dp.set(build=tr.total("sort.builds") > builds)
            with tr.span("sort.unpad", cat="engine", call=cid):
                return sort_unpad(y, n)

    traced.lower = entry.lower
    traced.__wrapped__ = entry.__wrapped__
    return traced
