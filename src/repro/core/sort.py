"""Distributed parallel merge sort — the paper's validation application.

Structure mirrors Algorithm 3: a local sort per worker (the
`mergesort_serial` leaves) followed by a log2(N)-level merge reduction tree.
The merge itself is the classic searchsorted rank-merge (log-depth, fully
vectorised — no data-dependent control flow, so it jits cleanly).

The paper's Table 1 axes map to:
  * homing      — input layout: chunk-contiguous vs hash-interleaved
  * localised   — one-shot `localise()` relayout before compute vs leaving
                  every tree level pinned to the hash layout (repeated
                  remote traffic, one all-to-all per level)
  * static      — explicit layout constraints everywhere vs letting the
                  compiler/runtime decide (the Tile-Linux-scheduler analogue)
"""
from __future__ import annotations

import functools
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.homing import Axis, Homing, axis_size
from repro.core.localisation import LocalisationPolicy

BIG = {jnp.dtype("int32"): jnp.iinfo(jnp.int32).max,
       jnp.dtype("float32"): jnp.inf}

BACKENDS = ("constraint", "shard_map")


def check_nan_free(x, where: str) -> None:
    """Raise a clear ValueError if a concrete float array contains NaN.

    NaN breaks both halves of the sort: it compares unordered in the
    searchsorted rank merge, and it sorts *after* the inf BIG sentinel, so
    the post-sort tail strip would keep a sentinel and silently drop the
    NaN.  Only concrete arrays can be inspected — inside a trace (jit) the
    guard is a no-op, which is why the jitted sort entry points check their
    (always concrete) inputs eagerly via `sort_entry` before dispatching.
    """
    if isinstance(x, jax.core.Tracer):
        return
    if not jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
        return
    bad = int(jnp.isnan(x).sum())
    if bad:
        raise ValueError(
            f"{where}: input contains {bad} NaN value(s) — NaN is unordered "
            f"under the rank merge and sorts after the inf padding sentinel, "
            f"so the result would silently drop it; filter NaNs out (e.g. "
            f"x[~jnp.isnan(x)]) or sort with jnp.sort directly")


def sort_entry(jitted, granule: int):
    """NaN-guard + eager-pad wrapper around a jitted sort.

    The wrapper sees the caller's concrete array *before* jit tracing, so
    `check_nan_free` can actually raise, and the BIG-sentinel padding to
    `granule` happens eagerly — GSPMD's partitioned concatenate mis-compiles
    on meshes with a >1-size axis outside the sort axis (padded elements
    arrive summed across it), so the traced fn must only ever see
    already-granular inputs; its internal `pad_to_multiple` then no-ops.
    The sentinel tail is stripped eagerly after the call.  `.lower` (used by
    the HLO structure tests) passes through to the jitted fn.
    """
    @functools.wraps(getattr(jitted, "__wrapped__", jitted))
    def call(x, *args, **kw):
        x, n = sort_prepare(x, granule)
        return sort_unpad(jitted(x, *args, **kw), n)
    call.lower = jitted.lower
    call.__wrapped__ = jitted
    return call


def sort_prepare(x, granule: int):
    """`sort_entry`'s eager first half: ``(padded x, caller's length)``.

    Coerces ``x`` to an array, raises on NaN, and pads with BIG sentinels
    up to a multiple of ``granule``."""
    x = jnp.asarray(x)                  # jit coerced sequences; keep doing so
    check_nan_free(x, "sort")           # pad skips its own scan: one pass
    return pad_to_multiple(x, granule, nan_check=False), x.shape[0]


def sort_unpad(y, n: int):
    """`sort_entry`'s eager second half: strip the sentinel tail."""
    return y[:n]


def pad_value(dtype):
    """Sort-neutral sentinel: sorts after every real element of `dtype`."""
    dt = jnp.dtype(dtype)
    if dt in BIG:
        return BIG[dt]
    if jnp.issubdtype(dt, jnp.floating):
        return jnp.inf
    return jnp.iinfo(dt).max


def pad_to_multiple(x, m: int, nan_check: bool = True):
    """Pad a 1-D array with BIG sentinels up to the next multiple of m.

    Sentinels sort after (or tie with) every real element, so after sorting
    the original multiset occupies the first `len(x)` slots — the caller
    strips them with `out[:len(x)]`.

    Float inputs must be NaN-free when padding occurs: NaN sorts after the
    inf sentinel, so the tail strip would keep a sentinel and silently drop
    the NaN (the searchsorted rank merge is NaN-unsound anyway).  Concrete
    float inputs are checked and raise ValueError (``nan_check=False`` for
    callers that already checked); traced inputs rely on the jitted entry
    points' eager `sort_entry` guard.
    """
    n = x.shape[0]
    n_pad = (-n) % m
    if n_pad == 0:
        return x
    if nan_check:
        check_nan_free(x, "pad_to_multiple")
    fill = jnp.full((n_pad,), pad_value(x.dtype), x.dtype)
    return jnp.concatenate([x, fill])


def constraint_granule(mesh: Optional[Mesh], policy: LocalisationPolicy,
                       num_workers: Optional[int], axis: Axis) -> int:
    """The constraint backend's padding granule: a chunk per worker, and —
    under static mapping — every merge level's run size divisible by the
    mesh axis, so no level falls back to a replicate constraint (the
    static-mapping policy promises an explicit layout at *every* level).
    The one definition shared by `distributed_merge_sort` (in-trace no-op
    re-pad) and `make_sort_fn` (the eager pad that must match it).
    """
    m = num_workers or (axis_size(mesh, axis) if mesh is not None else 8)
    if mesh is not None and policy.static_mapping:
        return m * axis_size(mesh, axis)
    return m


def check_pad_outside_trace(n: int, granule: int, mesh: Optional[Mesh],
                            axis: Axis, where: str) -> None:
    """Trace-time guard: in-jit sentinel padding is unsafe on some meshes.

    GSPMD mis-partitions the padding `concatenate` when the mesh has a
    >1-size axis outside the sort axis (padded elements arrive *summed*
    across it — silently wrong results).  All lengths/axis sizes are static,
    so this raises at trace time in exactly the dangerous corner; the
    blessed entry points (`make_sort_fn` / `Locale.workload`) pre-pad
    eagerly via `sort_entry` and never trip it.
    """
    if mesh is None or n % granule == 0:
        return
    if mesh.devices.size > axis_size(mesh, axis):
        raise ValueError(
            f"{where}: input length {n} needs in-trace sentinel padding to a "
            f"multiple of {granule}, but mesh axes outside {axis!r} have "
            f"size > 1 — GSPMD mis-partitions the padding concatenate there "
            f"(elements summed across the unrelated axis). Pre-pad with "
            f"pad_to_multiple(x, {granule}) outside jit, or call through "
            f"make_sort_fn / Locale.workload, which pad eagerly.")


def merge_sorted(a, b):
    """Merge two sorted 1-D arrays (stable, duplicate-safe rank merge).

    Gather form: `ia` (each a-element's output position, strictly
    increasing) is inverted with one more searchsorted, so every output
    element is *read* from a or b rather than scattered into place.
    Scatter-free on purpose — GSPMD mis-partitions chained set-scatters on
    meshes with an unrelated >1-size axis (elements arrive summed across
    it), which the constraint backend's sharded merge tree would trip.
    """
    na, nb = a.shape[-1], b.shape[-1]
    if na == 0 or nb == 0:              # static shapes: nothing to interleave
        return jnp.concatenate([a, b], axis=-1)
    ia = jnp.arange(na) + jnp.searchsorted(b, a, side="left")
    k = jnp.arange(na + nb)
    ra = jnp.searchsorted(ia, k, side="left")    # a-elements placed before k
    ra_c = jnp.minimum(ra, na - 1)
    is_a = (ra < na) & (jnp.take(ia, ra_c) == k)
    rb = jnp.clip(k - ra, 0, nb - 1)
    return jnp.where(is_a, jnp.take(a, ra_c), jnp.take(b, rb))


_merge_rows = jax.vmap(merge_sorted)


def _constrain_runs(runs, mesh: Optional[Mesh], policy: LocalisationPolicy,
                    axis: Axis = "data"):
    """Layout the (count, size) run matrix per policy, between tree levels."""
    if mesh is None or not policy.static_mapping:
        return runs
    N = axis_size(mesh, axis)
    count, size = runs.shape
    if not policy.localised and policy.homing == Homing.LOCAL_CHUNKED:
        # paper case 2/4: the conventional code under local homing — the whole
        # array is homed where it was created (one tile), every worker reads
        # remotely. Pod analogue: full replication (broadcast per level).
        return jax.lax.with_sharding_constraint(
            runs, NamedSharding(mesh, P(None, None)))
    if policy.localised:
        # each run homed on its leader's device (chunk-contiguous rows)
        spec = P(axis, None) if count % N == 0 else P(None, axis) \
            if size % N == 0 else P(None, None)
        return jax.lax.with_sharding_constraint(runs, NamedSharding(mesh, spec))
    # hash-for-home: every run striped element-wise across all devices
    if size % N == 0:
        r = runs.reshape(count, size // N, N)
        r = jax.lax.with_sharding_constraint(
            r, NamedSharding(mesh, P(None, None, axis)))
        return r.reshape(count, size)
    return runs


def distributed_merge_sort(x, mesh: Optional[Mesh] = None,
                           policy: LocalisationPolicy = LocalisationPolicy(),
                           num_workers: Optional[int] = None,
                           local_sort: Callable = jnp.sort,
                           axis: Axis = "data"):
    """Sort a 1-D array with an m-worker merge tree (m = #devices default).

    Arbitrary lengths are supported: the input is padded with BIG sentinels
    up to the next multiple of `constraint_granule(...)` and the padding is
    stripped after the tree.  Float inputs must be NaN-free (see
    `pad_to_multiple`).  On meshes with a >1-size axis outside `axis`,
    non-granular lengths must be pre-padded outside jit (`make_sort_fn` /
    `Locale.workload` do this; `check_pad_outside_trace` rejects the rest).
    """
    n = x.shape[0]
    m = num_workers or (axis_size(mesh, axis) if mesh is not None else 8)
    assert (m & (m - 1)) == 0, m

    granule = constraint_granule(mesh, policy, num_workers, axis)
    check_pad_outside_trace(n, granule, mesh, axis, "distributed_merge_sort")
    x = pad_to_multiple(x, granule)
    runs = x.reshape(m, x.shape[0] // m)
    runs = _constrain_runs(runs, mesh, policy, axis)
    runs = local_sort(runs, axis=-1)                 # leaves of the tree
    runs = _constrain_runs(runs, mesh, policy, axis)
    while runs.shape[0] > 1:
        merged = _merge_rows(runs[0::2], runs[1::2])
        runs = _constrain_runs(merged, mesh, policy, axis)
    return runs[0][:n]


def make_sort_fn(mesh, policy: LocalisationPolicy, num_workers=None,
                 local_sort=None, backend: str = "constraint",
                 axis: Axis = "data", local_phase: str = None):
    """Jitted sort for one Table-1 case; input buffer donated (step 5).

    backend="constraint": the original `with_sharding_constraint`-hint tree —
    layout is *suggested* and the XLA SPMD partitioner picks the collectives.
    backend="shard_map": the explicit per-device execution engine
    (`repro.core.engine`) — ownership, local Pallas sort and inter-device
    exchange are spelled out literally (paper Algorithms 1-3).

    `local_sort=None` picks the backend default (jnp.sort for the hint
    backend, the Pallas bitonic kernel for the engine).  `local_phase`
    selects the engine's per-device compute: "pallas" (fused VMEM-resident
    local-sort + kept-half merge-split kernels), "reference" (the jnp
    oracle), or None = auto by `local_sort` — engine backend only; the
    constraint tree has no kernel path.

    Callers normally reach this through `Locale.workload("sort", ...)`
    (`repro.core.api`), which supplies (mesh, axis, policy) from one object.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; want one of {BACKENDS}")
    if backend == "shard_map":
        from repro.core.engine import make_engine_fn   # local: avoid cycle
        return make_engine_fn(mesh, policy, num_workers=num_workers,
                              local_sort=local_sort or "bitonic",
                              axis=axis, local_phase=local_phase)
    if local_phase not in (None, "reference"):
        raise ValueError(
            f"local_phase={local_phase!r} needs backend='shard_map' — the "
            f"constraint tree's local phase is the jnp reference by nature")
    fn = partial(distributed_merge_sort, mesh=mesh, policy=policy,
                 num_workers=num_workers, local_sort=local_sort or jnp.sort,
                 axis=axis)
    granule = constraint_granule(mesh, policy, num_workers, axis)
    return sort_entry(jax.jit(fn, donate_argnums=(0,)), granule)
