"""The unified placement API — the paper's technique as a first-class object.

The paper's contribution is a *programming technique*: decide where data
lives, make that decision once, and write every workload against it.  This
module is that technique's surface.  Two abstractions:

`Locale`
    A frozen bundle of ``(mesh, axis, LocalisationPolicy)`` — the one object
    a caller constructs.  Everything the repo previously did with loose
    free functions hangs off it:

    ==================  ======================================================
    ``locale.put(x)``       host→device placement under the policy's homing
                            (was ``to_layout``); returns a `Homed`.
    ``locale.pin(x)``       in-jit sharding constraint per policy (was
                            ``place``/``constrain``); no-op without a mesh or
                            under ``static_mapping=False``.
    ``locale.localise(x)``  the one-shot Algorithm-2 relayout into the
                            chunk-contiguous locally-homed layout.
    ``locale.pin_tree(t)``  `localise` applied leaf-wise to a pytree along a
                            chosen dim (KV-cache slot homing).
    ``locale.jit(fn)``      policy-aware jit with step-5 donation
                            ('free as soon as finished').
    ``locale.make(s, cb)``  data *born* locally homed: per-device callback
                            materialisation (the data-pipeline path).
    ``locale.workload(n)``  registry factory subsuming ``make_sort_fn`` /
                            ``make_engine_fn`` / ``make_microbench_fn``,
                            with unified ``backend=`` selection.
    ==================  ======================================================

`Homed`
    A registered pytree wrapping ``(data, homing, axis)``.  The layout
    metadata travels *with* the array: ``.logical()`` recovers logical
    1-D order automatically (was ``logical_view``), and because the homing
    is pytree *aux data*, combining two differently-homed values in any
    ``jax.tree`` operation raises a structure mismatch — mixed-homing bugs
    become type errors instead of silent wrong layouts.

Table-1 knob mapping: ``policy.localised`` (copy into locally-homed buffers),
``policy.static_mapping`` (explicit layouts vs compiler-chosen), and
``policy.homing`` (LOCAL_CHUNKED vs HASH_INTERLEAVED) — see `README.md`.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.homing import (Axis, Homing, check_divisible, logical_view,
                               to_layout)
from repro.core.homing import axis_size as _mesh_axis_size
from repro.core.localisation import LocalisationPolicy, localise, place


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class Homed:
    """An array plus the homing it was placed under.

    `data` is stored in *placed* form: 1-D for LOCAL_CHUNKED, the (n/N, N)
    stripe view for HASH_INTERLEAVED on a mesh (row-major reshape recovers
    logical order).  `homing` and `axis` are pytree aux data, so a `Homed`
    passes through `jit`/`tree_map` transparently while tree operations over
    mixed homings fail loudly with a treedef mismatch.
    """
    data: Any
    homing: Homing = Homing.LOCAL_CHUNKED
    axis: Axis = "data"

    def tree_flatten(self):
        return (self.data,), (self.homing, self.axis)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)

    def logical(self):
        """The logical 1-D order (lazy; free for LOCAL_CHUNKED)."""
        return logical_view(self.data, self.homing)

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def shape(self):
        return self.data.shape

    @property
    def size(self) -> int:
        return math.prod(self.data.shape)


# ---------------------------------------------------------------------------
# workload registry
# ---------------------------------------------------------------------------
_WORKLOADS: Dict[str, Callable] = {}


def register_workload(name: str):
    """Register a factory ``builder(locale, **kw) -> jitted fn`` under `name`.

    New workloads (striped pipelines, served caches, multi-host sorts) plug
    into `Locale.workload` here instead of growing another ``make_*_fn``.
    """
    def deco(builder: Callable) -> Callable:
        _WORKLOADS[name] = builder
        return builder
    return deco


def workload_names() -> Tuple[str, ...]:
    """The registered workload names (homecheck's discovery surface)."""
    return tuple(sorted(_WORKLOADS))


@dataclass(frozen=True)
class Locale:
    """Where data lives: ``(mesh, axis, policy)`` as one first-class value.

    ``mesh=None`` is the single-device degenerate locale: every placement
    method becomes the identity, so workload code is written once and runs
    unchanged from a laptop to a pod.  `axis` may be a tuple of mesh axes,
    outer (slow, DCN) axes first — ``Locale(mesh, axis=("pod", "data"))``
    linearises devices pod-major, and every placement method (`put`, `pin`,
    `localise`, `make`) and workload (`workload("sort",
    backend="shard_map")` — the hierarchical engine) works across both
    hierarchy levels.
    """
    mesh: Optional[Mesh] = None
    axis: Axis = "data"
    policy: LocalisationPolicy = LocalisationPolicy()

    # -- construction helpers ------------------------------------------------
    @classmethod
    def auto(cls, policy: LocalisationPolicy = LocalisationPolicy(),
             axis: str = "data", devices=None) -> "Locale":
        """A locale over all (or the given) devices; mesh=None when only one."""
        devices = list(jax.devices()) if devices is None else list(devices)
        if len(devices) <= 1:
            return cls(mesh=None, axis=axis, policy=policy)
        mesh = jax.make_mesh((len(devices),), (axis,), devices=devices,
                             axis_types=(AxisType.Auto,))
        return cls(mesh=mesh, axis=axis, policy=policy)

    def with_policy(self, policy: LocalisationPolicy) -> "Locale":
        """Same placement substrate, different Table-1 policy corner."""
        return Locale(mesh=self.mesh, axis=self.axis, policy=policy)

    # -- mesh geometry -------------------------------------------------------
    @property
    def axis_size(self) -> int:
        """#devices along the locale's axis (1 without a mesh)."""
        if self.mesh is None:
            return 1
        return _mesh_axis_size(self.mesh, self.axis)

    def spec(self, ndim: int = 1) -> P:
        """Chunk-contiguous spec: leading dim owned per-device, rest whole."""
        return P(self.axis, *([None] * (ndim - 1)))

    def owners(self, size: int) -> Tuple[int, ...]:
        """Home-device index of each of `size` chunk-contiguously homed items.

        The ownership map of `chunk_bounds` (paper step 1/2 — the same math
        the engine uses for sort chunks), applied to any per-item axis:
        ``owners(B)[s]`` is the linearised (pod-major on tuple axes) device
        index that item/slot ``s`` lives on.  The serving scheduler routes,
        batches and evicts decode slots with exactly this map.  Without a
        mesh every item is homed on the single device 0.
        """
        from repro.core.localisation import chunk_bounds
        out: list = []
        for dev, (lo, hi) in enumerate(chunk_bounds(size, self.axis_size)):
            out.extend([dev] * (hi - lo))
        return tuple(out)

    def sharding(self, ndim: int = 1) -> Optional[NamedSharding]:
        """The chunk-contiguous NamedSharding (None without a mesh)."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(ndim))

    # -- placement -----------------------------------------------------------
    def put(self, x, pad: bool = False) -> Homed:
        """Host→device placement of a 1-D array under the policy's homing.

        Replaces ``to_layout``.  Lengths must divide the axis size; with
        ``pad=True`` the input is extended with BIG sort-neutral sentinels
        (``pad_to_multiple``, granule = the locale's axis size) — the
        `Homed.logical()` view then carries the sentinel tail, which
        sorts/strips exactly like the sort's padding.
        """
        if pad:
            from repro.core.sort import pad_to_multiple
            x = pad_to_multiple(x, self.axis_size)
        if self.mesh is None:
            import jax.numpy as jnp
            return Homed(jnp.asarray(x), self.policy.homing, self.axis)
        if self.policy.homing == Homing.HASH_INTERLEAVED:
            placed = to_layout(x, self.mesh, self.policy.homing, self.axis)
            return Homed(placed, self.policy.homing, self.axis)
        check_divisible(x.shape[0], self.axis_size, self.policy.homing,
                        str(self.axis))
        placed = jax.device_put(x, self.sharding(getattr(x, "ndim", 1)))
        return Homed(placed, self.policy.homing, self.axis)

    def pin(self, x):
        """In-jit layout constraint per the policy (replaces place/constrain).

        A strict no-op when ``mesh is None`` or ``static_mapping=False`` —
        the 'leave it to the compiler' baseline stays a baseline.  Accepts a
        raw array or a `Homed` (returned re-wrapped).
        """
        if isinstance(x, Homed):
            if self.mesh is None or not self.policy.static_mapping:
                return x                         # no-op before any checking
            if x.homing != self.policy.homing:
                raise TypeError(
                    f"cannot pin a {x.homing.value!r}-homed array under a "
                    f"{self.policy.homing.value!r} locale — re-place it with "
                    f"Locale.put or relayout with Locale.localise")
            # constrain via the logical view, then restore the stored placed
            # form so same-homing Homed values stay shape-compatible
            pinned = self.pin(x.logical())
            return Homed(pinned.reshape(x.data.shape), x.homing, x.axis)
        if self.mesh is None or not self.policy.static_mapping:
            return x
        return place(x, self.mesh, self.policy, self.axis)

    def localise(self, x):
        """The one-shot Algorithm-2 relayout into the locally-homed layout."""
        if isinstance(x, Homed):
            return Homed(localise(x.logical(), self.mesh, self.axis),
                         Homing.LOCAL_CHUNKED, self.axis)
        return localise(x, self.mesh, self.axis)

    def pin_tree(self, tree, dim: int = 0, size: Optional[int] = None):
        """Home every pytree leaf chunk-contiguously along `dim`.

        The KV-cache form of localisation: each slot along `dim` (a batch
        slot, a request) lives wholly on the device that computes it.  Leaves
        where `dim` doesn't exist, doesn't match `size`, or doesn't divide
        the axis are left unconstrained (replicated small state).  No-op
        without a mesh or under ``static_mapping=False``.
        """
        if self.mesh is None or not self.policy.static_mapping:
            return tree
        N = self.axis_size

        def leaf(x):
            if getattr(x, "ndim", 0) <= dim:
                return x
            if size is not None and x.shape[dim] != size:
                return x
            if x.shape[dim] % N != 0:
                return x
            spec = [None] * x.ndim
            spec[dim] = self.axis
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, P(*spec)))

        return jax.tree.map(leaf, tree)

    # -- execution -----------------------------------------------------------
    def jit(self, fn, donate=(0,), **jit_kw):
        """Policy-aware jit: paper step 5 ('free as soon as finished') ==
        donating the input buffers the relayout consumes."""
        return jax.jit(fn, donate_argnums=tuple(donate or ()), **jit_kw)

    def make(self, shape: Tuple[int, ...], cb: Callable):
        """An array *born* locally homed: `cb(index)` materialises only the
        chunk each device owns (``jax.make_array_from_callback`` under the
        chunk-contiguous sharding).  Without a mesh, `cb` runs once over the
        full index — same code path, degenerate locale.
        """
        sh = self.sharding(len(shape))
        if sh is None:
            import jax.numpy as jnp
            return jnp.asarray(cb(tuple(slice(None) for _ in shape)))
        return jax.make_array_from_callback(shape, sh, cb)

    def workload(self, name: str, **kw):
        """Build the jitted entry point of a registered workload.

        The one factory behind what used to be ``make_sort_fn`` /
        ``make_engine_fn`` / ``make_microbench_fn``:

            locale.workload("sort", backend="constraint" | "shard_map")
            locale.workload("microbench", reps=R)
        """
        try:
            builder = _WORKLOADS[name]
        except KeyError:
            raise ValueError(f"unknown workload {name!r}; registered: "
                             f"{sorted(_WORKLOADS)}") from None
        return builder(self, **kw)

    def check(self, workload: str = "sort", *, rules=None, suppress=(),
              **kw):
        """Statically verify a workload's lowering against this locale.

        The homecheck hook: lowers ``self.workload(workload, ...)`` for a
        representative input and runs rules R1-R11 (surprise collectives,
        home leaks, VMEM budget, donation audit, pallas write-race/
        coverage, exchange-network certification, index-arithmetic lint,
        dead grid lanes, scheduler certification, HBM live-range,
        collective control flow) over the partitioned HLO, jaxpr, and
        exchange network without executing anything.  Returns an
        `analysis.Report`; ``report.clean`` is the contract.  `rules`
        selects a subset (e.g. ``rules=("R5", "R6")``; None = all);
        `suppress` drops findings by rule id (e.g. ``suppress=("R4",)``).
        R9 applies to the serving target only (other workloads note the
        skip); R10 gates against `repro.kernels.HBM_BYTES_PER_DEVICE`
        unless ``hbm_ceiling=`` overrides it.
        """
        from repro.analysis import check_workload
        return check_workload(self, workload, rules=rules,
                              suppress=suppress, **kw)


# ---------------------------------------------------------------------------
# built-in workloads
# ---------------------------------------------------------------------------
@register_workload("sort")
def _sort_workload(locale: Locale, *, backend: str = "constraint",
                   num_workers=None, local_sort=None, local_phase: str = None):
    """The paper's validation app: distributed merge sort (Algorithms 1-3).

    A tuple locale axis (e.g. ("pod", "data")) selects the two-distance-class
    engine: intra-pod neighbour ppermutes on the fast inner axis, cross-pod
    exchanges per ``policy.outer`` (see `LocalisationPolicy.hierarchical`).

    ``local_phase`` (engine backend) picks the per-device compute:
    "pallas" — the VMEM-resident production path (ONE fused kernel for leaf
    sorts + local merge tree, bitonic merge-splits that compute only the
    kept half); "reference" — the jnp oracle; None — auto by ``local_sort``.
    """
    from repro.core.sort import make_sort_fn
    axis = locale.axis if locale.mesh is not None else "data"
    return make_sort_fn(locale.mesh, locale.policy, num_workers=num_workers,
                        local_sort=local_sort, backend=backend, axis=axis,
                        local_phase=local_phase)


@register_workload("engine")
def _engine_workload(locale: Locale, **kw):
    """Alias: the explicit shard_map execution engine backend."""
    kw.setdefault("backend", "shard_map")
    if kw["backend"] != "shard_map":
        raise ValueError("workload('engine') is the shard_map backend; use "
                         "workload('sort', backend=...) to choose freely")
    return _sort_workload(locale, **kw)


@register_workload("microbench")
def _microbench_workload(locale: Locale, *, reps: int):
    """The Fig-1 repetitive-copy micro-benchmark."""
    from repro.core.microbench import make_microbench_fn
    axis = locale.axis if locale.mesh is not None else "data"
    return make_microbench_fn(locale.mesh, locale.policy, reps, axis=axis)
