"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. Device order is *static* — the paper's static
thread->core mapping: chunk i of the data always lives on the same chip.

Two hierarchy levels: the ``data``/``model`` axes live on the fast
intra-pod interconnect (ICI); the ``pod`` axis is the slow cross-pod link
(DCN).  `make_host_mesh(n_pods=...)` builds the *emulated-pod* form of the
same (pod, data, model) topology out of local (or placeholder host)
devices, so tests and benchmarks exercise the hierarchical engine without
real multi-host hardware — e.g. ``XLA_FLAGS=--xla_force_host_platform_
device_count=8`` plus ``make_host_mesh(n_pods=2, n_data=2, n_model=2)``.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(n_data: int | None = None, n_model: int = 1,
                   n_pods: int | None = None):
    """Small mesh over whatever local devices exist (tests/benchmarks).

    With ``n_pods`` the mesh gains a leading ``pod`` axis — the emulated-pod
    path for the hierarchical engine.  The shape is validated up front:
    every requested factor must divide the device count and the full shape
    must use *exactly* the available devices, otherwise `jax.make_mesh`
    either crashes opaquely (non-divisor) or silently builds a mesh over a
    device subset (undersized shape).
    """
    n = len(jax.devices())
    outer = (n_pods,) if n_pods is not None else ()
    for name, size in (("n_pods", n_pods), ("n_model", n_model),
                       ("n_data", n_data)):
        if size is not None and (not isinstance(size, int) or size < 1):
            raise ValueError(f"{name}={size!r} must be a positive int")
    fixed = n_model * (n_pods or 1)
    if n % fixed != 0:
        raise ValueError(
            f"cannot mesh {n} host device(s): n_model={n_model}"
            + (f" x n_pods={n_pods}" if n_pods is not None else "")
            + f" = {fixed} does not divide the device count {n}")
    n_data = n_data or (n // fixed)
    shape = outer + (n_data, n_model)
    axes = (("pod",) if n_pods is not None else ()) + ("data", "model")
    want = math.prod(shape)
    if want != n:
        raise ValueError(
            f"requested mesh shape {dict(zip(axes, shape))} needs {want} "
            f"device(s) but this host has {n} — the shape must use exactly "
            f"the available devices")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
