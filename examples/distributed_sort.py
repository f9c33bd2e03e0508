"""The paper's merge-sort experiment across all Table-1 cases, on both
execution backends:

  * ``constraint`` — the `with_sharding_constraint` hint tree (layout left
    to the XLA SPMD partitioner);
  * ``shard_map``  — the explicit engine: per-device ownership, the Pallas
    bitonic kernel as the local sort (interpret mode on CPU), and explicit
    ppermute / all_gather / all_to_all exchanges per `LocalisationPolicy`.

Every case is one `Locale` (same mesh + axis, different policy) and the
sort comes from ``locale.workload("sort", backend=...)``.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/distributed_sort.py
"""
import time

import jax
import jax.numpy as jnp

from repro.configs.paper_sort import CASES
from repro.core import BACKENDS, Homing, Locale, LocalisationPolicy
from repro.kernels import ops


def main():
    n_dev = len(jax.devices())
    locale = Locale.auto()
    n = 1 << 18
    for backend in BACKENDS:
        # the engine's Pallas leaf sort only interprets on CPU — keep the
        # example snappy with the jnp leaf sort at full size
        local_sort = jnp.sort if backend == "shard_map" else None
        for num, c in sorted(CASES.items()):
            pol = LocalisationPolicy(localised=c.localised,
                                     static_mapping=c.static_mapping,
                                     homing=Homing(c.homing))
            fn = locale.with_policy(pol).workload(
                "sort", backend=backend, local_sort=local_sort,
                num_workers=max(n_dev, 8))
            x = jax.random.randint(jax.random.key(0), (n,), 0, 1 << 30,
                                   jnp.int32)
            t0 = time.perf_counter()
            y = jax.block_until_ready(fn(x))
            dt = time.perf_counter() - t0
            assert bool(jnp.all(y[1:] >= y[:-1]))
            print(f"{backend:10s} case {num} ({pol.name:22s}): "
                  f"{dt*1e3:8.1f} ms  sorted=True")

    # the engine end-to-end with its real local phase: ONE fused pallas_call
    # per chunk (leaf sorts + the whole local merge tree in VMEM) and
    # merge-path merge-splits that compute only the kept half (Algorithm 2
    # for the entire local phase — local_phase="pallas", the default)
    x = jax.random.randint(jax.random.key(1), (1 << 12,), 0, 1 << 30,
                           dtype=jnp.int32)
    fn = locale.workload("engine", local_phase="pallas")
    y = jax.block_until_ready(fn(x))
    assert bool(jnp.all(y[1:] >= y[:-1]))
    print("shard_map engine + fused pallas local phase: ok (interpret mode)")

    # two distance classes: an emulated (pod, data, model) mesh, the deep
    # merge-split levels confined to intra-pod ppermutes and ONE all_gather
    # over the pod axis per top level (see README "Hierarchy")
    if n_dev >= 2 and n_dev % 2 == 0:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(n_data=n_dev // 2, n_model=1, n_pods=2)
        hier = Locale(mesh=mesh, axis=("pod", "data"),
                      policy=LocalisationPolicy.hierarchical())
        fn = hier.workload("sort", backend="shard_map", local_sort=jnp.sort)
        x = jax.random.randint(jax.random.key(2), (1 << 14,), 0, 1 << 30,
                               dtype=jnp.int32)
        y = jax.block_until_ready(fn(x))
        assert bool(jnp.all(y[1:] >= y[:-1]))
        print(f"hierarchical engine on 2x{n_dev // 2} emulated pods: ok")

    # the kernels standalone: the fused local sort on power-of-two and
    # non-power-of-two rows (sentinels pad in VMEM, never in HBM), and the
    # kept-half-only merge split
    xs = jax.random.randint(jax.random.key(1), (8, 512), 0, 1 << 30,
                            dtype=jnp.int32)
    ys = ops.local_sort(xs)
    assert bool(jnp.all(ys[:, 1:] >= ys[:, :-1]))
    zs = ops.local_sort(jax.random.randint(jax.random.key(3), (4, 384),
                                           0, 1 << 30, dtype=jnp.int32))
    assert bool(jnp.all(zs[:, 1:] >= zs[:, :-1]))
    lo = ops.merge_split(ys[:4], ys[4:], jnp.ones((4,), bool))
    assert bool(jnp.all(lo[:, 1:] >= lo[:, :-1]))
    print("pallas kernels (fused local_sort / merge_split): ok")


if __name__ == "__main__":
    main()
