"""The homecheck orchestrator: trace, lower, extract facts, run R1-R11.

`check_workload` takes a `Locale` plus a registered workload name, builds
the jitted entry point exactly as a caller would (`Locale.workload`),
lowers it for a representative granular input, and runs every rule over
the resulting artifacts (optimized SPMD HLO + jaxpr + the engine's
exchange-network descriptor).  `check_decode` does the same for the
serving decode step.  Nothing is ever *executed* — the whole analysis is
static, so locality bugs surface at compile time, not in benchmark diffs.

`rules` filters which rules run (None/'all' = every rule); R1/R2 need HLO
facts, R3/R5/R7/R8 the jaxpr, R6 the (policy, mesh-slice) the shard_map
engine was built for, R9 a scheduler lattice (only the serving target has
one — other targets note the skip), R10/R11 just the compiled HLO (R10
additionally takes `compiled.memory_analysis()` when the caller has it).

Budget notes (R1):

  * The analytic budget is `engine.collective_census` — available only for
    the shard_map sort engine.  Backends without a byte model (the
    constraint tree, microbench, decode) skip R1 with a report note; their
    collectives are still screened by R2.
  * The entry point returns *logical* order.  For a non-localised
    hash-interleaved policy the engine's output is still the interleaved
    (chunk, m) view, so the jit epilogue un-interleaves it — one extra
    full-array all-gather that is part of the entry-point contract, not
    the engine schedule.  The orchestrator budgets it explicitly.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

from repro.analysis.findings import Report, normalize_rules
from repro.analysis.kernelcheck import (r5_block_coverage, r7_index_arith,
                                        r8_dead_lanes)
from repro.analysis.netverify import r6_network_certification
from repro.analysis.rules import (R4_MIN_BYTES, r1_surprise_collective,
                                  r2_home_leak, r3_vmem_budget,
                                  r4_donation_audit)
from repro.analysis.vmem import pallas_call_facts, pallas_footprints


def _mesh_axes(mesh):
    names = tuple(mesh.axis_names)
    return names, tuple(mesh.shape[a] for a in names)


def check_artifacts(target: str, hlo_text: str, *,
                    jaxpr=None,
                    predicted: Optional[Dict[str, Dict]] = None,
                    mesh=None,
                    allowed_axes: Sequence[str] = (),
                    vmem_ceiling: Optional[int] = None,
                    donation_min_bytes: float = R4_MIN_BYTES,
                    network=None,
                    sched_lattice=None,
                    hbm_ceiling: Optional[int] = None,
                    memory_stats=None,
                    context: Optional[Dict] = None,
                    rules=None,
                    suppress: Sequence[str] = ()) -> Report:
    """Run the selected rules over already-produced artifacts.

    `predicted=None` skips R1 (no analytic budget); `mesh=None` skips R2;
    `jaxpr=None` skips R3/R5/R7/R8; `network=None` (else a
    `(policy, sizes, axes)` triple for the shard_map engine) skips R6;
    `sched_lattice=None` skips R9 (else a sequence of
    `schedcheck.LatticeEntry`); R10 gates peak live bytes against
    `hbm_ceiling` (default `repro.kernels.HBM_BYTES_PER_DEVICE`), taking
    XLA's own `compiled.memory_analysis()` figures when passed as
    `memory_stats`; R11 needs only the HLO text.
    """
    from repro.kernels import HBM_BYTES_PER_DEVICE, VMEM_BYTES_PER_CORE
    from repro.launch.hlo_cost import analyze

    active = set(normalize_rules(rules))
    report = Report(target=target, context=dict(context or {}))
    facts = analyze(hlo_text)
    coll_ops = facts["collective_ops"]

    if "R1" in active:
        if predicted is not None:
            r1_surprise_collective(report, coll_ops, predicted)
        else:
            report.notes.append("R1 skipped: no analytic collective budget "
                                "for this target")
    if "R2" in active:
        if mesh is not None:
            names, sizes = _mesh_axes(mesh)
            r2_home_leak(report, coll_ops, names, sizes, allowed_axes)
        elif coll_ops:
            report.notes.append("R2 skipped: no mesh to map device groups "
                                "onto")
    if jaxpr is not None:
        if "R3" in active:
            r3_vmem_budget(report, pallas_footprints(jaxpr),
                           vmem_ceiling or VMEM_BYTES_PER_CORE)
        if active & {"R5", "R7", "R8"}:
            kfacts = pallas_call_facts(jaxpr)
            if "R5" in active:
                r5_block_coverage(report, kfacts)
            if "R7" in active:
                r7_index_arith(report, kfacts)
            if "R8" in active:
                r8_dead_lanes(report, kfacts)
    if "R4" in active:
        r4_donation_audit(report, hlo_text, min_bytes=donation_min_bytes)
    if "R6" in active:
        if network is not None:
            policy, sizes, axes = network
            r6_network_certification(report, policy, sizes, axes)
        else:
            report.notes.append("R6 skipped: target has no exchange "
                                "network (not the shard_map engine)")
    if "R9" in active:
        if sched_lattice is not None:
            from repro.analysis.schedcheck import r9_scheduler_certification
            r9_scheduler_certification(report, sched_lattice)
        else:
            report.notes.append("R9 skipped: target has no serving "
                                "scheduler (serve[decode] only)")
    if "R10" in active:
        from repro.analysis.livecheck import r10_hbm_live_range
        r10_hbm_live_range(report, hlo_text,
                           hbm_ceiling or HBM_BYTES_PER_DEVICE,
                           memory_stats=memory_stats)
    if "R11" in active:
        from repro.analysis.livecheck import r11_collective_control_flow
        r11_collective_control_flow(report, hlo_text)
    return report.suppress(suppress)


def _round_up(n: int, g: int) -> int:
    return (n + g - 1) // g * g


def check_workload(locale, workload: str = "sort", *,
                   backend: Optional[str] = None,
                   num_workers: Optional[int] = None,
                   local_phase: Optional[str] = None,
                   logn: int = 12, reps: int = 4,
                   vmem_ceiling: Optional[int] = None,
                   hbm_ceiling: Optional[int] = None,
                   rules=None,
                   suppress: Sequence[str] = ()) -> Report:
    """Statically check one registered workload under `locale`.

    Builds the workload exactly as `Locale.workload` would, lowers it for a
    granule-aligned int32 input of ~2**logn elements, and runs the selected
    rules (default all of R1-R8).
    """
    import jax
    import jax.numpy as jnp

    from repro.core.engine import collective_census, engine_granule
    from repro.core.homing import Homing, axis_tuple
    from repro.core.sort import constraint_granule

    mesh, policy = locale.mesh, locale.policy
    axes = axis_tuple(locale.axis)
    if mesh is not None:
        sort_sizes = tuple(mesh.shape[a] for a in axes)
    else:
        sort_sizes = (len(jax.devices()),)      # make_engine_fn's own mesh
    m = math.prod(sort_sizes)
    hash_homed = policy.homing == Homing.HASH_INTERLEAVED

    if workload in ("sort", "engine"):
        backend = backend or ("shard_map" if workload == "engine"
                              else "constraint")
        kw = dict(backend=backend, num_workers=num_workers,
                  local_phase=local_phase)
        if backend == "shard_map":
            granule = engine_granule(m, num_workers, hash_homed)
        else:
            granule = constraint_granule(mesh, policy, num_workers,
                                         locale.axis)
        fn = locale.workload(workload, **kw)
        n = _round_up(1 << logn, granule)
        predicted = None
        network = None
        if backend == "shard_map":
            network = (policy, sort_sizes,
                       axes if mesh is not None else None)
            predicted = collective_census(n, sort_sizes, policy,
                                          num_workers=num_workers,
                                          itemsize=4,
                                          local_phase=local_phase)
            if not policy.localised and hash_homed and m > 1:
                # the logical-order epilogue: un-interleaving the output
                # costs one more full-array gather (see module docstring)
                B = (n // m) * 4
                e = predicted.setdefault("all-gather",
                                         {"count": 0, "wire_bytes": 0.0})
                e["count"] += 1
                e["wire_bytes"] += (m - 1) * B
        context = dict(workload=workload, backend=backend,
                       policy=policy.name, n=n,
                       mesh=dict(zip(*_mesh_axes(mesh))) if mesh else None)
        target = f"{workload}[{backend}]"
    elif workload == "microbench":
        fn = locale.workload("microbench", reps=reps)
        n = _round_up(1 << logn, m)
        predicted = None
        network = None
        context = dict(workload="microbench", reps=reps, policy=policy.name,
                       n=n, mesh=dict(zip(*_mesh_axes(mesh))) if mesh else None)
        target = "microbench"
    else:
        raise ValueError(
            f"homecheck has no static driver for workload {workload!r}; "
            f"serving goes through check_decode")

    dtype = jnp.float32 if workload == "microbench" else jnp.int32
    x = jnp.arange(n, dtype=jnp.int32).astype(dtype)
    compiled = fn.lower(x).compile()
    hlo = compiled.as_text()
    traceable = getattr(fn, "__wrapped__", fn)
    jaxpr = jax.make_jaxpr(traceable)(x)
    return check_artifacts(target, hlo, jaxpr=jaxpr, predicted=predicted,
                           mesh=mesh, allowed_axes=axes,
                           vmem_ceiling=vmem_ceiling, network=network,
                           hbm_ceiling=hbm_ceiling,
                           memory_stats=_memory_stats(compiled),
                           context=context, rules=rules, suppress=suppress)


def _memory_stats(compiled):
    """`compiled.memory_analysis()`, None where the backend lacks it."""
    try:
        return compiled.memory_analysis()
    except Exception:
        return None


def check_decode(mesh=None, *, cfg_name: str = "qwen3-0.6b",
                 batch_slots: int = 4, max_len: int = 64,
                 prompt_len: int = 8,
                 hbm_ceiling: Optional[int] = None,
                 sched_lattice=None,
                 rules=None,
                 suppress: Sequence[str] = ()) -> Report:
    """Statically check the serving decode step (the `DecodeServer` jit).

    Builds a reduced-config server over `mesh` (None = single device),
    derives the KV-cache avals via `jax.eval_shape` on prefill (nothing
    runs), and lowers one decode step.  R2's declared axes are the plan's
    batch axes (slot homing) plus "model" (tensor parallelism) — any
    collective spanning another axis reshards homed cache state.

    R9 certifies the scheduler over `sched_lattice` (default the cheap
    `schedcheck.FAST_LATTICE` corner; the CLI runs the full
    `DEFAULT_LATTICE` once per invocation and prints the certificate).
    """
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduce_config
    from repro.configs.base import ShapeSpec
    from repro.models.model import LM
    from repro.runtime.server import DecodeServer
    from repro.sharding.partition import NULL_PLAN, make_plan

    cfg = reduce_config(get_config(cfg_name))
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    plan = (make_plan(mesh, cfg, ShapeSpec("d", max_len, batch_slots,
                                           "decode"))
            if mesh is not None else NULL_PLAN)
    srv = DecodeServer(cfg, params, batch_slots=batch_slots,
                       max_len=max_len, plan=plan)

    toks = jax.ShapeDtypeStruct((batch_slots, prompt_len), jnp.int32)
    _, caches = jax.eval_shape(
        lambda p, t: model.prefill(p, {"tokens": t}, plan, max_len=max_len),
        params, toks)
    batch = {"tokens": jax.ShapeDtypeStruct((batch_slots, 1), jnp.int32)}
    # per-slot position clocks: the continuous-batching decode step takes
    # a (batch_slots,) vector, each row at its own position
    pos = jnp.full((batch_slots,), prompt_len, jnp.int32)
    args = (params, caches, batch, pos)
    compiled = srv._decode.lower(*args).compile()
    hlo = compiled.as_text()
    jaxpr = jax.make_jaxpr(srv._decode)(*args)
    allowed = tuple(plan.batch_axes or ()) + ("model",)
    if sched_lattice is None:
        from repro.analysis.schedcheck import FAST_LATTICE
        sched_lattice = FAST_LATTICE
    context = dict(workload="serve", cfg=cfg_name, batch_slots=batch_slots,
                   max_len=max_len,
                   mesh=dict(zip(*_mesh_axes(mesh))) if mesh else None)
    return check_artifacts("serve[decode]", hlo, jaxpr=jaxpr,
                           predicted=None, mesh=mesh, allowed_axes=allowed,
                           sched_lattice=sched_lattice,
                           hbm_ceiling=hbm_ceiling,
                           memory_stats=_memory_stats(compiled),
                           context=context, rules=rules, suppress=suppress)
