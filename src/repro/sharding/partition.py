"""Static partitioning rules — the 'static mapping' leg of the paper's technique.

Every parameter and activation layout is chosen explicitly here; nothing is
left to the runtime. This mirrors the paper's static thread->core pinning
(Algorithm 1, step 3): placement decisions are made once, up front, and the
lowered HLO is the proof of where data lives.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Resolved placement plan for one (arch x shape x mesh) cell."""

    mesh: Optional[Mesh]
    dp: Tuple[str, ...] = ()          # data-parallel axes, e.g. ("pod", "data")
    tp: Optional[str] = None          # tensor-parallel axis ("model")
    # resolved per-cell decisions (None == replicate on that dim):
    batch_axes: Optional[Tuple[str, ...]] = None
    seq_axis: Optional[str] = None    # SP axis for the residual stream
    head_axis: Optional[str] = None
    kv_axis: Optional[str] = None
    expert_axis: Optional[str] = None
    fsdp_axes: Optional[Tuple[str, ...]] = None
    cache_seq_axis: Optional[object] = None  # str | tuple | None
    zero1_axes: Optional[Tuple[str, ...]] = None

    def axis_size(self, name) -> int:
        if self.mesh is None or name is None:
            return 1
        if isinstance(name, tuple):
            return math.prod(self.mesh.shape[a] for a in name)
        return self.mesh.shape[name]

    @property
    def dp_size(self) -> int:
        return self.axis_size(self.dp) if self.dp else 1

    @property
    def tp_size(self) -> int:
        return self.axis_size(self.tp) if self.tp else 1

    def sharding(self, *spec_dims) -> NamedSharding:
        assert self.mesh is not None
        return NamedSharding(self.mesh, P(*spec_dims))


NULL_PLAN = MeshPlan(mesh=None)


def ws(x, plan: MeshPlan, *spec_dims):
    """with_sharding_constraint that degrades to a no-op without a mesh."""
    if plan is None or plan.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(plan.mesh, P(*spec_dims)))


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


def make_plan(mesh: Optional[Mesh], cfg: ArchConfig, shape: ShapeSpec) -> MeshPlan:
    """Resolve the static placement plan for one cell."""
    if mesh is None:
        return NULL_PLAN
    names = mesh.axis_names
    dp = tuple(a for a in names if a != "model")
    tp = "model"
    tp_n = math.prod(mesh.shape[a] for a in [tp])
    dp_n = math.prod(mesh.shape[a] for a in dp)

    batch_axes = dp if _div(shape.global_batch, dp_n) else None
    seq_len = shape.seq_len if shape.kind != "decode" else 1
    seq_axis = (tp if (cfg.parallel.sequence_shard and _div(seq_len, tp_n)
                       and shape.kind != "decode") else None)
    head_axis = tp if _div(cfg.num_heads, tp_n) else None
    kv_axis = tp if _div(cfg.num_kv_heads, tp_n) else None
    expert_axis = tp if _div(cfg.num_experts, tp_n) else None
    # (mixtral iter3 tried dropping FSDP at inference: collective barely moved
    # — the big psum is the TP down-proj reduce, not FSDP — while the f32
    # expert buffers blew HBM 11->29GB. Refuted; FSDP stays whenever enabled.)
    fsdp_axes = dp if (cfg.parallel.fsdp and _div(cfg.d_model, dp_n)) else None
    zero1_axes = dp if (cfg.parallel.zero1 and _div(cfg.d_model, dp_n)) else None

    # decode-time KV cache layout: a cache of 8k+ positions shards its
    # sequence over "data" when the batch cannot shard (context parallel),
    # else over the model axis when the KV heads cannot
    cache_len = min(shape.seq_len, cfg.sliding_window) if cfg.sliding_window else shape.seq_len
    cache_seq_axis = None
    if shape.kind == "decode":
        if batch_axes is None and cache_len >= 8192:
            cache_seq_axis = "data" if "data" in names else None  # context parallel
        elif kv_axis is None and cache_len >= 8192:
            cache_seq_axis = tp
    return MeshPlan(mesh=mesh, dp=dp, tp=tp, batch_axes=batch_axes,
                    seq_axis=seq_axis, head_axis=head_axis, kv_axis=kv_axis,
                    expert_axis=expert_axis, fsdp_axes=fsdp_axes,
                    cache_seq_axis=cache_seq_axis, zero1_axes=zero1_axes)


# ---------------------------------------------------------------------------
# parameter partition specs (path-name driven)
# ---------------------------------------------------------------------------
def _leaf_spec(name: str, ndim: int, plan: MeshPlan, cfg: ArchConfig) -> P:
    tp, f = plan.tp, plan.fsdp_axes
    h, kv, e = plan.head_axis, plan.kv_axis, plan.expert_axis
    table = {
        # embeddings / head
        "tok_embed": P(tp, f),            # (Vp, D)
        "head_w": P(f, tp),               # (D, Vp)
        # attention (D,H,hd)/(H,hd,D)
        "wq": P(f, h, None),
        "wk": P(f, kv, None),
        "wv": P(f, kv, None),
        "wo": P(h, None, f),
        # dense mlp
        "w_gate": P(f, tp),
        "w_up": P(f, tp),
        "w_down": P(tp, f),
        # moe (E,D,F)/(E,F,D): EP when E divides, else TP on F
        "we_gate": P(e, f, None) if e else P(None, f, tp),
        "we_up": P(e, f, None) if e else P(None, f, tp),
        "we_down": P(e, None, f) if e else P(None, tp, f),
        "router": P(None, None),
        # mamba2
        "wz": P(f, tp),
        "wx": P(f, tp),
        "wBC": P(f, None),
        "wdt": P(f, tp),
        "conv_x": P(None, tp),
        "conv_bc": P(None, None),
        "out_proj": P(tp, f),
    }
    if name in table:
        spec = table[name]
        # trim/extend to leaf rank (vectors like scales fall through below)
        if len(spec) == ndim:
            return spec
    # norms, biases, A_log, dt_bias, D_skip, q/k norm scales: replicate
    return P(*([None] * ndim))


def param_specs(params_shape, plan: MeshPlan, cfg: ArchConfig):
    """Build a PartitionSpec pytree matching a parameter (shape-)pytree.

    Leaves under the 'stack' subtree carry a leading superblock axis ->
    their spec gets a None prepended.
    """
    def spec_for(path, leaf) -> P:
        keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        name = keys[-1]
        stacked = "stack" in keys
        ndim = len(leaf.shape) - (1 if stacked else 0)
        spec = _leaf_spec(name, ndim, plan, cfg)
        if stacked:
            spec = P(None, *spec)
        return spec

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


def shardings_for(tree, plan: MeshPlan, cfg: ArchConfig):
    specs = param_specs(tree, plan, cfg)
    return jax.tree.map(lambda s: NamedSharding(plan.mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


def batch_specs(batch_struct, plan: MeshPlan):
    """Chunk-contiguous 'local homing' layout: batch dim owned per-device."""
    def spec(_path, leaf):
        b = plan.batch_axes
        return P(b, *([None] * (len(leaf.shape) - 1)))
    return jax.tree_util.tree_map_with_path(spec, batch_struct)


def opt_specs(params_struct, plan: MeshPlan, cfg: ArchConfig):
    """Optimizer-state specs: m/v/ef mirror the params; ZeRO-1 additionally
    shards any dp-free leading dim over the dp axes where it divides."""
    pspecs = param_specs(params_struct, plan, cfg)

    def zero1(spec: P, leaf):
        if plan.zero1_axes is None:
            return spec
        dims = list(spec) + [None] * (len(leaf.shape) - len(spec))
        dp_n = plan.axis_size(plan.zero1_axes)
        for i, (d, sh) in enumerate(zip(dims, leaf.shape)):
            used = set()
            for dd in dims:
                for a in (dd if isinstance(dd, tuple) else (dd,)):
                    used.add(a)
            if d is None and sh % dp_n == 0 and not set(plan.zero1_axes) & used:
                dims[i] = plan.zero1_axes
                break
        return P(*dims)

    mv = jax.tree.map(zero1, pspecs, params_struct)
    return {"adam": {"m": mv, "v": mv, "step": P()}}


def full_opt_specs(opt_struct, params_struct, plan: MeshPlan, cfg: ArchConfig):
    """Spec tree matching init_opt_state's structure exactly."""
    base = opt_specs(params_struct, plan, cfg)
    out = {"adam": base["adam"]}
    if "ef" in opt_struct:
        out["ef"] = param_specs(params_struct, plan, cfg)
    return out
