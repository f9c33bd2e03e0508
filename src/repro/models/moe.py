"""Token-choice MoE with GShard-style capacity dispatch.

Expert placement is the paper's homing decision at pod scale: each expert is
*homed* on a model-axis shard; the dispatch einsum moves each token's
activation to its expert's home (all-to-all), compute runs local to the
expert shard, and the combine einsum brings results back. When the expert
count does not divide the model axis (mixtral: 8 experts, 16-way axis) the
experts are replicated and the FFN dim is TP-sharded instead
(`sharding/partition.py:make_plan` picks the expert axis).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import init_mlp, apply_mlp, ninit, pdt
from repro.sharding.partition import MeshPlan, ws


def init_moe(key, cfg: ArchConfig):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    scale_out = 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5) * 50
    p = {
        "router": ninit(kr, (D, E), jnp.float32),
        "we_gate": ninit(kg, (E, D, F), pdt(cfg)),
        "we_up": ninit(ku, (E, D, F), pdt(cfg)),
        "we_down": ninit(kd, (E, F, D), pdt(cfg), scale_out),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_mlp(ks, cfg, d_ff=cfg.num_shared_experts * F)
    return p


def _capacity(gs: int, cfg: ArchConfig) -> int:
    c = int(-(-gs * cfg.top_k * cfg.capacity_factor // cfg.num_experts))
    return max(4, -(-c // 4) * 4)


def apply_moe(p, x, cfg: ArchConfig, plan: MeshPlan = None, group_size: int = 2048):
    """x: (B, S, D) -> (y, aux_loss). Token-choice top-k with capacity drop."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    T = B * S
    gs = min(group_size, T)
    assert T % gs == 0, f"token count {T} not divisible by group size {gs}"
    Gn = T // gs
    C = _capacity(gs, cfg)
    b_ax = plan.batch_axes if plan else None
    e_ax = plan.expert_axis if plan else None
    f_ax = (plan.tp if plan and e_ax is None else None)
    fs_ax = plan.fsdp_axes if plan else None

    # un-SP first: gathering the bf16 residual here is ~1000x cheaper than
    # letting the dispatch einsum contract over a model-sharded token dim
    # (which psums the full f32 (E,D,Gn,C) dispatch output; mixtral iter2)
    x = ws(x, plan, b_ax, None, None)
    xg = x.reshape(Gn, gs, D)
    xg = ws(xg, plan, b_ax, None, None)
    logits = (xg.astype(jnp.float32) @ p["router"])            # (Gn, gs, E)
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, K)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)        # renormalise

    # ---- capacity-bucketed dispatch/combine (GShard) ----
    combine = jnp.zeros((Gn, gs, E, C), jnp.float32)
    prev = jnp.zeros((Gn, 1, E), jnp.float32)
    for kk in range(K):
        mk = jax.nn.one_hot(topi[..., kk], E, dtype=jnp.float32)   # (Gn,gs,E)
        posk = jnp.cumsum(mk, axis=1) - mk + prev                  # slot per token
        prev = prev + jnp.sum(mk, axis=1, keepdims=True)
        keep = mk * (posk < C)
        oh = jax.nn.one_hot(posk.astype(jnp.int32), C, dtype=jnp.float32)
        combine = combine + oh * (keep * topv[..., kk:kk + 1])[..., None]
    dispatch = (combine > 0).astype(x.dtype)
    combine = ws(combine, plan, b_ax, None, e_ax, None)

    # ---- send tokens to their experts' home shard (all-to-all under EP) ----
    xe = jnp.einsum("gsec,gsd->gecd", dispatch, xg)
    xe = ws(xe, plan, b_ax, e_ax, None, None)
    g = jnp.einsum("gecd,edf->gecf", xe, p["we_gate"].astype(x.dtype))
    u = jnp.einsum("gecd,edf->gecf", xe, p["we_up"].astype(x.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = ws(h, plan, b_ax, e_ax, None, f_ax)
    ye = jnp.einsum("gecf,efd->gecd", h, p["we_down"].astype(x.dtype))
    ye = ws(ye, plan, b_ax, e_ax, None, None)
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), ye)
    y = y.reshape(B, S, D)

    if "shared" in p:
        y = y + apply_mlp(p["shared"], x, cfg, plan)

    # ---- switch-style load-balance aux ----
    frac_tokens = jnp.mean(jax.nn.one_hot(topi[..., 0], E, dtype=jnp.float32),
                           axis=(0, 1))
    frac_gates = jnp.mean(gates, axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * frac_gates)
    return y, aux
