"""Attention: GQA + qk-norm + sliding window + cross-attention + KV cache.

Train/prefill attention is *chunked* (flash-style online softmax over KV
blocks via `lax.scan`) — the VMEM-localisation idea expressed at the XLA
level so that 32k-sequence prefill never materialises an (S x S) score
matrix. The Pallas kernel in `repro.kernels.flash_attention` is the TPU
drop-in for the same computation (`repro.kernels.ops.flash_attention`).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import ninit, pdt, rmsnorm, rope
from repro.sharding.partition import MeshPlan, ws

NEG_INF = -1e30


def init_attention(key, cfg: ArchConfig, cross: bool = False):
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kq, kk, kv_, ko = jax.random.split(key, 4)
    p = {
        "wq": ninit(kq, (D, H, hd), pdt(cfg)),
        "wk": ninit(kk, (D, KV, hd), pdt(cfg)),
        "wv": ninit(kv_, (D, KV, hd), pdt(cfg)),
        "wo": ninit(ko, (H, hd, D), pdt(cfg), 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5) * 50),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _head_axes(cfg: ArchConfig, plan: MeshPlan):
    """How to shard the (KV, Gq) grouped-head layout.

    Returns (kv_axis, gq_axis, expand). When the KV-head count does not
    divide the model axis but the full head count does, `expand` asks the
    caller to repeat KV heads up to H at compute time — the repeat is a
    local slice of the (replicated) KV tensor, and it makes the score/PV
    einsums shard over all H heads instead of running fully replicated.
    """
    if plan is None or plan.mesh is None:
        return None, None, False
    gq = cfg.num_heads // max(cfg.num_kv_heads, 1)
    kv_ax = plan.kv_axis
    if kv_ax is not None:
        return kv_ax, None, False
    if gq > 1 and gq % plan.tp_size == 0:
        return None, plan.tp, False
    if cfg.num_heads % plan.tp_size == 0 and gq > 1:
        return plan.tp, None, True  # expanded layout: KV_eff = H, Gq_eff = 1
    return None, None, False


def banded_swa_attention(q, k, v, *, window: int, plan: MeshPlan = None,
                         axes=(None, None)):
    """Sliding-window attention over a 2-block band: O(S*W) instead of O(S^2).

    The paper's locality discipline applied to the sequence dim: query block
    i touches only KV blocks {i-1, i} (block length == window), so 32k-token
    SWA prefill does S/(2W) = 4x less score work and movement than scanning
    every KV chunk.
    q: (B, Sq, KV, Gq, hd); k, v: (B, Sq, KV, hd); Sq % window == 0.
    """
    B, Sq, KV, Gq, hd = q.shape
    W = window
    nq = Sq // W
    kv_ax, gq_ax = axes
    b_ax = plan.batch_axes if plan else None
    scale = hd ** -0.5
    qb = jnp.transpose(q.reshape(B, nq, W, KV, Gq, hd),
                       (0, 1, 3, 4, 2, 5))                 # (B,nq,KV,Gq,W,hd)
    qb = ws(qb, plan, b_ax, None, kv_ax, gq_ax, None, None)
    kb = k.reshape(B, nq, W, KV, hd)
    vb = v.reshape(B, nq, W, KV, hd)
    prev = lambda t: jnp.concatenate(
        [jnp.zeros_like(t[:, :1]), t[:, :-1]], axis=1)
    # relative masks (W, W): diag block = causal & window; prev block = band
    qp = jnp.arange(W)[:, None]
    kp = jnp.arange(W)[None, :]
    mask_diag = (kp <= qp) & (qp - kp < W)
    mask_prev = (qp + W - kp) < W                          # kpos = kp - W
    block_valid = (jnp.arange(nq) > 0)                     # block 0 has no prev

    m = l = acc = None
    for kj, vj, mask, valid in [
            (prev(kb), prev(vb), mask_prev, block_valid),
            (kb, vb, mask_diag, jnp.ones((nq,), bool))]:
        kj = jnp.transpose(kj, (0, 1, 3, 2, 4))            # (B,nq,KV,W,hd)
        vj = jnp.transpose(vj, (0, 1, 3, 2, 4))
        s = jnp.einsum("bnkgqd,bnksd->bnkgqs", qb, kj,
                       preferred_element_type=jnp.float32) * scale
        full_mask = (mask[None, None, None, :, :]
                     & valid[:, None, None, None, None])  # (nq,1,1,W,W)
        s = jnp.where(full_mask[None], s, NEG_INF)
        s = ws(s, plan, b_ax, None, kv_ax, gq_ax, None, None)
        mj = jnp.max(s, axis=-1)
        pj = jnp.exp(s - mj[..., None])
        lj = jnp.sum(pj, axis=-1)
        pvj = jnp.einsum("bnkgqs,bnksd->bnkgqd", pj.astype(vj.dtype), vj,
                         preferred_element_type=jnp.float32)
        if m is None:
            m, l, acc = mj, lj, pvj
        else:
            m_new = jnp.maximum(m, mj)
            c1, c2 = jnp.exp(m - m_new), jnp.exp(mj - m_new)
            l = l * c1 + lj * c2
            acc = acc * c1[..., None] + pvj * c2[..., None]
            m = m_new
    out = acc / jnp.maximum(l, 1e-20)[..., None]           # (B,nq,KV,Gq,W,hd)
    out = jnp.transpose(out, (0, 1, 4, 2, 3, 5)).reshape(B, Sq, KV * Gq, hd)
    return out.astype(q.dtype)


def chunked_attention(q, k, v, *, q_positions, kv_positions, causal: bool,
                      window: int, kv_chunk: int = 1024, plan: MeshPlan = None,
                      axes=(None, None)):
    """Online-softmax attention over KV chunks.

    q: (B, Sq, KV, Gq, hd); k, v: (B, Skv, KV, hd). Positions are int32
    vectors used for causal/sliding-window masking. Returns (B, Sq, KV*Gq, hd).
    """
    B, Sq, KV, Gq, hd = q.shape
    Skv = k.shape[1]
    if (window and causal and Skv == Sq and Sq % window == 0
            and Sq // window > 1):
        return banded_swa_attention(q, k, v, window=window, plan=plan,
                                    axes=axes)
    kv_ax, gq_ax = axes
    b_ax = plan.batch_axes if plan else None
    # fallback when no head dim shards (e.g. musicgen's 24 heads): query rows
    # are independent, so shard the softmax state over the *sequence* dim
    seq_ax = None
    if (kv_ax is None and gq_ax is None and plan is not None
            and plan.mesh is not None and Sq % plan.tp_size == 0 and Sq > 1):
        seq_ax = plan.tp
    scale = hd ** -0.5
    kc = min(kv_chunk, Skv)
    nkc = -(-Skv // kc)
    pad = nkc * kc - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pad), constant_values=-(10 ** 9))
    # (B, KV, Gq, Sq, hd)
    qt = jnp.transpose(q, (0, 2, 3, 1, 4))
    qt = ws(qt, plan, b_ax, kv_ax, gq_ax, seq_ax, None)
    ks = jnp.transpose(k.reshape(B, nkc, kc, KV, hd), (1, 0, 3, 2, 4))  # (n,B,KV,kc,hd)
    vs = jnp.transpose(v.reshape(B, nkc, kc, KV, hd), (1, 0, 3, 2, 4))
    ks = ws(ks, plan, None, b_ax, kv_ax, None, None)
    vs = ws(vs, plan, None, b_ax, kv_ax, None, None)
    kps = kv_positions.reshape(nkc, kc)

    def body(carry, xs):
        m, l, acc = carry
        kj, vj, kpj = xs
        s = jnp.einsum("bkgqd,bksd->bkgqs", qt, kj,
                       preferred_element_type=jnp.float32) * scale
        s = ws(s, plan, b_ax, kv_ax, gq_ax, seq_ax, None)
        mask = jnp.ones((Sq, kc), bool)
        if causal:
            mask &= kpj[None, :] <= q_positions[:, None]
        if window:
            mask &= (q_positions[:, None] - kpj[None, :]) < window
        mask &= kpj[None, :] >= 0
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqs,bksd->bkgqd", p.astype(vj.dtype), vj,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        # pin the online-softmax state to the head sharding: without this the
        # partitioner is free to re-gather the (B,KV,Gq,Sq,*) state across the
        # model axis on every KV chunk
        m_new = ws(m_new, plan, b_ax, kv_ax, gq_ax, seq_ax)
        l_new = ws(l_new, plan, b_ax, kv_ax, gq_ax, seq_ax)
        acc_new = ws(acc_new, plan, b_ax, kv_ax, gq_ax, seq_ax, None)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, Gq, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, Gq, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, Gq, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (ks, vs, kps))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, Sq, KV * Gq, hd)
    return out.astype(q.dtype)


def _rowwise_positions(pos, B: int, Sq: int):
    """Normalise ``pos`` to a (B, Sq) int32 query-position matrix.

    Accepts the legacy scalar (one shared clock), a (B,) per-row clock
    (continuous batching: each slot decodes at its own position), or the
    full (B, Sq) matrix a page-stepped prefill passes."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        return jnp.broadcast_to(pos, (B, Sq))
    if pos.ndim == 1:
        return jnp.broadcast_to(pos[:, None], (B, Sq))
    return pos


def decode_attention(q, cache_k, cache_v, kpos, pos, *, window: int,
                     plan: MeshPlan = None, axes=(None, None),
                     cache_seq_axis=None):
    """Attention for Sq new tokens per row over a (ring-buffered) KV cache.

    q: (B, Sq, KV, Gq, hd); cache_k/v: (B, Sc, KV, hd); kpos: (B, Sc) or
    (Sc,) int32 holding the absolute position stored in each cache slot
    (-1 == empty); pos: scalar, (B,) or (B, Sq) int32 query positions.
    Per-row masks keep every row's output a function of its own cache
    alone, so rows at different positions decode in one call.
    """
    B, Sq, KV, Gq, hd = q.shape
    Sc = cache_k.shape[1]
    kpos = jnp.asarray(kpos)
    if kpos.ndim == 1:
        kpos = jnp.broadcast_to(kpos[None], (B, Sc))
    pos = _rowwise_positions(pos, B, Sq)
    kv_ax, gq_ax = axes
    b_ax = plan.batch_axes if plan else None
    scale = hd ** -0.5
    qt = jnp.transpose(q, (0, 2, 3, 1, 4))       # (B, KV, Gq, Sq, hd)
    qt = ws(qt, plan, b_ax, kv_ax, gq_ax, None, None)
    s = jnp.einsum("bkgqd,bskd->bkgqs", qt, cache_k,
                   preferred_element_type=jnp.float32) * scale
    s = ws(s, plan, b_ax, kv_ax, gq_ax, None, cache_seq_axis)
    mask = (kpos[:, None, :] >= 0) & (kpos[:, None, :] <= pos[:, :, None])
    if window:
        mask &= (pos[:, :, None] - kpos[:, None, :]) < window
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgqs,bskd->bkgqd", (p / l).astype(cache_v.dtype),
                     cache_v, preferred_element_type=jnp.float32)
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, Sq, KV * Gq, hd)
    return out.astype(q.dtype)


def apply_attention(p, x, *, cfg: ArchConfig, plan: MeshPlan,
                    positions=None, cache: Optional[dict] = None,
                    pos=None, kv_src=None, build_cache: bool = False,
                    cross: bool = False, kv_chunk: int = 1024,
                    cache_len: Optional[int] = None, write_mask=None):
    """Full attention block body (no residual/norm — the block adds those).

    Returns (y, new_cache). `cache` (decode) is a dict {k, v, kpos} for self
    attention or {k, v} for cross attention. `build_cache` (prefill) returns
    the cache built from this call's K/V. `write_mask` (B, Sq) bool gates
    which of a decode call's new tokens are committed to the cache (page-
    stepped prefill: pad rows/positions compute but never write).
    """
    B, Sq, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    Gq = H // KV
    W = 0 if cross else cfg.sliding_window
    kv_ax, gq_ax, expand = _head_axes(cfg, plan)
    axes = (kv_ax, gq_ax)
    KVe, Gqe = (H, 1) if expand else (KV, Gq)
    rep = (lambda t: jnp.repeat(t, Gq, axis=2)) if expand else (lambda t: t)
    b_ax = plan.batch_axes if plan else None

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if "q_norm" in p:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)

    new_cache = None
    cs_ax = plan.cache_seq_axis if plan else None
    if cs_ax is not None and cs_ax in (kv_ax, gq_ax):
        # decode memory is cache-read bound: prefer sharding the cache's
        # sequence dim over the model axis; heads stay replicated.
        kv_ax = gq_ax = None
        axes = (None, None)
        KVe, Gqe = KV, Gq
        rep = lambda t: t  # noqa: E731
    if cache is not None and not cross:
        # ---- decode / page-step: Sq new tokens per row, masked ring write
        k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
        v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
        if "k_norm" in p:
            k_new = rmsnorm(k_new, p["k_norm"], cfg.norm_eps)
        pos = _rowwise_positions(pos, B, Sq)
        q = rope(q, pos, cfg.rope_theta)
        k_new = rope(k_new, pos, cfg.rope_theta)
        Sc = cache["k"].shape[1]
        kpos_c = cache["kpos"]
        if kpos_c.ndim == 1:
            kpos_c = jnp.broadcast_to(kpos_c[None], (B, Sc))
        wmask = (jnp.ones((B, Sq), bool) if write_mask is None
                 else write_mask)
        slot = (pos % Sc).astype(jnp.int32)
        # masked one-hot scatter instead of dynamic_update_slice: each row
        # writes its own slot(s), so rows at different positions share one
        # call and a row's cache never depends on its wave-mates (the bit-
        # identity contract).  The one-hot multiply-sum hits exactly one
        # source per written slot (query positions within a call are
        # distinct), so committed values are written exactly.
        oh = ((slot[:, :, None] == jnp.arange(Sc)[None, None, :])
              & wmask[:, :, None])                     # (B, Sq, Sc)
        written = jnp.any(oh, axis=1)                  # (B, Sc)
        upd_k = jnp.einsum("bqs,bqkd->bskd", oh.astype(cache["k"].dtype),
                           k_new.astype(cache["k"].dtype))
        upd_v = jnp.einsum("bqs,bqkd->bskd", oh.astype(cache["v"].dtype),
                           v_new.astype(cache["v"].dtype))
        ck = jnp.where(written[:, :, None, None], upd_k, cache["k"])
        cv = jnp.where(written[:, :, None, None], upd_v, cache["v"])
        kpos = jnp.where(written,
                         jnp.einsum("bqs,bq->bs", oh.astype(jnp.int32), pos),
                         kpos_c)
        new_cache = {"k": ck, "v": cv, "kpos": kpos}
        out = decode_attention(q.reshape(B, Sq, KVe, Gqe, hd), rep(ck), rep(cv),
                               kpos, pos, window=W, plan=plan, axes=axes,
                               cache_seq_axis=plan.cache_seq_axis if plan else None)
    elif cache is not None and cross:
        # ---- decode through a cross layer: static image KV ----
        out = decode_attention(q.reshape(B, 1, KVe, Gqe, hd), rep(cache["k"]),
                               rep(cache["v"]), cache["kpos"], jnp.int32(2 ** 30),
                               window=0, plan=plan, axes=axes)
        new_cache = cache
    else:
        # ---- train / prefill ----
        src = kv_src if cross else x
        k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(x.dtype))
        if "k_norm" in p:
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
        if positions is None:
            positions = jnp.arange(Sq, dtype=jnp.int32)
        if not cross:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
            kv_positions = positions
        else:
            kv_positions = jnp.arange(src.shape[1], dtype=jnp.int32)
        out = chunked_attention(q.reshape(B, Sq, KVe, Gqe, hd), rep(k), rep(v),
                                q_positions=positions, kv_positions=kv_positions,
                                causal=not cross, window=W, kv_chunk=kv_chunk,
                                plan=plan, axes=axes)
        if build_cache:
            if cross:
                new_cache = {"k": k, "v": v,
                             "kpos": jnp.zeros((src.shape[1],), jnp.int32)}
            else:
                # cache sized for the full decode horizon; ring-buffer of
                # window size under SWA (requires Sq % window == 0)
                total = max(cache_len or Sq, Sq)
                Sc = min(W, total) if W else total
                keep = min(Sc, Sq)
                ck = k[:, -keep:].astype(x.dtype)
                cv = v[:, -keep:].astype(x.dtype)
                kp = positions[-keep:].astype(jnp.int32)
                if Sc > keep:
                    padw = ((0, 0), (0, Sc - keep), (0, 0), (0, 0))
                    ck = jnp.pad(ck, padw)
                    cv = jnp.pad(cv, padw)
                    kp = jnp.pad(kp, (0, Sc - keep), constant_values=-1)
                # per-row kpos: decode is per-slot-clocked, so each row
                # carries its own occupancy map from here on
                kp = jnp.broadcast_to(kp[None], (B, Sc))
                new_cache = {"k": ck, "v": cv, "kpos": kp}

    out = ws(out, plan, b_ax, None, axes[0] or axes[1], None)
    y = jnp.einsum("bshk,hkd->bsd", out.reshape(*out.shape[:2], H, hd),
                   p["wo"].astype(x.dtype))
    return y, new_cache
