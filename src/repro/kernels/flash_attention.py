"""Flash attention Pallas TPU kernel: blocked online softmax with VMEM tiling.

This is KV-localisation at the cache level: each (query tile,
KV tile) pair is copied HBM->VMEM once via the BlockSpec index maps, all
arithmetic runs on the MXU out of VMEM, and only the finished output tile is
written back. Supports causal masking, sliding windows (with *block
skipping*: fully-masked KV tiles are never computed — the TPU analogue of
not fetching remote lines you will not read) and GQA via the KV index map.

Grid: (B, H, nq, nk) with nk minor-most — TPU grid order makes the KV axis
sequential, so the online-softmax state lives in VMEM scratch across steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            nk: int, sq: int, skv: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # block skipping: is any element of this tile unmasked?
    first_q, last_q = iq * bq, iq * bq + bq - 1
    first_k, last_k = ik * bk, ik * bk + bk - 1
    live = True
    if causal:
        live = jnp.logical_and(live, first_k <= last_q)
    if window:
        live = jnp.logical_and(live, last_k > first_q - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = (qpos < sq) & (kpos < skv)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    scale: float | None = None,
                    interpret: bool | None = None):
    """q: (B, H, Sq, hd); k, v: (B, KV, Skv, hd) -> (B, H, Sq, hd)."""
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    scale = scale if scale is not None else hd ** -0.5
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    nq, nk = -(-Sq // bq), -(-Skv // bk)
    pq, pk = nq * bq - Sq, nk * bk - Skv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, nk=nk,
                               sq=Sq, skv=Skv)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    return out[:, :, :Sq]
