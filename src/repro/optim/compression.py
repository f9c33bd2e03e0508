"""Gradient compression: int8 block quantisation + error feedback.

Rationale: the cross-pod data-parallel axis is the slow link
(DCN/ICI-limited); quantising gradient traffic to int8 cuts its collective
bytes 4x. `compressed_psum` is the shard_map building block (all-gather of
int8 payloads + local dequant-reduce — wire format is genuinely 1 byte per
element); `error_feedback_update` keeps the quantisation bias from
accumulating across steps (property-tested for convergence).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize(x, block: int = 256):
    """Symmetric per-block int8 quantisation. Returns (q int8, scales f32)."""
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % block
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale[:, 0]


def dequantize(q, scale, shape):
    deq = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    return deq[: int(jnp.prod(jnp.array(shape)))].reshape(shape)


def compress_tree(tree, block: int = 256):
    return jax.tree.map(lambda x: quantize(x, block), tree,
                        is_leaf=lambda x: isinstance(x, jnp.ndarray))


def decompress_tree(ctree, shapes_tree):
    return jax.tree.map(lambda qs, ref: dequantize(qs[0], qs[1], ref.shape),
                        ctree, shapes_tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def error_feedback_update(grad, ef, block: int = 256):
    """Quantise (grad + ef); return (dequantised grad, new ef residual)."""
    g = grad.astype(jnp.float32) + ef
    q, s = quantize(g, block)
    deq = dequantize(q, s, g.shape)
    return deq.astype(grad.dtype), g - deq


def compressed_psum(x, axis_name: str, block: int = 256):
    """int8-wire psum for use inside shard_map.

    Wire format: each shard contributes an int8 payload + f32 per-block
    scales; the all-gather moves 1 byte/element instead of 4. Exact sum is
    recovered up to quantisation error (bounded by scale/2 per element).
    """
    q, s = quantize(x, block)
    qg = jax.lax.all_gather(q, axis_name)          # (n, blocks, block) int8 wire
    sg = jax.lax.all_gather(s, axis_name)          # (n, blocks) f32 (tiny)
    deq = qg.astype(jnp.float32) * sg[..., None]
    total = jnp.sum(deq, axis=0).reshape(-1)
    return total[: x.size].reshape(x.shape).astype(x.dtype)
