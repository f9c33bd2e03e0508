"""The work a dense decoder needs, from its published sizes alone: the
operations and bytes that the per-layer readers divide by the chip's peaks.

``m`` is a configuration file's dict (Hugging Face key names).  Only the
work the model requires counts: the matmul weights once per step, the K/V
of each position a token attends to, 2 FLOPs per multiply-add.  Padding,
recomputation and whole-cache rewrites are the program's cost, not work.
"""
from __future__ import annotations


def dims(m: dict):
    return (m["num_hidden_layers"], m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"], m["intermediate_size"],
            m["vocab_size"])


def matmul_params(m: dict) -> int:
    """Weights that multiply each token: every layer's projections and MLP,
    and the output head (the embedding lookup multiplies nothing)."""
    L, D, H, KV, hd, F, V = dims(m)
    per_layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    return L * per_layer + D * V


def token_flops(m: dict, pos: int) -> int:
    """Forward FLOPs of one token at position ``pos`` (0-based): the matmuls
    and attention over ``pos + 1`` keys (scores and values)."""
    L, D, H, KV, hd, F, V = dims(m)
    return 2 * matmul_params(m) + 4 * L * H * hd * (pos + 1)


def weight_bytes(m: dict, itemsize: int = 2) -> int:
    """Bytes of the matmul weights, read once by every decode step."""
    return matmul_params(m) * itemsize


def kv_bytes_per_position(m: dict, itemsize: int = 2) -> int:
    """Bytes of K and V that one cached position holds over all layers."""
    L, D, H, KV, hd, F, V = dims(m)
    return L * 2 * KV * hd * itemsize
