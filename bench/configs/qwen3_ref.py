"""Plain reference of the Qwen3 dense decoder (model_type ``qwen3``), written
from the published description (Hugging Face ``Qwen3ForCausalLM``): RMSNorm
before attention and MLP, q/k RMSNorm per head, rotary embedding on the
split halves (``rotate_half``) with base ``rope_theta``, grouped-query
attention with causal mask and 1/sqrt(head_dim) scaling, SwiGLU MLP, final
RMSNorm, output head tied to the embedding.

Float32 throughout, every matmul at ``Precision.HIGHEST``; no cache, no
batching tricks: each row is one sequence from position 0.  It imports
nothing of the program and reads only the benchmark's own weights, whose
names follow the published checkpoint:

    embed (V, D); norm (D,); layers: input_layernorm (L, D),
    q_proj (L, D, H, hd), k_proj / v_proj (L, D, KV, hd), o_proj (L, H, hd, D),
    q_norm / k_norm (L, hd), post_attention_layernorm (L, D),
    gate_proj / up_proj (L, D, F), down_proj (L, F, D)

``int8=True`` is the control: the same forward computed in int8, with every
matmul operand rounded to int8 (weights per output channel, activations per
token, symmetric) and the products summed in float32.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _q8(x, axes):
    """Round ``x`` to int8 levels, one scale per slice over ``axes``."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.rint(x / s), -127, 127) * s


def _mm(spec, x, w, w_in_axes, int8):
    x, w = x.astype(F32), w.astype(F32)
    if int8:
        x = _q8(x, (-1,))
        w = _q8(w, w_in_axes)
    return jnp.einsum(spec, x, w, precision=HI)


def _rms(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, theta):
    """x: (R, S, N, hd) at positions 0..S-1."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def hidden(w, tokens, m: dict, int8: bool = False):
    """Final-normed hidden states (R, S, D) of token rows (R, S)."""
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    emb = w["embed"].astype(F32)
    if int8:
        emb = _q8(emb, (-1,))
    x = emb[tokens]
    S = tokens.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        h = _rms(x, p["input_layernorm"], eps)
        q = _mm("rsd,dhk->rshk", h, p["q_proj"], (0,), int8)
        k = _mm("rsd,dhk->rshk", h, p["k_proj"], (0,), int8)
        v = _mm("rsd,dhk->rshk", h, p["v_proj"], (0,), int8)
        q = _rope(_rms(q, p["q_norm"], eps), theta)
        k = _rope(_rms(k, p["k_norm"], eps), theta)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        s = jnp.einsum("rqhk,rshk->rhqs", q, k, precision=HI) \
            * q.shape[-1] ** -0.5
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jnp.einsum("rhqs,rshk->rqhk", jax.nn.softmax(s, -1), v,
                       precision=HI)
        x = x + _mm("rshk,hkd->rsd", a, p["o_proj"], (0, 1), int8)
        h = _rms(x, p["post_attention_layernorm"], eps)
        g = _mm("rsd,df->rsf", h, p["gate_proj"], (0,), int8)
        u = _mm("rsd,df->rsf", h, p["up_proj"], (0,), int8)
        x = x + _mm("rsf,fd->rsd", jax.nn.silu(g) * u, p["down_proj"], (0,),
                    int8)
        return x, None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    return _rms(x, w["norm"], eps)


@partial(jax.jit, static_argnames=("m_items", "control"))
def _gaps(w, tokens, pos, served, m_items, control):
    m = dict(m_items)
    V = m["vocab_size"]
    emb = w["embed"][:V].astype(F32)
    ref = hidden(w, tokens, m)
    ctl = hidden(w, tokens, m, int8=True) if control else ref
    emb8 = _q8(emb, (-1,))

    def row(args):
        h_ref, h_ctl, p, t = args
        logits = jnp.einsum("od,vd->ov", h_ref[p], emb, precision=HI)
        best = jnp.max(logits, -1)
        ok = (t >= 0) & (t < V)
        g = jnp.where(ok, best - jnp.take_along_axis(
            logits, jnp.clip(t, 0, V - 1)[:, None], -1)[:, 0], jnp.inf)
        if not control:
            return g, jnp.zeros_like(g)
        lc = jnp.einsum("od,vd->ov", _q8(h_ctl[p], (-1,)), emb8,
                        precision=HI)
        tc = jnp.argmax(lc, -1)
        gc = best - jnp.take_along_axis(logits, tc[:, None], -1)[:, 0]
        return g, gc

    return jax.lax.map(row, (ref, ctl, pos, served))


def logit_gaps(w, m: dict, seqs, block_rows: int, length: int,
               control: bool = False):
    """For each (prompt, served tokens) pair of ``seqs``: the gap by which
    each served token's reference logit lies below the reference's best at
    its position, and with ``control`` the same gap of the token that the
    int8 control puts first.  Rows are padded to ``length`` tokens and run
    ``block_rows`` at a time, so one compiled shape serves every block.
    Returns two lists of float arrays, one entry per sequence."""
    import numpy as np
    items = tuple(sorted((k, v) for k, v in m.items()
                         if isinstance(v, (int, float, str, bool))))
    width = max(len(out) for _, out in seqs)
    served, control_gaps = [], []
    for b in range(0, len(seqs), block_rows):
        blk = seqs[b:b + block_rows]
        toks = np.zeros((block_rows, length), np.int32)
        pos = np.zeros((block_rows, width), np.int32)
        out = np.full((block_rows, width), -1, np.int32)
        for i, (prompt, o) in enumerate(blk):
            seq = np.concatenate([prompt, o])[:length]
            toks[i, :len(seq)] = np.clip(seq, 0, m["vocab_size"] - 1)
            P = len(prompt)
            pos[i, :len(o)] = np.arange(P - 1, P - 1 + len(o))
            out[i, :len(o)] = o
        g, gc = _gaps(w, jnp.asarray(toks), jnp.asarray(pos),
                      jnp.asarray(out), items, control)
        g, gc = np.asarray(g), np.asarray(gc)
        for i, (_, o) in enumerate(blk):
            served.append(g[i, :len(o)])
            control_gaps.append(gc[i, :len(o)])
    return served, control_gaps
