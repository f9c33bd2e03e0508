"""The paper's Fig-1 micro-benchmark as a TPU kernel.

Localised version: the chunk is copied HBM->VMEM once (BlockSpec), then all
R repetition passes run *inside* VMEM before one write-back — arithmetic
intensity scales with R. The non-localised reference (`ref.localised_copy_ref`
compiled as written) performs R full-array passes, re-streaming HBM every
pass. Identical arithmetic, different locality — the Fig-1 gap.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _kernel(x_ref, o_ref, *, reps: int):
    y = x_ref[...].astype(jnp.float32)

    def body(_, y):
        return y * 1.0001 + 1.0

    y = jax.lax.fori_loop(0, reps, body, y)
    o_ref[...] = y.astype(o_ref.dtype)


def localised_copy(x, reps: int, *, block: int | None = None,
                   interpret: bool | None = None):
    """x: (chunks, block_len) -> same shape; R passes per chunk in VMEM."""
    chunks, L = x.shape
    bl = block or L
    return pl.pallas_call(
        functools.partial(_kernel, reps=reps),
        grid=(chunks,),
        in_specs=[pl.BlockSpec((1, bl), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, bl), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((chunks, L), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x)
