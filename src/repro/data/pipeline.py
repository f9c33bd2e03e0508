"""Synthetic LM data pipeline with *localised placement*.

The pipeline is the data-path expression of the paper's technique: batches
are *born* locally homed through `Locale.make` — each device's batch chunk
is generated directly on (for) that device, never resharded after the fact
(Algorithm 1 steps 1-4 fused).  This is the same placement code path the
sort and the serving layer use; the pipeline constructs no shardings of
its own.

Host-side generation is *striped* to match: every leaf of the batch
(tokens, targets, frame embeddings, image embeddings) is produced by a
per-row content function, and under ``striped=True`` (the default) each
device's callback materialises only the rows that device owns — the full
``(B, S[, D])`` array is never built on the host.  ``striped=False`` keeps
the old build-everything-then-place path as the bit-exact oracle that
`tests/test_substrate.py` checks the striped path against.

Determinism: batch content is a pure function of (seed, step, element row),
so a restart replays exactly the same batches — the property checkpoint
resume and straggler/failure recovery rely on.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ArchConfig
from repro.core.api import Locale


def _row_tokens(seed: int, step: int, row: int, seq_len: int,
                vocab: int) -> np.ndarray:
    """One deterministic 'document': a noisy arithmetic sequence (learnable)."""
    rng = np.random.RandomState((seed * 1_000_003 + step * 7919 + row)
                                % (2 ** 31 - 1))
    start = rng.randint(0, vocab)
    stride = rng.randint(1, 17)
    toks = (start + stride * np.arange(seq_len + 1)) % vocab
    noise = rng.rand(seq_len + 1) < 0.02
    toks = np.where(noise, rng.randint(0, vocab, seq_len + 1), toks)
    return toks.astype(np.int32)


def _embed_rows(toks: np.ndarray, d_model: int) -> np.ndarray:
    """Stub frontend: frame embeddings derived deterministically per row."""
    return (np.sin(toks[..., None] * (1.0 + np.arange(d_model)))
            / 8.0).astype(np.float32)


def _row_image_embeds(seed: int, step: int, row: int, n_tokens: int,
                      d_model: int) -> np.ndarray:
    """Per-row image stub — a function of (seed, step, row) like every other
    leaf, so image batches stripe over devices exactly like token batches."""
    rng = np.random.RandomState((seed * 31 + step * 7919 + row * 104_729)
                                % (2 ** 31 - 1))
    return (rng.randn(n_tokens, d_model) / 8.0).astype(np.float32)


@dataclass
class SyntheticLM:
    cfg: ArchConfig
    global_batch: int
    seq_len: int
    seed: int = 0
    mesh: Optional[Mesh] = None
    striped: bool = True    # per-device generation; False = host-build oracle

    @property
    def locale(self) -> Locale:
        """Batch rows chunk-contiguous over the data-parallel axes.

        Mesh order is preserved, so on a (pod, data, model) mesh the axis
        tuple is ("pod", "data") — pod-major, matching the hierarchical
        engine's device linearisation: a pod's batch rows are contiguous
        and never born across the DCN boundary.
        """
        if self.mesh is None:
            return Locale(mesh=None)
        dp = tuple(a for a in self.mesh.axis_names if a != "model")
        return Locale(mesh=self.mesh, axis=dp)

    def batch(self, step: int) -> dict:
        B, S, V = self.global_batch, self.seq_len, self.cfg.vocab_size
        locale = self.locale
        if not self.striped:
            return self._host_batch(step, locale)

        built = {}

        def build(rows):
            # every callback sees the same row range per device — build once
            key = (rows.start, rows.stop)
            if key not in built:
                built[key] = np.stack([_row_tokens(self.seed, step, r, S, V)
                                       for r in rows])
            return built[key]

        def rows_of(index):
            return range(*index[0].indices(B))

        # localised generation: each device materialises only the rows it
        # owns — for every leaf, including the (B, S, D) embedding stripes
        def cb(index):
            return build(rows_of(index))[:, :-1]

        def cb_t(index):
            return build(rows_of(index))[:, 1:]

        batch = {"targets": locale.make((B, S), cb_t)}
        if self.cfg.embed_input:
            batch["tokens"] = locale.make((B, S), cb)
        else:
            def cb_e(index):
                return _embed_rows(build(rows_of(index))[:, :-1],
                                   self.cfg.d_model)

            batch["embeds"] = locale.make((B, S, self.cfg.d_model), cb_e)
        if self.cfg.family == "vlm":
            T, D = self.cfg.num_image_tokens, self.cfg.d_model

            def cb_i(index):
                return np.stack([_row_image_embeds(self.seed, step, r, T, D)
                                 for r in rows_of(index)])

            batch["image_embeds"] = locale.make((B, T, D), cb_i)
        return batch

    def _host_batch(self, step: int, locale: Locale) -> dict:
        """The pre-striping oracle: build every full array on the host, then
        place it.  Same per-row content functions, so `striped=True` must
        reproduce it bit-exactly; kept as the tests' oracle."""
        B, S, V = self.global_batch, self.seq_len, self.cfg.vocab_size

        def place(a: np.ndarray):
            if locale.mesh is None:
                return jnp.asarray(a)
            return jax.device_put(a, locale.sharding(a.ndim))

        full = np.stack([_row_tokens(self.seed, step, r, S, V)
                         for r in range(B)])
        batch = {"targets": place(full[:, 1:])}
        if self.cfg.embed_input:
            batch["tokens"] = place(full[:, :-1])
        else:
            batch["embeds"] = place(_embed_rows(full[:, :-1],
                                                self.cfg.d_model))
        if self.cfg.family == "vlm":
            T, D = self.cfg.num_image_tokens, self.cfg.d_model
            batch["image_embeds"] = place(
                np.stack([_row_image_embeds(self.seed, step, r, T, D)
                          for r in range(B)]))
        return batch


def make_batch_iterator(cfg, global_batch, seq_len, seed=0, mesh=None,
                        start_step: int = 0, striped: bool = True
                        ) -> Iterator[dict]:
    ds = SyntheticLM(cfg, global_batch, seq_len, seed, mesh, striped)
    step = start_step
    while True:
        yield ds.batch(step)
        step += 1
