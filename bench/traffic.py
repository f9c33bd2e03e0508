"""The one traffic generator.  A mix is a data file, ``bench/traffic/<mix>.json``;
this module reads it and makes the inputs of every run from ``--seed``.

Every seed gets the same work: the lengths in a burst are fixed quantiles
of the mix's log-uniform range, and the sessions' shares are fixed by their
Zipf weights; the seed only chooses the tokens and the order.  So two seeds
differ by arrangement, not by the amount of work.

Kinds of mix:

``serve_bursts``  closed-loop bursts of requests: ``burst`` requests all
                  submitted at once, the next burst when the last drains.
                  With ``sessions`` > 0 each prompt is its session's fixed
                  ``prefix_len``-token prefix plus a fresh suffix; with 0
                  every prompt is fresh and in a session of its own.
``sort_calls``    sort calls over a few pre-made inputs of uniform int32 keys.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import List, NamedTuple, Optional

import numpy as np

HERE = Path(__file__).resolve().parent

#: stream tags, so that no two uses of one seed share random numbers
TAG_SESSIONS, TAG_BURST, TAG_WARMUP, TAG_JAX, TAG_CHECK = range(5)


def load(mix: str) -> dict:
    return json.loads((HERE / "traffic" / f"{mix}.json").read_text())


def rng(seed: int, *tags: int) -> np.random.Generator:
    """A generator for one use of ``seed``; any whole number is a seed."""
    return np.random.default_rng(
        np.random.SeedSequence([seed % (1 << 64), *tags]))


def jax_key(seed: int, *tags: int):
    """A JAX key for one use of ``seed`` (JAX itself keeps only 32 bits of
    a large seed, so two seeds could share a key)."""
    import jax
    word = np.random.SeedSequence([seed % (1 << 64), TAG_JAX, *tags]
                                  ).generate_state(1)[0]
    return jax.random.key(int(word))


def log_quantiles(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` lengths at the midpoint quantiles of a log-uniform [lo, hi]."""
    q = (np.arange(n) + 0.5) / n
    return np.rint(np.exp(np.log(lo) + (np.log(hi) - np.log(lo)) * q)
                   ).astype(np.int64)


def zipf_counts(n: int, sessions: int, s: float = 1.0) -> np.ndarray:
    """How many of ``n`` requests go to each session, in proportion to the
    Zipf weights 1/(1+i)^s (largest remainders get the rest)."""
    w = 1.0 / (1.0 + np.arange(sessions)) ** s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    rest = np.argsort(-(exact - counts), kind="stable")[:n - counts.sum()]
    counts[rest] += 1
    return counts


class Req(NamedTuple):
    prompt: np.ndarray     # int32 token ids
    max_new: int
    session: str


class ServeMix:
    """The requests of a ``serve_bursts`` mix for one seed."""

    def __init__(self, params: dict, seed: int, vocab: int):
        if params["kind"] != "serve_bursts":
            raise ValueError(f"not a serve mix: {params['kind']}")
        self.p, self.seed, self.vocab = params, seed, vocab
        n_sess, plen = params["sessions"], params["prefix_len"]
        self.prefixes = rng(seed, TAG_SESSIONS).integers(
            0, vocab, (n_sess, plen), dtype=np.int32)

    def burst(self, k: int, n: Optional[int] = None,
              max_new: Optional[int] = None, warmup: bool = False
              ) -> List[Req]:
        """Burst ``k`` (``n`` requests, the mix's burst size by default)."""
        p = self.p
        n = p["burst"] if n is None else n
        g = rng(self.seed, TAG_WARMUP if warmup else TAG_BURST, k)
        suffix = g.permutation(log_quantiles(*p["suffix_len"], n))
        out = g.permutation(log_quantiles(*p["output_len"], n))
        if max_new is not None:
            out = np.minimum(out, max_new)
        if p["sessions"]:
            sess = g.permutation(np.repeat(np.arange(p["sessions"]),
                                           zipf_counts(n, p["sessions"],
                                                       p["zipf_s"])))
        reqs = []
        for j in range(n):
            fresh = g.integers(0, self.vocab, suffix[j], dtype=np.int32)
            if p["sessions"]:
                s = int(sess[j])
                reqs.append(Req(np.concatenate([self.prefixes[s], fresh]),
                                int(out[j]), f"s{s}"))
            else:
                tag = "w" if warmup else "b"
                reqs.append(Req(fresh, int(out[j]), f"{tag}{k}.{j}"))
        return reqs


def sort_inputs(params: dict, seed: int, n: int, sharding):
    """The mix's pre-made inputs: ``inputs`` arrays of ``n`` uniform int32
    keys, made on the devices in one jitted call, each chip making its own
    share under ``sharding``."""
    import jax
    import jax.numpy as jnp
    if params["kind"] != "sort_calls" or params["keys"] != "uniform_int32":
        raise ValueError(f"not a uniform int32 sort mix: {params}")
    info = jnp.iinfo(jnp.int32)
    keys = [jax_key(seed, i) for i in range(params["inputs"])]

    def make(keys):
        return [jax.random.randint(k, (n,), info.min, info.max,
                                   dtype=jnp.int32) for k in keys]

    return jax.jit(make, out_shardings=[sharding] * len(keys))(keys)
