"""Serving cells: a published decoder behind `repro.runtime.server.DecodeServer`
(paged mode, ``homed`` scheduler), fed closed-loop bursts.

Set-up makes the weights on the device from the seed in one jitted call,
builds one server, and warms it with the mix's warm-up bursts, which run
every program the window runs (prefill pages, pool attach, cache reset,
decode step, publishing pages).  The window then submits burst after burst,
each when the last has drained, until ``seconds`` have passed; the burst
running at the close is drained outside the window.  Token times are taken
outside the program: each request's ``out`` list stamps ``perf_counter``
on every append, and the server only appends.

After the window: peak memory is read, the server is freed, and the plain
reference (``bench/configs/<reference>``) runs over a sample of the
finished requests drawn from the seed, the longest among them, and in a
mix with shared prefixes requests whose prefix was attached from the pool.
"""
from __future__ import annotations

import importlib.util
import time
from pathlib import Path
from typing import List

import numpy as np

from bench import traffic

HERE = Path(__file__).resolve().parents[1]


class TimedTokens(list):
    """A request's output list that stamps the host clock on each append."""

    def __init__(self):
        super().__init__()
        self.times: List[float] = []

    def append(self, tok) -> None:
        self.times.append(time.perf_counter())
        super().append(tok)


def load_reference(name: str):
    path = HERE / "configs" / name
    spec = importlib.util.spec_from_file_location(f"bench_ref_{path.stem}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch_config(m: dict):
    """The program's config for a published ``qwen3`` configuration."""
    from repro.configs.base import ArchConfig, ParallelConfig
    if m["model_type"] != "qwen3":
        raise ValueError(f"no serving path for model_type {m['model_type']}")
    return ArchConfig(
        name=m["name"], family="dense", num_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        qk_norm=True, rope_theta=float(m["rope_theta"]),
        norm_eps=m["rms_norm_eps"], dtype=m["torch_dtype"],
        param_dtype=m["torch_dtype"],
        parallel=ParallelConfig(fsdp=False, microbatches=1))


def make_weights(m: dict, seed: int, vocab_padded: int):
    """The benchmark's weights, on the device, from the seed, in one call.

    Matrices are N(0, initializer_range) in the served type; norm scales are
    1 + N(0, 0.1) in float32, so that a path that skipped one would show.
    The output head is the embedding's transpose (tied, as published); the
    rows past ``vocab_size`` up to the program's padded vocabulary are zero,
    so their logits are 0 and never win against 151936 real ones.
    """
    import jax
    import jax.numpy as jnp
    L, D, H, KV, hd, F, V = (m["num_hidden_layers"], m["hidden_size"],
                             m["num_attention_heads"],
                             m["num_key_value_heads"], m["head_dim"],
                             m["intermediate_size"], m["vocab_size"])
    dt, std = jnp.dtype(m["torch_dtype"]), m["initializer_range"]
    shapes = {"q_proj": (L, D, H, hd), "k_proj": (L, D, KV, hd),
              "v_proj": (L, D, KV, hd), "o_proj": (L, H, hd, D),
              "gate_proj": (L, D, F), "up_proj": (L, D, F),
              "down_proj": (L, F, D)}
    norms = {"input_layernorm": (L, D), "post_attention_layernorm": (L, D),
             "q_norm": (L, hd), "k_norm": (L, hd)}

    def make(key):
        ks = iter(jax.random.split(key, len(shapes) + len(norms) + 2))
        layers = {n: (std * jax.random.normal(next(ks), s)).astype(dt)
                  for n, s in shapes.items()}
        layers.update({n: 1.0 + 0.1 * jax.random.normal(next(ks), s)
                       for n, s in norms.items()})
        emb = (std * jax.random.normal(next(ks), (V, D))).astype(dt)
        emb = jnp.pad(emb, ((0, vocab_padded - V), (0, 0)))
        return {"embed": emb, "lm_head": emb.T, "layers": layers,
                "norm": 1.0 + 0.1 * jax.random.normal(next(ks), (D,))}

    return jax.jit(make)(traffic.jax_key(seed, 0))


def program_params(w: dict):
    """The program's parameter tree over the same arrays (no copies)."""
    p = w["layers"]
    return {"stack": {"m0": {
                "ln1": p["input_layernorm"],
                "attn": {"wq": p["q_proj"], "wk": p["k_proj"],
                         "wv": p["v_proj"], "wo": p["o_proj"],
                         "q_norm": p["q_norm"], "k_norm": p["k_norm"]},
                "ln2": p["post_attention_layernorm"],
                "mlp": {"w_gate": p["gate_proj"], "w_up": p["up_proj"],
                        "w_down": p["down_proj"]}}},
            "final_norm": w["norm"], "head": {"head_w": w["lm_head"]},
            "embed": {"tok_embed": w["embed"]}}


def check_layout(model, params) -> None:
    """Refuse to run when the program's parameter layout differs."""
    import jax
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the program's parameter layout differs from the "
                         "one the benchmark builds")


def run(ctx) -> None:
    """Set up, run the window, check: fills ``ctx`` (see `bench.run.Run`)."""
    import jax
    from repro.models.model import LM
    from repro.obs import Tracer
    from repro.runtime.server import DecodeServer, Request

    m, mix, seed = ctx.config, ctx.mix, ctx.seed
    srv_p = mix["server"]
    cfg = arch_config(m)
    model = LM(cfg)

    t = time.perf_counter()
    w = jax.block_until_ready(make_weights(m, seed, cfg.vocab_padded))
    params = program_params(w)
    check_layout(model, params)
    ctx.setup["init_s"] = time.perf_counter() - t

    tracer_epoch = time.perf_counter()      # the tracer's clock starts here
    tracer = Tracer() if ctx.trace else None
    srv = DecodeServer(cfg, params, batch_slots=srv_p["slots"],
                       max_len=srv_p["max_len"], scheduler=srv_p["scheduler"],
                       prompt_pad=srv_p["prompt_pad"], tracer=tracer)
    gen = traffic.ServeMix(mix, seed, cfg.vocab_size)
    rid = iter(range(1 << 62))

    def submit(burst) -> list:
        reqs = [Request(rid=next(rid), prompt=r.prompt, max_new=r.max_new,
                        session=r.session, out=TimedTokens()) for r in burst]
        for r in reqs:
            srv.submit(r)
        return reqs

    t = time.perf_counter()
    wu = mix["warmup"]
    for k in range(wu["bursts"]):
        submit(gen.burst(k, n=wu["requests"], max_new=wu["max_new"],
                         warmup=True))
        srv.run()
    ctx.setup["warmup_s"] = time.perf_counter() - t

    bursts = []                  # (t_submit, t_drained, requests)
    traced = mix["trace"]["bursts"] if ctx.trace else 0
    ctx.open_window()
    t0 = ctx.t0
    deadline = t0 + ctx.seconds
    k = 0
    while time.perf_counter() < deadline:
        if k == 0 and traced:
            ctx.capture_begin()
        with ctx.annotate("bench.burst"):
            t_sub = time.perf_counter()
            reqs = submit(gen.burst(k))
            srv.run()
        if k == traced - 1:
            ctx.capture_end()
        bursts.append((t_sub, time.perf_counter(), reqs))
        k += 1
    ctx.close_window()

    reqs = [r for _, _, rs in bursts for r in rs]
    t_sub = {r.rid: ts for ts, _, rs in bursts for r in rs}
    tokens = sum(sum(t0 <= x <= deadline for x in r.out.times) for r in reqs)
    ttft = [(r.out.times[0] - t_sub[r.rid]) * 1e3 for r in reqs if r.out]
    itl = [(b - a) * 1e3 for r in reqs
           for a, b in zip(r.out.times, r.out.times[1:])
           if t0 <= b <= deadline]
    unfinished = [r for r in reqs if len(r.out) != r.max_new]
    ctx.attempted, ctx.failed = len(reqs), len(unfinished)
    ctx.metric("output_tokens_per_s", tokens / ctx.seconds, "tokens/s")
    if ttft:
        ctx.metric("ttft_p90_ms", np.percentile(ttft, 90), "ms")
    if itl:
        ctx.metric("itl_p95_ms", np.percentile(itl, 95), "ms")
    ctx.info.update(bursts=len(bursts), requests=len(reqs), tokens=tokens,
                    ttft_samples=len(ttft), itl_samples=len(itl))

    # what the per-layer readers need
    ctx.data.update(
        model=m, page_size=srv.page_size,
        requests=[dict(plen=len(r.prompt), times=list(r.out.times),
                       burst=i) for i, (_, _, rs) in enumerate(bursts)
                  for r in rs],
        traced_bursts=list(range(min(traced, len(bursts)))),
        window=(t0, deadline))
    if tracer is not None:
        ctx.data["tracer"] = (tracer_epoch, tracer.records())
        ctx.host_spans = [(rec["name"], tracer_epoch + rec["ts"] / 1e6,
                           rec["dur"] / 1e6) for rec in tracer.records()
                          if rec["kind"] == "span"]

    attached = {r.rid: getattr(r, "_attached", 0) for r in reqs}
    done = [r for r in reqs if len(r.out) == r.max_new]
    ctx.read_memory()
    del srv, reqs, bursts
    check(ctx, w, m, done, attached)


def sample(done, attached, seed: int, chk: dict) -> list:
    """The finished requests to compare: the longest, then ``attached``
    requests whose prefix came from the pool, then others drawn from the
    seed until ``tokens`` served tokens are covered."""
    g = traffic.rng(seed, traffic.TAG_CHECK)
    order = [done[i] for i in g.permutation(len(done))]
    pick = {}
    if done:
        longest = max(done, key=lambda r: (len(r.out), r.rid))
        pick[longest.rid] = longest
    for r in [r for r in order if attached[r.rid] > 0][:chk["attached"]]:
        pick.setdefault(r.rid, r)
    for r in order:
        if sum(len(x.out) for x in pick.values()) >= chk["tokens"]:
            break
        pick.setdefault(r.rid, r)
    return list(pick.values())


def check(ctx, w, m, done, attached) -> None:
    ref = load_reference(ctx.config["reference"])
    chk, srv_p = ctx.mix["check"], ctx.mix["server"]
    pick = sample(done, attached, ctx.seed, chk)
    t = time.perf_counter()
    seqs = [(np.asarray(r.prompt), np.asarray(list(r.out))) for r in pick]
    gaps, control = ref.logit_gaps(w, m, seqs, chk["block_rows"],
                                   srv_p["max_len"], control=ctx.control)
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    if ctx.control:
        ctx.info["control_logit_gap"] = float(np.concatenate(control).max())
    ctx.info.update(checked_requests=len(pick),
                    checked_tokens=int(flat.size),
                    checked_attached=sum(attached[r.rid] > 0 for r in pick),
                    reference_s=time.perf_counter() - t)
    ctx.compare("logit_gap", float(flat.max()) if flat.size else np.inf)
    ctx.compare("unfinished", ctx.failed)
