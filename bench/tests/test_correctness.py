"""Each cell's window loop, rehearsed on the CPU through the harness's own
functions at a size a test run can hold, and the comparison that decides
``correct`` shown to fail: for the control (the reference one step below the
configuration) and for each fault a cell can have, planted under the timed
path.  The command itself still refuses to run without a TPU."""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench.peaks
from bench import calibrate, traffic
from bench import run as bench_run
from bench.systems import serve, sort

#: the serve cells at test size: the program's own tiny widths, float32
TINY = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=128,
            vocab_size=503, torch_dtype="float32", initializer_range=0.2)

#: every cell the harness drives: (configuration, traffic, chips); a cell
#: may be left out of BENCHMARK.json until it is proven on the chip
CELLS = {"sort-1chip": ("sort-int32-case8", "uniform", 1),
         "sort-4chip": ("sort-int32-case8-4chip", "uniform", 4),
         "serve-prefix-sessions": ("qwen3-0.6b", "prefix-sessions", 1),
         "serve-decode-heavy": ("qwen3-0.6b", "decode-heavy", 1)}

#: the limits at test size: the sort's are exact; the float32 test model
#: serves the reference's own argmax, so any gap at all is a fault
LIMITS = {"sort": {"wrong_keys": 0, "misplaced_shares": 0},
          "serve": {"logit_gap": 0.05, "unfinished": 0}}


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    """The readers need a peak table entry; the CPU has no published one."""
    monkeypatch.setitem(bench.peaks.PEAKS, "cpu",
                        bench.peaks.Peak(1e12, 1e11, 1e9, "test only"))


def make_run(name, seconds=2.0, trace=False, burst=None, **config):
    cfg_name, mix_name, chips = CELLS[name]
    cell = {"name": name, "config": cfg_name, "traffic": mix_name,
            "chips": chips}
    cfg = json.loads((bench_run.HERE / "configs" / f"{cfg_name}.json"
                      ).read_text())
    mix = traffic.load(mix_name)
    if cfg["system"] == "sort":
        cfg = dict(cfg, array_size=4096 * chips)
    else:
        cfg = dict(cfg, **TINY)
        mix = dict(mix, burst=burst or 8)
    cfg.update(config)
    return bench_run.load_spec(), bench_run.Run(
        cell, cfg, mix, LIMITS[cfg["system"]], seed=2**31 + 3,
        seconds=seconds, trace=trace, devices=jax.devices()[:chips])


def execute(name, system, **kw):
    spec, run = make_run(name, **kw)
    return run, bench_run.execute(spec, run, system)


# ---------------------------------------------------------------------------
# the sort
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["sort-1chip", "sort-4chip"])
def test_sort_window_is_correct(name):
    run, res = execute(name, sort)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["sort_keys_per_s"]["value"] > 0
    assert "setup_s" in res["metrics"]
    assert not run.compiles["window"].get("lower")
    assert list(res)[-1] == "checks"


def _fault_identity(x):
    return jnp.copy(x)


def _fault_half(x):
    n = x.shape[0] // 2
    y = jnp.concatenate([jnp.sort(x[:n]), x[n:]])
    return jax.device_put(y, x.sharding)


def _fault_altered(x):
    y = jnp.sort(x)
    return jax.device_put(y.at[y.shape[0] // 3].add(1), x.sharding)


def _fault_no_exchange(x):
    """Each chip sorts its own share; the merge-split network is left out."""
    from jax.sharding import PartitionSpec as P
    mesh = x.sharding.mesh
    return jax.shard_map(jnp.sort, mesh=mesh, in_specs=P("data"),
                         out_specs=P("data"))(x)


@pytest.mark.parametrize("name,fault", [
    ("sort-1chip", _fault_identity),
    ("sort-1chip", _fault_half),
    ("sort-1chip", _fault_altered),
    ("sort-1chip", calibrate.control_sort),
    ("sort-4chip", _fault_no_exchange),
    ("sort-4chip", _fault_altered),
    ("sort-4chip", calibrate.control_sort),
])
def test_sort_faults_and_control_are_not_correct(name, fault):
    spec, run = make_run(name, seconds=0.5)
    run_sort = lambda r: sort.run(r, sort_fn=fault)  # noqa: E731
    res = bench_run.execute(spec, run, type("S", (), {"run": run_sort}))
    assert not res["correct"], res["checks"]
    assert res["checks"]["wrong_keys"]["value"] > 0


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["serve-prefix-sessions",
                                  "serve-decode-heavy"])
def test_serve_window_is_correct(name):
    run, res = execute(name, serve, burst=32)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 8 and res["failed"] == 0
    for m in ("output_tokens_per_s", "ttft_p90_ms", "itl_p95_ms", "setup_s"):
        assert res["metrics"][m]["value"] > 0
    assert not run.compiles["window"].get("lower"), run.compiles
    assert run.info["checked_tokens"] >= run.mix["check"]["tokens"]
    if name == "serve-prefix-sessions":
        assert run.info["checked_attached"] >= 1


def test_serve_traced_run_reads_program_counters():
    run, res = execute("serve-prefix-sessions", serve, trace=True)
    assert res["correct"]
    read = lambda m: bench_run.load_reader(m).read(run)  # noqa: E731
    assert 0 < read("sched.prefix_hit_pct") < 100
    assert 0 < read("mfu.serve") < 100
    assert "busy_s" in res["device"] and "window_s" in res["device"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _faulty_server(kind):
    from repro.runtime.server import DecodeServer

    class Faulty(DecodeServer):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            inner = self._decode
            calls = iter(range(1 << 30))

            def step(p, c, b, pos):
                if kind == "state_unchanged":
                    keep = jax.tree.map(jnp.copy, c)
                    logits, _ = inner(p, c, b, pos)
                    return logits, keep
                logits, c2 = inner(p, c, b, pos)
                if kind == "half_batch":
                    logits = logits.at[logits.shape[0] // 2:].set(0)
                elif kind == "token_altered":
                    # one row's token per step, a different row each step
                    r = next(calls) % logits.shape[0]
                    top = jnp.argmax(logits[r])
                    logits = logits.at[r, (top + 1) % self.cfg.vocab_size
                                       ].set(jnp.max(logits[r]) + 1)
                return logits, c2
            self._decode = step
    return Faulty


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "token_altered"])
def test_serve_faults_are_not_correct(kind, monkeypatch):
    import repro.runtime.server
    monkeypatch.setattr(repro.runtime.server, "DecodeServer",
                        _faulty_server(kind))
    # at the cell's own load: every slot busy
    run, res = execute("serve-decode-heavy", serve, burst=64)
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_serve_control_reads_above_the_program():
    spec, run = make_run("serve-decode-heavy")
    run.control = True
    res = bench_run.execute(spec, run, serve)
    assert res["correct"]
    assert run.info["control_logit_gap"] > 3 * \
        res["checks"]["logit_gap"]["value"]


def test_reference_matches_the_program_forward_at_float32():
    """The plain reference and the program's forward pass agree on the same
    weights, so a gap measures the served path, not a different model."""
    from repro.models.model import LM
    m = dict(json.loads((bench_run.HERE / "configs" / "qwen3-0.6b.json"
                         ).read_text()), **TINY)
    cfg = serve.arch_config(m)
    w = serve.make_weights(m, 5, cfg.vocab_padded)
    model = LM(cfg)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, m["vocab_size"], (2, 24)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = model.forward(serve.program_params(w), {"tokens": toks})[0]
    ref = serve.load_reference(m["reference"])
    want = jnp.einsum("rsd,vd->rsv", ref.hidden(w, toks, m),
                      w["embed"][:m["vocab_size"]].astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got[..., :m["vocab_size"]]),
                               np.asarray(want), rtol=2e-4, atol=2e-4)


def test_command_refuses_without_a_tpu():
    out = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "sort-1chip",
         "--seed", "0", "--seconds", "10", "--trace", "0"],
        cwd=str(bench_run.ROOT), capture_output=True, text=True,
        timeout=300, env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr
