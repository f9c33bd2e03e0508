"""Substrate tests: data determinism, checkpoint atomicity+elasticity,
optimizer correctness, gradient compression properties."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # offline CI image: deterministic shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.checkpoint import latest_step, restore, save
from repro.data import SyntheticLM
from repro.optim import AdamW
from repro.optim.compression import (compressed_psum, dequantize,
                                     error_feedback_update, quantize)

from helpers import build, make_batch, tiny


def test_data_deterministic_across_restarts():
    cfg = tiny("qwen3-0.6b")
    a = SyntheticLM(cfg, 4, 32, seed=7).batch(13)
    b = SyntheticLM(cfg, 4, 32, seed=7).batch(13)
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))
    c = SyntheticLM(cfg, 4, 32, seed=8).batch(13)
    assert not np.array_equal(np.asarray(a["tokens"]), np.asarray(c["tokens"]))


def test_pipeline_striped_matches_host_oracle():
    """Striped generation (each leaf born per-device via Locale.make) must
    reproduce the build-on-host-then-place oracle bit-exactly, for every
    batch family: tokens, frame embeddings, and VLM image embeddings."""
    for arch in ("qwen3-0.6b", "musicgen-medium", "llama-3.2-vision-90b"):
        cfg = tiny(arch)
        a = SyntheticLM(cfg, 4, 16, seed=11, striped=True).batch(3)
        b = SyntheticLM(cfg, 4, 16, seed=11, striped=False).batch(3)
        assert set(a) == set(b), (arch, set(a), set(b))
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                          err_msg=f"{arch}:{k}")


@pytest.mark.slow
def test_pipeline_striped_matches_host_on_mesh():
    """On a real multi-device mesh the striped batch must match the host
    oracle bit-exactly *and* land under the same chunk-contiguous sharding
    (rows born on their home device, never resharded)."""
    import subprocess
    import sys
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
from jax.sharding import AxisType
from repro.configs import get_config, reduce_config
from repro.data import SyntheticLM
for arch in ("qwen3-0.6b", "musicgen-medium", "llama-3.2-vision-90b"):
    cfg = reduce_config(get_config(arch))
    mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
    a = SyntheticLM(cfg, 8, 16, seed=5, mesh=mesh, striped=True).batch(2)
    b = SyntheticLM(cfg, 8, 16, seed=5, mesh=mesh, striped=False).batch(2)
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
        assert a[k].sharding == b[k].sharding, (k, a[k].sharding)
print("STRIPED_PIPELINE_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"},
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "STRIPED_PIPELINE_OK" in r.stdout, r.stdout + r.stderr


def test_checkpoint_roundtrip_and_gc(tmp_path):
    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"c": jnp.ones((2,), jnp.bfloat16),
                  "step": jnp.int32(7)}}
    for s in [1, 2, 3, 4, 5]:
        save(str(tmp_path), s, tree, keep_last=2)
    assert latest_step(str(tmp_path)) == 5
    assert sorted(d for d in os.listdir(tmp_path) if d.startswith("step_")) \
        == ["step_00000004", "step_00000005"]
    out = restore(str(tmp_path), 5, jax.tree.map(jnp.zeros_like, tree))
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def test_checkpoint_atomic_no_partial_visible(tmp_path):
    # a tmp.<step> dir must never be picked up by latest_step
    os.makedirs(tmp_path / "tmp.9")
    assert latest_step(str(tmp_path)) is None


def test_adamw_matches_numpy_reference():
    opt = AdamW(lr=0.1, b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.0,
                clip_norm=0.0)
    p = {"w": jnp.asarray([1.0, -2.0, 3.0])}
    g = {"w": jnp.asarray([0.1, 0.2, -0.3])}
    st_ = opt.init(p)
    p1, st1, _ = opt.update(g, st_, p)
    m = 0.1 * np.asarray(g["w"])
    v = 0.01 * np.asarray(g["w"]) ** 2
    upd = (m / (1 - 0.9)) / (np.sqrt(v / (1 - 0.99)) + 1e-8)
    np.testing.assert_allclose(np.asarray(p1["w"]),
                               np.asarray(p["w"]) - 0.1 * upd, rtol=1e-5)


@given(st.integers(0, 2**31 - 1), st.sampled_from([32, 100, 257]))
@settings(max_examples=25, deadline=None)
def test_quantize_roundtrip_bounded_error(seed, n):
    x = jax.random.normal(jax.random.key(seed), (n,)) * 10
    q, s = quantize(x, block=64)
    y = dequantize(q, s, x.shape)
    err = np.abs(np.asarray(x) - np.asarray(y))
    bound = np.repeat(np.asarray(s), 64)[:n] * 0.5 + 1e-6
    assert (err <= bound).all()


def test_error_feedback_removes_bias():
    """Constant grad + EF: accumulated dequantised sum converges to true sum."""
    g = jnp.full((64,), 0.0123, jnp.float32)
    ef = jnp.zeros_like(g)
    total = jnp.zeros_like(g)
    for _ in range(50):
        deq, ef = error_feedback_update(g, ef, block=64)
        total = total + deq
    np.testing.assert_allclose(np.asarray(total), 50 * 0.0123,
                               rtol=5e-3)


@pytest.mark.slow
def test_compressed_psum_multidevice():
    import subprocess, sys
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from functools import partial
from repro.optim.compression import compressed_psum
from jax.sharding import AxisType
mesh = jax.make_mesh((4,), ("d",), axis_types=(AxisType.Auto,))
x = jax.random.normal(jax.random.key(0), (4, 256)) * 3
f = jax.jit(jax.shard_map(partial(compressed_psum, axis_name="d"),
    mesh=mesh, in_specs=P("d"), out_specs=P(None), check_vma=False))
out = np.asarray(f(x))[0]
expect = np.asarray(x).sum(0)
err = np.abs(out - expect).max()
assert err < 0.25, err  # <= n_shards * max|x|/254 analytic bound
print("PSUM_OK", err)
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": "src"},
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=300)
    assert "PSUM_OK" in r.stdout, r.stdout + r.stderr
