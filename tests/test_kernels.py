"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # offline CI image: deterministic shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
ATTN_CASES = [
    # (B, H, KV, Sq, Skv, hd, causal, window, bq, bk)
    (1, 2, 2, 128, 128, 32, True, 0, 64, 64),
    (2, 4, 2, 128, 128, 64, True, 0, 64, 64),      # GQA
    (1, 2, 1, 96, 96, 32, True, 0, 64, 64),        # ragged tail + MQA
    (1, 2, 2, 128, 128, 32, True, 48, 64, 64),     # sliding window
    (2, 2, 2, 64, 192, 32, False, 0, 64, 64),      # cross (no mask), Sq != Skv
    (1, 8, 8, 256, 256, 16, True, 0, 128, 128),
]
# fast lane keeps the plain-causal case; the full sweep runs in tier-1
ATTN_PARAMS = [pytest.param(c, marks=() if i < 1 else (pytest.mark.slow,))
               for i, c in enumerate(ATTN_CASES)]


@pytest.mark.parametrize("case", ATTN_PARAMS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_attention_vs_ref(case, dtype):
    B, H, KV, Sq, Skv, hd, causal, window, bq, bk = case
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, hd), dtype)
    k = jax.random.normal(ks[1], (B, KV, Skv, hd), dtype)
    v = jax.random.normal(ks[2], (B, KV, Skv, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=bq, block_k=bk)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.slow
def test_flash_attention_block_skipping_matches_dense_window():
    """SWA with many fully-skipped KV tiles still matches the oracle."""
    q = jax.random.normal(jax.random.key(1), (1, 2, 512, 32))
    k = jax.random.normal(jax.random.key(2), (1, 2, 512, 32))
    v = jax.random.normal(jax.random.key(3), (1, 2, 512, 32))
    out = ops.flash_attention(q, k, v, causal=True, window=64,
                              block_q=64, block_k=64)
    expect = ref.attention_ref(q, k, v, causal=True, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# bitonic local sort on power-of-two rows
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunks,L", [(1, 64), (4, 128), (8, 256),
                                      pytest.param(2, 1024,
                                                   marks=pytest.mark.slow)])
@pytest.mark.parametrize("dtype", ["int32", "float32"])
def test_bitonic_sort_vs_ref(chunks, L, dtype):
    if dtype == "int32":
        x = jax.random.randint(jax.random.key(0), (chunks, L), -10**6, 10**6,
                               dtype=jnp.int32)
    else:
        x = jax.random.normal(jax.random.key(0), (chunks, L), jnp.float32)
    out = ops.local_sort(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref.sort_ref(x)))


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_chunked_sort_property(seed):
    x = jax.random.randint(jax.random.key(seed), (8, 128), -2**30, 2**30,
                           dtype=jnp.int32)
    out = np.asarray(ops.chunked_sort(x))
    np.testing.assert_array_equal(out, np.sort(np.asarray(x).reshape(-1)))


# ---------------------------------------------------------------------------
# localised copy (Fig-1 kernel)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunks,L,reps", [(4, 256, 1), (8, 512, 16),
                                           pytest.param(2, 1024, 64,
                                                        marks=pytest.mark.slow)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_localised_copy_vs_ref(chunks, L, reps, dtype):
    x = jax.random.normal(jax.random.key(0), (chunks, L), dtype)
    out = ops.localised_copy(x, reps)
    expect = ref.localised_copy_ref(x, reps)
    tol = 3e-2 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)
