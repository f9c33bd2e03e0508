"""The main path's Pallas kernels compile for a TPU v5e at the sizes the chip
runs them (`chip_smoke.py`), from this CPU-only process.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library, and pytest-xdist workers
import every test file.  Nothing here runs a kernel; a compile that passes
is not a chip run.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

import repro.kernels.local_sort
import repro.kernels.merge_split
from repro.analysis.homecheck import check_artifacts
from repro.analysis.vmem import pallas_footprints
from repro.core import Locale
from repro.kernels.flash_attention import flash_attention
from repro.kernels.local_sort import local_sort, max_chunk
from repro.kernels.merge_split import merge_split

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler library in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile_hlo(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_local_sort_compiles_at_fused_chunk(one_chip):
    n = max_chunk()               # the one-chip fused sort of chip_smoke.py
    assert n >= 1 << 20
    x = jax.ShapeDtypeStruct((1, n), jnp.int32, sharding=one_chip)
    hlo = _compile_hlo(lambda v: local_sort(v, interpret=False), x)
    assert "tpu_custom_call" in hlo


def test_merge_split_compiles_at_four_chip_chunk(one_chip):
    C = chip_smoke.KEYS_PER_CHIP_4
    a = jax.ShapeDtypeStruct((1, C), jnp.int32, sharding=one_chip)
    keep = jax.ShapeDtypeStruct((1,), jnp.bool_, sharding=one_chip)
    hlo = _compile_hlo(lambda u, v, k: merge_split(u, v, k, interpret=False),
                       a, a, keep)
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles(one_chip):
    q = jax.ShapeDtypeStruct((1, 16, 512, 128), jnp.bfloat16,
                             sharding=one_chip)
    hlo = _compile_hlo(lambda a, b, c: flash_attention(a, b, c,
                                                       interpret=False),
                       q, q, q)
    assert "tpu_custom_call" in hlo


def test_engine_sort_past_vmem_compiles_at_100m_keys(one_chip, monkeypatch):
    """The paper's Fig. 2 sort, 100M int32 keys on one chip, through the
    engine: run formation and the run merge compile, every pallas_call fits
    VMEM (rule R3) and the program fits HBM (rule R10)."""
    for mod in (repro.kernels.local_sort, repro.kernels.merge_split):
        monkeypatch.setattr(mod, "resolve_interpret", lambda i=None: False)
    mesh = Mesh(np.array(list(one_chip.device_set)), ("data",),
                axis_types=(AxisType.Auto,))
    fn = Locale(mesh=mesh).workload("sort", backend="shard_map",
                                    local_phase="pallas")
    x = jax.ShapeDtypeStruct((100_000_000,), jnp.int32,
                             sharding=NamedSharding(mesh, PartitionSpec("data")))
    compiled = fn.lower(x).compile()
    jaxpr = jax.make_jaxpr(fn.__wrapped__)(x)
    # 12 runs of 2^23 keys in a network of 16: 10 substages
    assert [f.name for f in pallas_footprints(jaxpr)] == \
        ["local_sort"] + ["run_merge"] * 10
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 2
    rep = check_artifacts("sort-100m", hlo, jaxpr=jaxpr, rules=["R3", "R10"],
                          memory_stats=compiled.memory_analysis())
    assert rep.clean, rep.format()
