"""Sharded == single-device numerics: the strongest sharding-spec test.

Runs a tiny model's train step on a real (2 data x 2 model) host mesh with
the full production plan (TP + SP + constraints + KV-expand path) and
asserts the loss matches the unsharded run.
"""
import os
import subprocess
import sys

import pytest

CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from functools import partial
from repro.configs import get_config, reduce_config
from repro.configs.base import ShapeSpec
from repro.sharding.partition import (make_plan, param_specs, batch_specs,
                                      full_opt_specs, NULL_PLAN)
from repro.models.model import LM
from repro.models.steps import make_train_step, init_opt_state, make_loss_fn
from repro.optim import AdamW

for name in ["qwen3-0.6b", "mixtral-8x7b", "mamba2-2.7b", "jamba-1.5-large-398b"]:
    base = reduce_config(get_config(name))
    # heads=4/kv=2 on a 2-way model axis exercises TP + the GQA paths
    cfg = base.replace(parallel=base.parallel.__class__(
        fsdp=True, sequence_shard=True, remat=True, microbatches=2))
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    shape = ShapeSpec("t", 32, 4, "train")
    plan = make_plan(mesh, cfg, shape)
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    opt = AdamW(lr=1e-3)
    batch = {"targets": jax.random.randint(jax.random.key(2), (4, 32), 0,
                                           cfg.vocab_size)}
    if cfg.embed_input:
        batch["tokens"] = jax.random.randint(jax.random.key(1), (4, 32), 0,
                                             cfg.vocab_size)
    else:
        batch["embeds"] = 0.1*jax.random.normal(jax.random.key(1), (4, 32, cfg.d_model))
    if cfg.family == "vlm":
        batch["image_embeds"] = 0.1*jax.random.normal(
            jax.random.key(3), (4, cfg.num_image_tokens, cfg.d_model))

    # single-device reference
    loss_ref = make_loss_fn(model, cfg, NULL_PLAN)(params, batch)[1]

    nm = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda s: isinstance(s, P))
    ostate = init_opt_state(cfg, opt, params)
    step = jax.jit(make_train_step(model, cfg, plan, opt),
                   in_shardings=(nm(param_specs(params, plan, cfg)),
                                 nm(full_opt_specs(ostate, params, plan, cfg)),
                                 nm(batch_specs(batch, plan))))
    _, _, m = step(params, ostate, batch)
    np.testing.assert_allclose(float(m["loss"]), float(loss_ref),
                               rtol=2e-4, atol=2e-4)
    print(f"EQ_OK {name} sharded={float(m['loss']):.5f} ref={float(loss_ref):.5f}")
print("ALL_EQ_OK")
"""


@pytest.mark.slow
def test_sharded_train_matches_single_device():
    r = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": "src"},
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))), timeout=900)
    assert "ALL_EQ_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
