import os

# tests see exactly 1 CPU device; the multi-device tests start a subprocess
# with XLA_FLAGS=--xla_force_host_platform_device_count=N of their own.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
