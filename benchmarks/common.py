"""Benchmark helpers: timing + subprocess-with-N-host-devices runner."""
from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Optional


def timeit(fn, *args, warmup: int = 1, iters: int = 3):
    """Best-of-iters wall time in microseconds (jit-compatible)."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def host_device_env(n_devices: Optional[int]) -> dict:
    """Environment for a child that emulates placeholder CPU devices
    (``n_devices`` of them, or as many as the child asks for itself).

    ``JAX_PLATFORMS=cpu`` keeps the child off any accelerator: on a TPU host
    it would otherwise take the chip from the parent and see one TPU in
    place of N host devices.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    return env


def run_with_devices(module: str, n_devices: int = 8, timeout: int = 1200,
                     args=()):
    """Run `python -m benchmarks.<module>` with N host devices; return its
    stdout.  A child that fails raises `subprocess.CalledProcessError`."""
    r = subprocess.run([sys.executable, "-m", f"benchmarks.{module}",
                        *args],
                       capture_output=True, text=True, timeout=timeout,
                       env=host_device_env(n_devices))
    if r.returncode != 0:
        print(f"# {module} FAILED:\n{r.stderr[-2000:]}", file=sys.stderr)
        raise subprocess.CalledProcessError(r.returncode, r.args, r.stdout,
                                            r.stderr)
    return r.stdout
