"""The benchmark's own tests run on the CPU with four virtual devices, so
that the four-chip paths rehearse without a chip:

    python -m pytest bench/tests -q
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = " ".join(
    [os.environ.get("XLA_FLAGS", ""),
     "--xla_force_host_platform_device_count=4"]).strip()

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
