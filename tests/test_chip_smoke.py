"""`chip_smoke.py` rehearsed on the CPU: every phase function at a tiny size
(the reduced config, 2^10 keys), the four-chip phases on placeholder host
devices, and the refusals — no TPU, no checkout around the script."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def test_one_chip_phases_tiny(capsys):
    chip_smoke.one_chip(fused_keys=1 << 10, reference_keys=1 << 10,
                        reduced=True)
    lines = capsys.readouterr().out.splitlines()
    assert [ln.split()[1] for ln in lines] == ["sort-fused", "sort-padded",
                                               "sort-reference", "serve"]
    serve = json.loads(lines[-1].split(" ", 2)[2])
    assert serve["requests"] == chip_smoke.SERVE_REQUESTS
    assert len(serve["parity"]) == chip_smoke.PARITY_REQUESTS
    for p in serve["parity"]:                 # float32: exact greedy tokens
        assert p["rel_rms"] <= chip_smoke.LOGIT_RTOL
        assert p["tokens_exact"] == p["tokens"]


def test_sort_phase_raises_on_wrong_placement():
    from repro.core import Locale
    with pytest.raises(chip_smoke.CheckFailed, match="devices"):
        chip_smoke.sort_phase("t", Locale.auto(chip_smoke.case_policy(8)),
                              1 << 10, expect_devices=4)


FOUR = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, "src")
import chip_smoke
chip_smoke.four_chips(4, keys_per_chip=1 << 8, reduced=True)
print("FOUR_OK")
"""


def test_four_chip_phases_on_host_devices():
    r = subprocess.run([sys.executable, "-c", FOUR], capture_output=True,
                       text=True, cwd=ROOT, timeout=600,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert "FOUR_OK" in r.stdout, r.stdout + r.stderr
    names = [ln.split()[1] for ln in r.stdout.splitlines()
             if ln.startswith("phase ")]
    assert names == ["sort-case8-4chip", "sort-case7-4chip", "sort-hier-2x2",
                     "serve-4homes"]


def _no_result(r):
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_refuses_without_tpu():
    r = subprocess.run([sys.executable, "chip_smoke.py"], capture_output=True,
                       text=True, cwd=ROOT, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    _no_result(r)
    assert "no TPU" in r.stderr


def test_refuses_outside_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], capture_output=True,
                       text=True, cwd=tmp_path, timeout=120,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    _no_result(r)
    assert "not found" in r.stderr
