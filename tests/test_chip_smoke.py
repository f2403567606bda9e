"""Rehearsal of chip_smoke.py on the CPU: its phases at a tiny size, with
the kernels in interpret mode, pass every reference comparison they make;
and the script itself refuses to run without a TPU."""
import importlib.util
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phase_a_tiny():
    out = _smoke().phase_a(n=4096, chunk=1024, budget=2048, requests=8,
                           max_rows=32, updates=128)
    assert out["n"] == 4096 and out["d"] == 16 and out["m"] > 0


def test_phase_b_tiny():
    out = _smoke().phase_b(n=2048, queries=256)
    assert out["n"] == 2048 and out["d"] == 256 and out["m"] > 0


def test_phase_sharded_tiny():
    """The --chips 4 path on four forced host devices (fresh process)."""
    code = f"""
import sys
sys.path.insert(0, {ROOT!r})
import chip_smoke
from repro.launch.mesh import data_mesh
out = chip_smoke.phase_sharded(data_mesh(4), n=4096, chunk=1024,
                               budget=2048, queries=256)
assert out["m"] > 0
print("SHARDED_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "SHARDED_OK" in r.stdout, \
        r.stdout[-3000:] + r.stderr[-3000:]


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_refuses_without_tpu(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py"),
                        *args], env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout
