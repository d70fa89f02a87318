"""Packaging smoke tests: the wheel builds, contains the native library and
console scripts, and the installed package imports and runs a forward pass.

≙ the reference's dist artifact + pip package (ref: make-dist.sh:1,
pyspark/setup.py:1): `pip install bigdl-tpu` must give a working framework.
Build runs with --no-build-isolation (zero-egress image) and --no-deps.
"""

import os
import subprocess
import sys
import zipfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env(**extra):
    # child processes of a CPU suite: a clean path and the CPU backend
    env = dict(os.environ, PYTHONPATH="", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


@pytest.mark.slow
def test_wheel_builds_installs_and_runs(tmp_path):
    wheel_dir = tmp_path / "wheels"
    proc = subprocess.run(
        [sys.executable, "-m", "pip", "wheel", "--no-deps",
         "--no-build-isolation", "--wheel-dir", str(wheel_dir), REPO],
        env=_clean_env(), capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    wheels = list(wheel_dir.glob("bigdl_tpu-*.whl"))
    assert len(wheels) == 1, list(wheel_dir.iterdir())
    wheel = wheels[0]

    # Wheel contents: native lib + console-script metadata.
    with zipfile.ZipFile(wheel) as zf:
        names = zf.namelist()
        assert "bigdl_tpu/native/libbigdl_native.so" in names
        entry = next(n for n in names if n.endswith("entry_points.txt"))
        eps = zf.read(entry).decode()
    for script in ("bigdl-tpu-convert", "bigdl-tpu-perf", "bigdl-tpu-sweep"):
        assert script in eps, eps

    # Install into a target dir and run a real forward pass from there.
    site = tmp_path / "site"
    proc = subprocess.run(
        [sys.executable, "-m", "pip", "install", "--no-deps", "--target",
         str(site), str(wheel)],
        env=_clean_env(), capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]

    check = (
        "import jax, jax.numpy as jnp;"
        "from bigdl_tpu.models.lenet import LeNet5;"
        "from bigdl_tpu.native import masked_crc32c;"
        "m = LeNet5(10);"
        "out = m.forward(jnp.zeros((2, 1, 28, 28)));"
        "assert out.shape == (2, 10), out.shape;"
        "assert masked_crc32c(b'bigdl') is not None;"
        "print('PKG_OK')"
    )
    proc = subprocess.run(
        [sys.executable, "-c", check],
        env=_clean_env(PYTHONPATH=str(site)), capture_output=True, text=True,
        timeout=300, cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "PKG_OK" in proc.stdout
