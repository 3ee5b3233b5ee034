"""Launch plumbing: the local-device mesh, the compile-cache rule and
chip_smoke.py's refusal to run without a TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import compile_cache_dir
from repro.launch.mesh import make_local_mesh

ROOT = Path(__file__).resolve().parents[1]


def _cpu_env(**extra):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"), **extra)
    return env


def test_local_mesh_one_device():
    mesh = make_local_mesh()
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert list(mesh.devices.flat) == jax.devices()[:1]
    with pytest.raises(ValueError, match="must divide"):
        make_local_mesh(2)


_FOUR = """
from repro.launch.mesh import make_local_mesh
for tp in (1, 2, 4):
    m = make_local_mesh(tp)
    ids = sorted(d.id for d in m.devices.flat)
    print(tp, m.shape["data"], m.shape["model"], ids)
try:
    make_local_mesh(3)
except ValueError:
    print("tp3 refused")
"""


def test_local_mesh_four_virtual_devices():
    out = subprocess.run(
        [sys.executable, "-c", _FOUR], capture_output=True, text=True,
        timeout=300, env=_cpu_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split("\n")[:4] == [
        "1 4 1 [0, 1, 2, 3]", "2 2 2 [0, 1, 2, 3]",
        "4 1 4 [0, 1, 2, 3]", "tp3 refused"]


def test_compile_cache_dir_follows_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)


def test_compile_cache_dir_defaults_into_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache_dir() == str(ROOT / ".jax_cache")
    # the same path on every call: the cache key includes it
    assert compile_cache_dir() == compile_cache_dir()


def test_chip_smoke_refuses_the_cpu():
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], capture_output=True,
        text=True, timeout=300, env=_cpu_env(), cwd=ROOT)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "platform=cpu" in out.stdout


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = _cpu_env()
    env.pop("PYTHONPATH")
    out = subprocess.run(
        [sys.executable, str(tmp_path / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=tmp_path)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "No module named 'repro'" in out.stderr
