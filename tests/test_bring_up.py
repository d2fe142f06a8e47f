"""What keeps a run honest about its device: the per-backend Pallas
``interpret`` flag, the compile-cache location, the meshes' axis types, and
``chip_smoke.py`` refusing to report a result without a TPU."""
import os
import shutil
import subprocess
import sys

import jax
import pytest
from jax.sharding import AxisType

from repro.kernels import padding
from repro.launch import runtime
from repro.launch.mesh import make_host_mesh, make_host_mesh_2d

from proptest import REPO_ROOT


@pytest.mark.parametrize("backend,want", [("tpu", False), ("cpu", True),
                                          ("gpu", None), ("metal", None)])
def test_interpret_resolves_by_backend(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match=backend):
            padding.resolve_interpret()
        with pytest.raises(RuntimeError):
            padding.PadPlan.make(8, 32)
    else:
        assert padding.resolve_interpret() is want
        assert padding.PadPlan.make(8, 32).interpret is want
    # an explicit flag is never second-guessed
    assert padding.resolve_interpret(False) is False
    assert padding.PadPlan.make(8, 32, interpret=True).interpret is True


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_env(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch,
                                                       cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.enable_compile_cache()
    assert path == os.path.join(REPO_ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert runtime.enable_compile_cache() == path  # same path every call
    with open(os.path.join(REPO_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_device_report_names_backend_and_flag():
    rep = runtime.device_report()
    dev = jax.devices()[0]
    assert rep == {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "interpret": padding.resolve_interpret()}


def test_meshes_have_auto_axes():
    for mesh in (make_host_mesh(), make_host_mesh_2d(1, 1)):
        assert all(t == AxisType.Auto for t in mesh.axis_types)
    # jax.make_mesh's own default is Explicit: the launchers must not use it
    assert jax.make_mesh((1,), ("data",)).axis_types == (AxisType.Explicit,)


def _run_smoke(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_fails_without_a_tpu():
    r = _run_smoke(REPO_ROOT)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_chip_smoke_fails_outside_the_repository(tmp_path):
    shutil.copy(os.path.join(REPO_ROOT, "chip_smoke.py"), tmp_path)
    r = _run_smoke(tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
