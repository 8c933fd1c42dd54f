"""The kernel-backend default and the persistent compile-cache location:
the two rules that decide what an entry point runs and where its compiled
programs are kept."""
import os
import subprocess
import sys

import jax
import pytest

from repro import backend as be
from repro.launch import compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- kernel backend default ---------------------------------------------------

def test_backend_resolves_lazily_to_xla_on_cpu():
    """Importing ``repro.backend`` decides nothing; the first use resolves
    the unset default from the platform JAX runs on."""
    env = {k: v for k, v in os.environ.items() if k != "REPRO_BACKEND"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c",
         "import repro.backend as be\n"
         "assert be._backend is None, be._backend\n"
         "print(be.get_backend(), be.resolve(None))"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["xla", "xla"]


def test_backend_resolves_to_pallas_on_tpu(monkeypatch):
    monkeypatch.setattr(be, "_backend", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert be.get_backend() == "pallas"
    assert be.resolve(None) == "pallas"


@pytest.mark.parametrize("how", ["env", "set_backend", "impl"])
def test_pallas_without_tpu_raises(monkeypatch, how):
    """``REPRO_BACKEND=pallas`` (or the same asked per call) on a host
    without a TPU is an error, not a silent run elsewhere."""
    assert jax.default_backend() != "tpu"
    monkeypatch.setattr(be, "_backend", "xla")
    with pytest.raises(RuntimeError, match="needs a TPU"):
        if how == "env":
            monkeypatch.setattr(be, "_backend", "pallas")
            be.get_backend()
        elif how == "set_backend":
            be.set_backend("pallas")
        else:
            be.resolve("pallas")


def test_unknown_backend_raises(monkeypatch):
    """A misspelt ``REPRO_BACKEND`` is refused on first use, not taken for
    the Pallas path."""
    monkeypatch.setattr(be, "_backend", "palas")
    with pytest.raises(ValueError, match="unknown kernel backend"):
        be.resolve(None)


def test_cpu_backends_stay_selectable(monkeypatch):
    monkeypatch.setattr(be, "_backend", None)
    for name in ("xla", "interpret"):
        be.set_backend(name)
        assert be.get_backend() == name == be.resolve(None)


# -- compile cache ------------------------------------------------------------

def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    monkeypatch.setenv(cc.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cc.compile_cache_dir() == str(tmp_path)
    assert cc.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: no other directory is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv(cc.ENV, raising=False)
    path = cc.compile_cache_dir()
    assert path == os.path.join(REPO, ".jax_cache")
    assert cc.compile_cache_dir() == path          # same on every call
    before = jax.config.jax_compilation_cache_dir
    try:
        assert cc.enable_compile_cache() == path
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
