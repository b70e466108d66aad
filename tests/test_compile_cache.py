"""Start-up contracts of the entry points: the persistent compile cache sits
at a stable place, and importing the package touches no JAX backend (a
process that imports it before choosing its devices must still be free to
choose them)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_env_var_wins_and_sets_nothing(monkeypatch):
    monkeypatch.setenv(compile_cache.CACHE_ENV, "/elsewhere/jax-cache")
    before = jax.config.values["jax_compilation_cache_dir"]
    assert compile_cache.enable_compile_cache() == "/elsewhere/jax-cache"
    assert jax.config.values["jax_compilation_cache_dir"] == before


def test_default_is_fixed_dir_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    before = jax.config.values["jax_compilation_cache_dir"]
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.values["jax_compilation_cache_dir"] == got
        # the same path on every call: a moving directory never hits
        assert compile_cache.enable_compile_cache() == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_outside_a_checkout_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    before = jax.config.values["jax_compilation_cache_dir"]
    assert compile_cache.enable_compile_cache(tmp_path) is None
    assert jax.config.values["jax_compilation_cache_dir"] == before
    assert not (tmp_path / ".jax_cache").exists()


_IMPORT_SCRIPT = textwrap.dedent("""
    import repro.fed, repro.fed.distributed, repro.kernels.ops
    import repro.launch.train, repro.launch.mesh, repro.sharding.specs
    from jax._src import xla_bridge
    assert not xla_bridge._backends, sorted(xla_bridge._backends)
    print("NO_BACKEND")
""")


def test_import_initialises_no_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", _IMPORT_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "NO_BACKEND" in out.stdout
