"""The compile-cache helper: where the persistent cache goes, and that
importing the library sets nothing."""

import os

import jax
import pytest

from repro import runtime


@pytest.fixture
def cache_config():
    """Restore the cache directory the process had (no compile runs while
    a test holds a different one)."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_defaults_to_the_repo(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = runtime.use_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_cache_dir_from_the_environment_wins(cache_config, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    before = jax.config.jax_compilation_cache_dir
    assert runtime.use_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before


@pytest.fixture(scope="module")
def fresh_import():
    """Import the main-path entry modules in a fresh interpreter; report
    the cache directory it ends with and the ``repro.launch`` modules it
    loaded."""
    import json
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(runtime.__file__))
    code = ("import json, sys, jax, repro.core.gp, repro.core.scenarios, "
            "repro.core.distributed, repro.serve.online; "
            "print(json.dumps([jax.config.jax_compilation_cache_dir, "
            "[m for m in sys.modules if m.startswith('repro.launch')]]))")
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_importing_repro_sets_no_cache_dir(fresh_import):
    assert fresh_import[0] is None


def test_main_path_does_not_import_launch(fresh_import):
    """``repro.launch`` pins the CPU platform when imported; no entry
    module of the solver may pull it in."""
    assert fresh_import[1] == []
