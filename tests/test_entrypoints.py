"""Entry-point plumbing: the compile-cache placement helper and the chip
smoke's refusal to run without a TPU."""
import importlib.util

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_compile_cache_env_var_wins(monkeypatch, tmp_path, restore_cache_dir):
    # JAX reads JAX_COMPILATION_CACHE_DIR into its config at import; the
    # helper must leave that value alone and set no other directory
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_defaults_to_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(compile_cache.REPO_ROOT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    ignored = (compile_cache.REPO_ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_chip_smoke_refuses_without_tpu(capsys):
    # the tests run with JAX_PLATFORMS=cpu: no TPU, so no ok line
    path = compile_cache.REPO_ROOT / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out and "no TPU" in err
