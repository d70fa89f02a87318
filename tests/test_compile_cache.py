"""Compile-cache placement policy (utils/compile_cache.py): placed from
outside through ``JAX_COMPILATION_CACHE_DIR``, else fixed at
``<checkout>/.jax_cache`` — never the cwd, never a private variable."""

import os

import jax
import pytest

from bigdl_tpu.utils import compile_cache

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore jax's cache settings after a policy test."""
    before = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_compilation_cache_include_metadata_in_key")}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_env_set_code_sets_no_directory(monkeypatch, tmp_path, cache_config):
    placed = str(tmp_path / "placed")
    monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, placed)
    seen = []
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: seen.append(k), raising=True)
    assert compile_cache.enable_persistent_cache() == placed
    assert "jax_compilation_cache_dir" not in seen


@pytest.mark.parametrize("cwd", ["checkout", "elsewhere"])
def test_env_unset_uses_checkout_whatever_the_cwd(monkeypatch, tmp_path,
                                                  cache_config, cwd):
    monkeypatch.delenv(compile_cache.ENV_CACHE_DIR, raising=False)
    # the private variable of old must mean nothing now
    monkeypatch.setenv("BIGDL_TPU_COMPILE_CACHE", str(tmp_path / "old"))
    monkeypatch.chdir(CHECKOUT if cwd == "checkout" else tmp_path)
    got = compile_cache.enable_persistent_cache()
    assert got == os.path.join(CHECKOUT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert str(tmp_path) not in got


def test_one_function_names_the_default_directory():
    """`.jax_cache` is spelled in exactly one module of the program."""
    hits = []
    for root, _, files in os.walk(CHECKOUT):
        if any(part in root for part in (
                os.sep + "tests", os.sep + ".git", os.sep + ".scratch",
                os.sep + "chiprun_out", os.sep + "build")):
            continue
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path, encoding="utf-8") as fh:
                    if '".jax_cache"' in fh.read():
                        hits.append(os.path.relpath(path, CHECKOUT))
    assert hits == [os.path.join("bigdl_tpu", "utils", "compile_cache.py")]
