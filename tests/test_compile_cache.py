"""The persistent compile cache is placed from outside
(common/compile_cache.py): JAX_COMPILATION_CACHE_DIR when set, else one
fixed directory under the checkout — the path is part of the cache's key,
so it may depend on nothing that changes between two runs."""

import os
import subprocess
import sys

import jax

from byteps_tpu.common import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRINT_PATHS = (
    "import jax\n"
    "from byteps_tpu.common.compile_cache import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


def _in_fresh_process(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PRINT_PATHS], env=env,
                         capture_output=True, text=True, timeout=120,
                         cwd="/")    # not the checkout: cwd must not matter
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_env_set_wins_and_nothing_is_written(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def no_write(*a, **k):
        raise AssertionError(f"jax.config.update{a} with the env set")

    monkeypatch.setattr(jax.config, "update", no_write)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # and jax itself reads the variable: that is the directory in use
    assert _in_fresh_process(str(tmp_path)) == [str(tmp_path)] * 2


def test_env_unset_is_one_fixed_path_under_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    want = os.path.join(REPO, ".jax_cache")
    try:
        assert compile_cache.enable_compile_cache() == want
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    # two more processes, started elsewhere, agree
    assert _in_fresh_process(None) == [want] * 2
    assert _in_fresh_process(None) == [want] * 2
