"""The persistent compilation cache lands where the caller can predict."""
import os
import subprocess
import sys

import jax
import pytest

from repro.common import compile_cache
from repro.common.compile_cache import ENV_VAR, use_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLAGS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture(autouse=True)
def restore_jax_cache_config():
    """The test process must not go on caching every later compile."""
    saved = {f: getattr(jax.config, f) for f in FLAGS}
    yield
    for f, v in saved.items():
        jax.config.update(f, v)


def _in_child(env_dir=None):
    """(returned dir, JAX's configured dir) from a fresh interpreter."""
    env = {k: v for k, v in os.environ.items() if k != ENV_VAR}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env[ENV_VAR] = env_dir
    code = ("import jax\n"
            "from repro.common.compile_cache import use_compile_cache\n"
            "print(use_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split("\n")
    return out[0], out[1]


def test_env_var_dir_is_used_and_no_other_set(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the function sets no directory
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    # in a fresh process the directory in force is the variable's
    assert _in_child(str(tmp_path)) == (str(tmp_path), str(tmp_path))


def test_default_dir_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.DEFAULT_DIR == want
    assert use_compile_cache() == want
    assert use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    # the same path in two other processes: no pid, time or temp dir in it
    assert _in_child() == (want, want)
    assert _in_child() == (want, want)


def test_default_dir_is_git_ignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
