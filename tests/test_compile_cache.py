"""The compile-cache rule (utils/compile_cache.py): placed from outside when
JAX_COMPILATION_CACHE_DIR is set, else one fixed path under the checkout."""

import os
import subprocess
import sys

import jax

from apex_example_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_set_code_sets_no_path(monkeypatch):
    """With the variable set, JAX reads it itself; the helper must not
    override whoever placed the cache from outside."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/x")
    assert compile_cache.enable_compile_cache() == "/x"
    assert jax.config.jax_compilation_cache_dir == before


def test_env_unset_fixed_path_under_checkout(monkeypatch):
    # (Leaves the config at the checkout path: what every entry point
    # sets anyway, so nothing to restore.)
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want     # idempotent
    assert jax.config.jax_compilation_cache_dir == want


def test_same_path_in_two_processes():
    """The path is part of the cache's lookup: two processes started from
    the same checkout (different pids, different cwd) must agree on it."""
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env["PYTHONPATH"] = REPO
    code = ("import os, jax\n"
            "from apex_example_tpu.utils.compile_cache import "
            "enable_compile_cache\n"
            "print(os.getpid(), enable_compile_cache(), "
            "jax.config.jax_compilation_cache_dir)")
    procs = [subprocess.Popen([sys.executable, "-c", code], env=env, cwd=cwd,
                              stdout=subprocess.PIPE, text=True)
             for cwd in (REPO, "/")]
    outs = [p.communicate(timeout=120)[0].split() for p in procs]
    assert all(p.returncode == 0 for p in procs)
    (pid_a, ret_a, cfg_a), (pid_b, ret_b, cfg_b) = outs
    assert pid_a != pid_b
    assert ret_a == ret_b == cfg_a == cfg_b == os.path.join(REPO,
                                                            ".jax_cache")
