"""utils/compile_cache.py: placed from outside, else a fixed checkout
path, and never turned on by the test suite."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROBE = ("import jax\n"
         "from distributedratelimiting.redis_tpu.utils.compile_cache import "
         "enable_compile_cache\n"
         "print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)")


@pytest.mark.parametrize("case", ["outside", "default", "suite"])
def test_cache_placement(case, tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "JAX_ENABLE_COMPILATION_CACHE")}
    want = os.path.join(REPO, ".jax_cache")
    if case == "outside":
        want = str(tmp_path / "cc")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    elif case == "suite":
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    out = subprocess.run([sys.executable, "-c", PROBE], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    if case == "suite":
        assert out == ["None", "None"]
    else:
        assert out == [want, want]


def test_suite_runs_with_the_cache_off():
    import jax

    assert not jax.config.jax_enable_compilation_cache
