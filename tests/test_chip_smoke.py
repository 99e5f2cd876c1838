"""``chip_smoke.py`` refuses to run anywhere but a TPU, and the compilation
cache lands where ``launch/compile_cache.py`` says.

Each case runs a child Python pinned to the CPU: a child never claims a
chip.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def _run(args, cwd, env=None, timeout=300):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=timeout)


def _printed_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "ok" in json.loads(line):
                return True
        except (ValueError, TypeError):
            pass
    return False


@pytest.mark.parametrize("args", [[], ["--four-chips"]])
def test_chip_smoke_refuses_cpu(args):
    r = _run(["chip_smoke.py", *args], cwd=ROOT)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and "cpu" in r.stderr
    assert not _printed_result(r.stdout)


def test_chip_smoke_refuses_without_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = _run(["chip_smoke.py"], cwd=str(tmp_path))
    assert r.returncode != 0
    assert "no repro package" in r.stderr
    assert not _printed_result(r.stdout)


_CACHE_CODE = """
import sys
sys.path.insert(0, sys.argv[1])
from repro.launch.compile_cache import enable_compilation_cache
import jax, jax.numpy as jnp
print(enable_compilation_cache())
print(jax.config.jax_compilation_cache_dir)
if len(sys.argv) > 2:
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones((7,))).block_until_ready()
"""


def test_compile_cache_from_env(tmp_path):
    cache = tmp_path / "cc"
    r = _run(["-c", _CACHE_CODE, os.path.join(ROOT, "src"), "compile"],
             cwd=str(tmp_path), env={"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(cache), str(cache)]
    assert any(cache.iterdir()), "no compiled entry landed in the cache"


def test_compile_cache_default_is_the_checkout():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    r = subprocess.run([sys.executable, "-c", _CACHE_CODE,
                        os.path.join(ROOT, "src")], cwd=ROOT,
                       env=dict(env, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    want = os.path.join(os.path.realpath(ROOT), ".jax_cache")
    assert r.stdout.split() == [want, want]
