"""Where the entry points keep JAX's persistent compilation cache.

Each case runs in a fresh interpreter: turning the cache on is
process-wide, and tests never turn it on in the test process itself.
"""
import os
import pathlib
import subprocess
import sys

import pytest

from repro.launch.compile_cache import CACHE_DIR

REPO = pathlib.Path(__file__).resolve().parents[1]
PROBE = ("from repro.launch.compile_cache import enable_compile_cache; "
         "enable_compile_cache(); import jax; "
         "print(jax.config.jax_compilation_cache_dir)")


@pytest.mark.parametrize("placed", [None, "outside"])
def test_cache_dir(tmp_path, placed):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    want = CACHE_DIR
    if placed:
        want = tmp_path / placed
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    out = subprocess.run([sys.executable, "-c", PROBE], env=env, check=True,
                         capture_output=True, text=True, cwd=tmp_path)
    assert pathlib.Path(out.stdout.strip().splitlines()[-1]) == want
    assert CACHE_DIR == REPO / ".jax_cache"
