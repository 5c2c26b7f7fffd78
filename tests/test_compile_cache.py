"""The persistent-compilation-cache rule, and chip_smoke.py's refusal to
run without a TPU. Each case runs in a fresh interpreter: the cache
directory is process-wide JAX state."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

PROBE = """
import json, sys
import jax
before = jax.config.jax_compilation_cache_dir
import repro.launch.compile_cache, repro.launch.train, repro.launch.serve
at_import = jax.config.jax_compilation_cache_dir
from repro.launch.compile_cache import enable_compile_cache
returned = enable_compile_cache()
print(json.dumps({"before": before, "at_import": at_import,
                  "returned": returned,
                  "after": jax.config.jax_compilation_cache_dir}))
"""


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), env.get("PYTHONPATH", "")]))
    env.update(extra)
    return env


def _probe(**extra):
    out = subprocess.run([sys.executable, "-c", PROBE], env=_env(**extra),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_default_is_fixed_checkout_path_and_nothing_at_import():
    r = _probe()
    assert r["before"] is None and r["at_import"] is None
    assert r["returned"] == r["after"] == str(ROOT / ".jax_cache")


def test_env_var_is_honoured_and_nothing_else_set(tmp_path):
    cache = str(tmp_path / "jax-cache")
    r = _probe(JAX_COMPILATION_CACHE_DIR=cache)
    # JAX reads the variable itself; the function sets no other directory
    assert r["before"] == r["at_import"] == r["after"] == cache
    assert r["returned"] == cache


def test_cache_dir_is_gitignored():
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


@pytest.mark.parametrize("chips", [[], ["--chips", "4"]])
def test_chip_smoke_refuses_without_tpu(chips):
    """Under JAX_PLATFORMS=cpu the smoke exits non-zero before any phase
    and prints no result line."""
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"),
                          *chips], env=_env(), capture_output=True,
                         text=True, timeout=120, cwd=str(ROOT))
    assert out.returncode != 0
    assert "== phase" not in out.stdout
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr
