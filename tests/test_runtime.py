"""Entry-point set-up: the persistent compilation cache lands in one
fixed directory, the device is reported as JAX sees it, and the chip
smoke test refuses the CPU.

Each cache case runs in a subprocess: the cache directory is process
state that JAX fixes at the first compilation.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.utils.runtime import CHECKOUT_CACHE_DIR, device_info

REPO = Path(__file__).resolve().parents[1]

CACHE_PROBE = """
import json, jax, jax.numpy as jnp
from repro.utils.runtime import enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
path = enable_compile_cache()
if COMPILE:
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
print(json.dumps({"path": path,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _probe(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = CACHE_PROBE.replace("COMPILE", str(compile_))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_lands_in_env_dir(tmp_path):
    cache = tmp_path / "jax-cache"
    got = _probe(cache, compile_=True)
    assert got == {"path": str(cache), "config": str(cache)}
    assert any(cache.iterdir())  # the compiled program was written there


def test_compile_cache_defaults_to_checkout_dir():
    got = _probe(None, compile_=False)
    assert CHECKOUT_CACHE_DIR == REPO / ".jax_cache"
    assert got == {"path": str(CHECKOUT_CACHE_DIR),
                   "config": str(CHECKOUT_CACHE_DIR)}


def test_device_info():
    assert device_info() == {"platform": "cpu", "kind": "cpu", "count": 1}


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         env=env, cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_importing_launchers_leaves_devices_alone():
    """Importing the launchers neither sets XLA_FLAGS nor starts JAX's
    backend, so the importer still chooses its own device count."""
    code = """
import os
import repro.launch.dryrun, repro.launch.sweep, repro.launch.train
import repro.launch.serve
assert "XLA_FLAGS" not in os.environ, os.environ["XLA_FLAGS"]
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=3"
import jax
print(len(jax.devices()))
"""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "3"
