"""Process-level setup: where the persistent compile cache lands, how a
kernel's ``force=`` pin resolves, and that the chip smoke refuses to run
anywhere but on a TPU.

The cache and smoke cases run in subprocesses: the suite itself never turns
the persistent cache on.
"""
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from repro.kernels.dispatch import FORCES, pallas_interpret
from repro.runtime.jax_env import DEFAULT_CACHE_DIR

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _python(args, *, cwd=ROOT, env=None, drop=()):
    full = {k: v for k, v in os.environ.items() if k not in drop}
    full.update({"JAX_PLATFORMS": "cpu"}, **(env or {}))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


_SRC = {"PYTHONPATH": str(ROOT / "src")}
_ENABLE = ("from repro.runtime.jax_env import enable_compile_cache\n"
           "print(enable_compile_cache())\n")


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    cache = tmp_path / "cache"
    code = _ENABLE + (
        "import jax, jax.numpy as jnp\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(4)).block_until_ready()\n")
    out = _python(["-c", code],
                  env={**_SRC, "JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[-1] == str(cache)
    assert cache.is_dir() and any(cache.iterdir()), "nothing was cached"


def test_compile_cache_defaults_to_one_ignored_checkout_dir():
    out = _python(["-c", _ENABLE], env=_SRC,
                  drop=("JAX_COMPILATION_CACHE_DIR",))
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[-1] == str(DEFAULT_CACHE_DIR)
    assert DEFAULT_CACHE_DIR == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().split()
    assert f"{DEFAULT_CACHE_DIR.name}/" in ignored


@pytest.mark.parametrize("force", FORCES)
def test_force_pin_resolution(force):
    on_tpu = jax.default_backend() == "tpu"
    want = {"ref": None, "auto": False if on_tpu else None,
            "pallas": False, "interpret": True}[force]
    assert pallas_interpret(force) is want


def test_force_pin_rejects_unknown_values():
    with pytest.raises(ValueError, match="force"):
        pallas_interpret("tpu")


def test_chip_smoke_refuses_without_a_tpu():
    out = _python([str(ROOT / "chip_smoke.py")])
    assert out.returncode != 0
    assert "no TPU" in out.stderr, out.stderr[-2000:]
    assert '"ok"' not in out.stdout


def test_chip_smoke_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    out = _python([str(tmp_path / "chip_smoke.py")], cwd=tmp_path,
                  drop=("PYTHONPATH",))
    assert out.returncode != 0
    assert "no repro package" in out.stderr, out.stderr[-2000:]
    assert '"ok"' not in out.stdout
