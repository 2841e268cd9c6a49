"""The chip path refuses to run anywhere but a TPU: ``chip_smoke.py`` exits
non-zero naming the platform it found, and the Pallas impl cannot be
selected off a TPU (it would otherwise fall back or fail obscurely)."""
import json
import os
import subprocess
import sys

import pytest

from repro.kernels import config as kcfg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_without_tpu():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode != 0
    assert "'cpu'" in r.stderr, r.stderr
    for line in r.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_pallas_impl_refused_off_tpu():
    assert kcfg.default_impl() == "xla"
    assert kcfg.get_impl() == "xla"
    with pytest.raises(RuntimeError, match="'cpu'"):
        kcfg.set_impl("pallas")
    with pytest.raises(RuntimeError, match="'cpu'"):
        with kcfg.use_impl("pallas"):
            pass
    assert kcfg.get_impl() == "xla"  # a refused selection changes nothing


def test_use_impl_restores_backend_default():
    with kcfg.use_impl("pallas_interpret"):
        assert kcfg.get_impl() == "pallas_interpret"
        with kcfg.use_impl("xla"):
            assert kcfg.get_impl() == "xla"
        assert kcfg.get_impl() == "pallas_interpret"
    assert kcfg.get_impl() == kcfg.default_impl()
    with pytest.raises(ValueError, match="unknown kernel impl"):
        kcfg.set_impl("cuda")


_CACHE_SCRIPT = r"""
import sys
import jax
from repro.launch.compile_cache import REPO_ROOT, enable_compile_cache
path = enable_compile_cache(several_devices=sys.argv[1] == "several")
print(path, jax.config.jax_compilation_cache_dir,
      jax.config.jax_enable_compilation_cache)
"""


@pytest.mark.parametrize(
    "env_dir,devices",
    [(None, "one"), ("given", "one"), ("given", "several")],
)
def test_compile_cache_place(tmp_path, env_dir, devices):
    """The cache sits at ``$JAX_COMPILATION_CACHE_DIR`` when set (no code
    sets another), else at ``.jax_cache/`` in the checkout; a process whose
    programs span several devices keeps it off."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_SCRIPT, devices], env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    path, config_dir, enabled = r.stdout.split()
    if devices == "several":
        assert (path, enabled) == ("None", "False")
        return
    want = (
        str(tmp_path / env_dir) if env_dir
        else os.path.join(REPO, ".jax_cache")
    )
    assert path == config_dir == want and enabled == "True"
