"""What ``chip_smoke.py`` rests on, checked without a chip: the placeable
compile cache, the smoke's jax-free parent and its refusal to pass off-TPU,
and the start-up errors that replace silent CPU fallbacks."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from distributed_ml_pytorch_tpu.runtime import startup
from distributed_ml_pytorch_tpu.runtime.mesh import require_devices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CACHE_PROBE = """
import json, sys
import jax, jax.numpy as jnp
from distributed_ml_pytorch_tpu.runtime import startup
d = startup.enable_compile_cache()
if "--compile" in sys.argv:
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
print(json.dumps({"dir": d, "stats": startup.compile_cache_stats()}))
"""


def _probe_cache(env_dir, *flags):
    # the suite's conftest switches the cache off for its own process tree;
    # these children are about the cache, so they get it back
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_ENABLE_COMPILATION_CACHE", startup.CACHE_ENV)}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    if env_dir is not None:
        env[startup.CACHE_ENV] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", CACHE_PROBE, *flags], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_env_dir_is_used_and_second_run_compiles_nothing(tmp_path):
    placed = tmp_path / "placed"
    cold = _probe_cache(placed, "--compile")
    assert cold["dir"] == str(placed)
    assert cold["stats"]["requests"] > 0 and cold["stats"]["compiled"] > 0
    assert any(placed.iterdir()), "nothing was cached where the variable said"
    warm = _probe_cache(placed, "--compile")
    assert warm["stats"]["requests"] == cold["stats"]["requests"]
    assert warm["stats"]["hits"] == warm["stats"]["requests"]
    assert warm["stats"]["compiled"] == 0


def test_compile_cache_default_is_one_fixed_path_in_the_checkout():
    """Unset, every process resolves the same in-checkout directory — no pid,
    timestamp or tempfile name in it (a cache that moves never hits)."""
    first, second = _probe_cache(None), _probe_cache(None)
    assert first["dir"] == second["dir"] == os.path.join(REPO, ".jax_cache")
    assert startup.DEFAULT_CACHE_DIR == first["dir"]
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def _run_smoke(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = ("import sys, chip_smoke\n"
            "rc = chip_smoke.main([])\n"
            "print('PARENT_IMPORTED_JAX', 'jax' in sys.modules)\n"
            "sys.exit(rc)\n")
    return subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_parent_stays_off_jax_and_fails_without_a_chip(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    os.symlink(os.path.join(REPO, "distributed_ml_pytorch_tpu"),
               tmp_path / "distributed_ml_pytorch_tpu")
    out = _run_smoke(tmp_path)
    assert out.returncode != 0, out.stdout + out.stderr
    assert "PARENT_IMPORTED_JAX False" in out.stdout
    # it says why: the probe names the backend it found and the variable
    assert "default backend is 'cpu'" in out.stdout
    assert "JAX_PLATFORMS='cpu'" in out.stderr
    assert '{"ok"' not in out.stdout


def test_chip_smoke_stage_run_by_hand_refuses_the_cpu(tmp_path):
    """A stage is a child that can be started directly; it has no mode that
    runs without the chip either."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--stage", "lm",
         "--result", str(tmp_path / "lm.json")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert out.returncode != 0
    assert "chip_smoke needs a TPU" in out.stderr
    assert not (tmp_path / "lm.json").exists()


def test_chip_smoke_alone_in_a_directory_fails_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run_smoke(tmp_path)
    assert out.returncode != 0, out.stdout + out.stderr
    assert "PARENT_IMPORTED_JAX False" in out.stdout
    assert "No module named 'distributed_ml_pytorch_tpu'" in out.stdout
    assert '{"ok"' not in out.stdout


def test_backend_tpu_without_a_tpu_is_an_error(tmp_path):
    """``--backend tpu`` / ``--cuda`` used to carry on on the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    for flag in (["--backend", "tpu"], ["--cuda"]):
        out = subprocess.run(
            [sys.executable, "-m", "distributed_ml_pytorch_tpu.training.cli",
             "--no-distributed", "--synthetic-data", "--epochs", "1",
             "--log-dir", str(tmp_path)] + flag,
            env=env, capture_output=True, text=True, timeout=120)
        assert out.returncode == 2, out.stdout + out.stderr
        assert "needs a TPU" in out.stderr
        assert "Training for epoch" not in out.stdout
        assert not list(tmp_path.iterdir())  # no CSV under a TPU name


def test_require_tpu_and_require_devices_raise_on_the_cpu_mesh():
    with pytest.raises(RuntimeError, match="needs a TPU"):
        startup.require_tpu("this test")
    require_devices(8, "this test")  # the conftest's eight virtual devices
    with pytest.raises(RuntimeError, match="JAX_NUM_CPU_DEVICES=16"):
        require_devices(16, "this test")
