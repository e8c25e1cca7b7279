"""`chip_smoke.py` rehearsed on the CPU at smoke size.

The script itself refuses to run without a TPU; these tests drive its
phases directly with the Pallas kernels in interpret mode (the same
kernel logic the chip compiles), its `--four-chips` path on four virtual
CPU devices, and check both refusals: no TPU, and no repository beside
the script.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


def test_phases_pass_with_interpreted_kernels(capsys):
    failed = chip_smoke.run(chip_smoke.SMOKE_SIZES,
                            kernel_backend="pallas-interpret",
                            expect="pallas-interpret")
    out = capsys.readouterr().out
    assert failed == [], out
    for phase in ("serve-dense", "serve-packed", "serve-paged", "train"):
        assert f"[{phase}] passed" in out
    assert "greedy tokens matching 24/24" in out
    assert '"paged_decode_attn": {"pallas-interpret"' in out


def test_four_chip_path_on_virtual_devices():
    code = ("import sys, chip_smoke as c; c._import_repro(); "
            "sys.exit(len(c.run(c.SMOKE_SIZES, four_chips=True)))")
    res = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert res.returncode == 0, res.stdout + res.stderr
    assert "greedy tokens tp=4 == tp=1: True" in res.stdout


def test_refuses_without_a_tpu():
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                         capture_output=True, text=True, timeout=300,
                         env=_env())
    assert res.returncode != 0
    assert "no TPU" in res.stderr and "cpu" in res.stderr
    assert '"ok"' not in res.stdout


def test_refuses_outside_the_repository(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=300,
                         env=_env(PYTHONPATH=""))
    assert res.returncode != 0
    assert "src/repro" in res.stderr
    assert '"ok"' not in res.stdout


@pytest.mark.parametrize("env_dir", [True, False], ids=["env", "repo"])
def test_compile_cache_placement(tmp_path, env_dir):
    """With JAX_COMPILATION_CACHE_DIR set the cache is written there and
    nowhere else; without it, the directory is <repo>/.jax_cache."""
    code = (
        "import json, jax, jax.numpy as jnp\n"
        "from repro.launch import compile_cache\n"
        "path = compile_cache.enable()\n"
        f"if {env_dir}:\n"
        "    jax.config.update('jax_persistent_cache_min_compile_time_secs',"
        " 0)\n"
        "    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()"
        "\n"
        "print(json.dumps({'path': path,"
        " 'config': jax.config.jax_compilation_cache_dir}))\n")
    extra = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if env_dir else {}
    env = _env(**extra)
    if not env_dir:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    res = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert res.returncode == 0, res.stderr
    got = json.loads(res.stdout.strip().splitlines()[-1])
    want = str(tmp_path) if env_dir else os.path.join(REPO, ".jax_cache")
    assert got["path"] == got["config"] == want
    if env_dir:
        assert os.listdir(tmp_path), "nothing was written to the cache dir"
