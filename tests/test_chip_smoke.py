"""CPU rehearsal of chip_smoke.py: its phase functions at mnist_cnn_small
size, its 4-chip comparison on 4 virtual CPU devices, and its refusal to
run (or print a result) without a TPU."""
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402
from repro.utils import compile_cache  # noqa: E402

SMALL = "mnist_cnn_small"


def _phase_a():
    out = cs.phase_main(SMALL)
    assert out["trace_count"] == 1 and out["device_data"]


def _phase_b():
    _, diff = cs.phase_reference(SMALL)
    # XLA:CPU keeps scan and the per-client loop equal to float rounding.
    assert diff["max_param_diff"] < 1e-6


def _phase_c():
    out = cs.phase_int8(SMALL)
    assert not out["tpu_custom_call"]  # interpret mode on the CPU
    assert out["max_param_diff"] == 0.0


def _phase_d():
    cs.phase_sampled(SMALL, M=10_000, K=8)


def _phase_e():
    solo, _ = cs.phase_reference(SMALL)
    out = cs.phase_engines(solo, SMALL)
    assert out["fleet"]["max_param_diff"] == 0.0
    # The event engine trains one client per event and sums deltas: equal
    # to the vmapped scan to float rounding on XLA:CPU.
    assert out["sync_limit"]["max_param_diff"] < 1e-6


@pytest.mark.parametrize("phase", [_phase_a, _phase_b, _phase_c, _phase_d,
                                   _phase_e], ids="abcde")
def test_phase_rehearses_on_cpu(phase):
    phase()


_SHARD = """
import sys
sys.path.insert(0, sys.argv[1])
import chip_smoke as cs
out = cs.phase_shard("mnist_cnn_small", M=10_000, K=8)
print("SHARD_OK", out["max_param_diff"])
"""


def test_shard_comparison_on_four_virtual_devices():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", _SHARD, REPO], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHARD_OK" in out.stdout


def _run_script(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu(tmp_path):
    """The repo is there but JAX finds no TPU: nonzero exit, no result."""
    out = _run_script(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_fails_without_the_repo(tmp_path):
    """The script copied alone into an empty directory cannot import the
    simulator: nonzero exit, no result."""
    out = _run_script(shutil.copy(os.path.join(REPO, "chip_smoke.py"),
                                  tmp_path), tmp_path)
    assert out.returncode != 0
    assert "ModuleNotFoundError" in out.stderr
    assert '"ok"' not in out.stdout


def test_compile_cache_dir_follows_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)
    monkeypatch.delenv(compile_cache.ENV)
    assert compile_cache.cache_dir() == os.path.join(REPO, ".jax_cache")
