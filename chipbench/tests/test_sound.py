"""The program itself, driven as on the chip at the small size, is
`correct` against the reference: host draws, local steps, the weighted
aggregate and the int8 uplink agree, and on four virtual CPU devices the
sharded aggregate does too."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import run as bench
from chipbench.tests.small import small_cell

REPO = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("name", ["mnist.paper_dense", "cifar.paper_int8",
                                  "mnist.cohort_100k"])
def test_program_is_correct_at_small_size(name):
    out = bench.run_cell(small_cell(name), 123, 0.05, False)
    assert out["correct"] is True, out["check"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"samples_per_s", "setup_s"}
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        assert k in out["device"]


SHARDED = """
import json, sys
sys.path[:0] = [{src!r}, {repo!r}]
import jax
assert jax.device_count() == 4, jax.devices()
from chipbench import run as bench
from chipbench.tests.small import small_cell
out = bench.run_cell(small_cell("mnist.cohort_100k_x4"), 123, 0.05, False)
print(json.dumps({{"correct": out["correct"], "check": out["check"]}}))
"""


def test_sharded_program_is_correct_on_four_virtual_devices():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    code = SHARDED.format(src=str(REPO / "src"), repo=str(REPO))
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["check"]
