"""Dry-run smoke: one (arch x shape) pair lowered + compiled on the real
16x16 production mesh, in a subprocess (XLA device-count flag must not
leak into this test process)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("arch,shape", [("qwen2-0.5b", "decode_32k")])
def test_dryrun_single_pair(tmp_path, arch, shape):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", shape, "--mesh", "single", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.load(open(tmp_path / f"{arch}--{shape}--single.json"))
    assert rec["ok"], rec.get("error")
    assert rec["flops_per_device"] > 0
    assert rec["terms_seconds"]["memory"] > 0
    assert rec["dominant"] in ("compute", "memory", "collective")
