"""Chaos smoke (CI chaos-smoke job): SIGKILL a checkpointing Study
mid-run and prove the resumed run is bit-identical to an uninterrupted
one.

The study has two arms with DIFFERENT scenarios, so they land in two
envelope groups that execute sequentially — the parent watches the
checkpoint directory, kills the child the moment the first group's
members hit disk, and resumes in-process. `Study.run(checkpoint_dir=...)`
members are saved atomically (tmp + fsync + rename), so whatever the
kill left behind is either absent or complete — never torn."""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.federated.experiment import ExperimentSpec
from repro.federated.study import Study

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spec(scenario):
    return ExperimentSpec(
        fed=FedConfig(n_devices=3, batch_size=4,
                      theta=float(np.exp(-2 / 2.0)), nu=2.0, lr=0.05,
                      compress_updates=False),
        model="mnist_cnn_tiny", dataset="mnist", n_train=120, n_test=40,
        seed=0, scenario=scenario, with_eval=False)


def _study():
    # different scenarios -> different group signatures -> two groups
    # that run sequentially, giving the kill a real window between them
    return Study(arms=[("plain", _spec(None)), ("dropout", _spec("dropout"))],
                 seeds=(0, 1), max_rounds=2, eval_every=2)


def _payload(res):
    return json.dumps(res.to_json(), sort_keys=True, default=float)


def test_sigkill_mid_study_then_resume_bit_identical(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    ref = _payload(_study().run())

    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    child = subprocess.Popen([sys.executable, os.path.abspath(__file__),
                              ckpt], env=env, cwd=REPO,
                             stdout=subprocess.DEVNULL,
                             stderr=subprocess.DEVNULL)
    first = os.path.join(ckpt, "arm000_seed0.pkl")
    deadline = time.time() + 600
    try:
        while not os.path.exists(first):
            assert child.poll() is None, \
                "child exited before writing its first member checkpoint"
            assert time.time() < deadline, "child never wrote a checkpoint"
            time.sleep(0.05)
    finally:
        if child.poll() is None:
            child.send_signal(signal.SIGKILL)
    assert child.wait(timeout=60) == -signal.SIGKILL

    saved = sorted(os.listdir(ckpt))
    assert "arm000_seed0.pkl" in saved
    assert len(saved) < 4, "child finished everything before the kill — " \
        "the resume below would be vacuous"

    resumed = _study().run(checkpoint_dir=ckpt)
    assert _payload(resumed) == ref
    assert sorted(os.listdir(ckpt)) == [
        "arm000_seed0.pkl", "arm000_seed1.pkl",
        "arm001_seed0.pkl", "arm001_seed1.pkl"]


if __name__ == "__main__":  # the chaos child: run until killed
    _study().run(checkpoint_dir=sys.argv[1])
