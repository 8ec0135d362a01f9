"""The FedAvg CNN through its family module gives the very readings the
reference gave before the model became a plug-in of the harness.

`cnn_parity_seed123.json` holds the readings of the earlier reference,
which had the CNN written into chipbench/reference.py: each small cell
of `small.py` at seed 123 on the CPU, every round's loss, the accuracy
after each step, each leaf's norm (math.fsum of float64 squares, so the
sum's order is fixed) of the model before the first step and after each
step, and a sha256 over those leaves' bytes. Compared exactly."""
from __future__ import annotations

import hashlib
import json
import math
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import reference
from chipbench import run as bench
from chipbench.tests.small import small_cell

RECORDED = json.loads(
    (Path(__file__).parent / "cnn_parity_seed123.json").read_text())


def _norm(a) -> float:
    a = np.asarray(a, np.float64).reshape(-1)
    return math.sqrt(math.fsum(a * a))


@pytest.mark.parametrize("name", sorted(RECORDED["cells"]))
def test_cnn_family_reads_what_the_earlier_reference_read(name):
    seed = RECORDED["seed"]
    cell = small_cell(name, seed)
    assert cell.family.__name__.endswith("fedavg_cnn")
    data, test = bench._datasets(cell, seed, bench.data_maker(cell))
    b, V = (cell.traffic["plan_expected"][k] for k in ("b", "V"))
    got = reference.readings(
        reference.Reference(cell, seed, data, test, b, V),
        cell.check_steps, cell.eval_every)
    want = RECORDED["cells"][name]
    assert got["losses"] == want["losses"]
    assert got["acc"] == want["acc"]
    assert [[_norm(x) for x in jax.tree.leaves(p)]
            for p in got["params"]] == want["norms"]
    digest = hashlib.sha256(b"".join(
        np.ascontiguousarray(x).tobytes()
        for p in got["params"] for x in jax.tree.leaves(p))).hexdigest()
    assert digest == want["sha256"]
