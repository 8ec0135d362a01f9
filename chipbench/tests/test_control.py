"""`correct` has to come out false for the control and for every planted
fault a cell can have, at the cell's own limits.

The control is the reference in bfloat16 put in the program's place; a
fault is the reference with that fault, put in the program's place. The
run is driven as on the chip (set-up steps, window, reference), at the
small size of `small.py` and without the look for a chip."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from chipbench import run as bench
from chipbench.tests.small import small_cell

CASES = [("mnist.paper_dense", "control"),
         ("mnist.paper_dense", "frozen"),
         ("mnist.paper_dense", "half_batch"),
         ("cifar.paper_int8", "control"),
         ("cifar.paper_int8", "frozen"),
         ("cifar.paper_int8", "half_batch"),
         ("mnist.cohort_100k", "control"),
         ("mnist.cohort_100k", "frozen"),
         ("mnist.cohort_100k", "half_batch"),
         ("mnist.cohort_100k_x4", "control"),
         ("mnist.cohort_100k_x4", "frozen"),
         ("mnist.cohort_100k_x4", "half_batch"),
         ("mnist.cohort_100k_x4", "no_exchange")]


@pytest.mark.parametrize("name,who", CASES)
def test_control_and_faults_are_not_correct(name, who):
    cell = small_cell(name)

    def make(c, s, m):
        if who == "control":
            return bench.ReferenceProgram(c, s, m, dtype=jnp.bfloat16)
        return bench.ReferenceProgram(c, s, m, fault=who)

    out = bench.run_cell(cell, 123, 0.05, False, make_program=make)
    assert out["correct"] is False, out["check"]
    assert list(out)[-1] == "check"
    assert any(v["value"] > v["limit"] for v in out["check"].values())
