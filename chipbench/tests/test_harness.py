"""The harness without a chip: cells resolve by name, a new file is found
with no edit, unknown keys are refused, and the command refuses to run
where it finds no TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import cells

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAMES = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", NAMES)
def test_every_cell_resolves_and_builds_its_spec(name):
    from repro.federated import scenarios

    from chipbench import run

    cell = cells.workload(name)
    spec = cells.experiment_spec(cell, 2**31 + 7)
    run._check_program_matches(cell, spec, scenarios)
    assert spec.n_devices() == cell.traffic["population"]["M"]
    assert cell.lanes == (cell.cohort or spec.n_devices())
    assert spec.shard_clients == (cell.chips > 1)
    assert set(cell.limits) <= {"first_loss_gap", "last_loss_gap",
                                "update_gap", "change_gap", "acc_gap",
                                "plan_gap"}
    for m in cell.per_layer:
        assert (REPO / "chipbench" / "metrics" / f"{m['name']}.py").is_file()


def test_every_metric_has_a_reader_and_moves_an_end_to_end_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert (REPO / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in NAMES


def _copy_bench(tmp_path: Path) -> Path:
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_a_new_traffic_file_is_found_by_name(tmp_path):
    root = _copy_bench(tmp_path)
    tr = json.loads((root / "chipbench/traffic/paper_dense.json").read_text())
    tr.update(name="paper_dense_m20", population={"M": 20, "K": None})
    (root / "chipbench/traffic/paper_dense_m20.json").write_text(
        json.dumps(tr))
    shutil.copy(root / "chipbench/limits/mnist.paper_dense.json",
                root / "chipbench/limits/mnist.paper_dense_m20.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "mnist.paper_dense_m20",
                               "config": "fedavg_cnn_mnist",
                               "traffic": "paper_dense_m20", "chips": 1,
                               "why": "twenty clients"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.workload("mnist.paper_dense_m20", repo=root)
    assert cell.lanes == 20
    assert cells.experiment_spec(cell, 5).n_devices() == 20


def test_unknown_keys_are_refused(tmp_path):
    root = _copy_bench(tmp_path)
    path = root / "chipbench/traffic/paper_dense.json"
    tr = json.loads(path.read_text())
    tr["eval_evry"] = 5
    path.write_text(json.dumps(tr))
    with pytest.raises(cells.CellError, match="unknown keys.*eval_evry"):
        cells.workload("mnist.paper_dense", repo=root)
    with pytest.raises(cells.CellError, match="no workload"):
        cells.workload("mnist.nowhere")


def _command(cwd: Path, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable] + BENCH["command"][1:]
        + ["--workload", "mnist.paper_dense", "--seed", "3",
           "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    r = _command(REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs 1 TPU chip" in r.stderr


def test_with_only_the_benchmark_files_the_command_fails(tmp_path):
    r = _command(_copy_bench(tmp_path))
    assert r.returncode != 0
    assert r.stdout.strip() == ""
