"""Cells of the benchmark, found by name.

A cell (an entry of `workloads` in BENCHMARK.json) names a model
configuration and a traffic mix. Each lives in a file of its own, found
by name and never listed in code, so a later cell adds files and edits
none:

    chipbench/configs/<config>.json    model, data and FL constants
    chipbench/traffic/<traffic>.json   population, scenario, uplink, cadence
    chipbench/limits/<workload>.json   the limit of each number `correct`
                                       compares (chipbench/check.py)
    chipbench/metrics/<metric>.py      one per-layer metric reader

Unknown keys are refused, so a misspelt knob cannot fall back to a
default unseen.
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

REPO = Path(__file__).resolve().parent.parent
BENCH_DIR = "chipbench"

CONFIG_KEYS = {"name", "source", "model", "architecture", "params",
               "dataset", "n_train", "n_test", "data_seed", "fl", "dtype",
               "matmul_precision", "reduced", "assumed", "notes"}
ARCH_KEYS = {"input_hw", "in_channels", "n_classes", "conv_channels",
             "kernel", "fc_dim"}
FL_KEYS = {"epsilon", "nu", "c", "lr", "plan", "batch_cap", "alpha"}
TRAFFIC_KEYS = {"name", "population", "scenario", "compress_updates",
                "impl", "backend", "eval_every", "shard_clients", "chips",
                "plan_expected", "check_steps", "notes"}
POPULATION_KEYS = {"M", "K"}
SCENARIO_KEYS = {"name", "dropout", "link_failure"}
PLAN_KEYS = {"b", "V"}
LIMIT_KEYS = {"limits", "readings", "notes"}


class CellError(ValueError):
    """A cell, configuration, traffic mix or limit file is malformed."""


def _refuse_unknown(what: str, got: Dict, allowed: set) -> None:
    extra = set(got) - allowed
    if extra:
        raise CellError(f"{what}: unknown keys {sorted(extra)}; "
                        f"allowed {sorted(allowed)}")


def _read_json(path: Path) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise CellError(f"no such file: {path}") from None


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict[str, float]
    per_layer: List[Dict]

    @property
    def cohort(self) -> Optional[int]:
        return self.traffic["population"].get("K")

    @property
    def lanes(self) -> int:
        """Client lanes the device trains every round: K, or M dense."""
        pop = self.traffic["population"]
        return int(pop["K"] if pop.get("K") is not None else pop["M"])

    @property
    def eval_every(self) -> int:
        return int(self.traffic["eval_every"])

    @property
    def check_steps(self) -> int:
        return int(self.traffic["check_steps"])


def load_benchmark(repo: Path = REPO) -> Dict:
    return _read_json(Path(repo) / "BENCHMARK.json")


def _check_config(cfg: Dict, where: str) -> None:
    _refuse_unknown(where, cfg, CONFIG_KEYS)
    _refuse_unknown(f"{where} architecture", cfg["architecture"], ARCH_KEYS)
    _refuse_unknown(f"{where} fl", cfg["fl"], FL_KEYS)
    if cfg["dtype"] != "float32":
        raise CellError(f"{where}: dtype {cfg['dtype']!r}; the reference "
                        "and its control are written for float32")


def _check_traffic(tr: Dict, where: str) -> None:
    _refuse_unknown(where, tr, TRAFFIC_KEYS)
    _refuse_unknown(f"{where} population", tr["population"], POPULATION_KEYS)
    if tr.get("scenario") is not None:
        _refuse_unknown(f"{where} scenario", tr["scenario"], SCENARIO_KEYS)
    _refuse_unknown(f"{where} plan_expected", tr["plan_expected"], PLAN_KEYS)
    if tr["backend"] != "scan":
        raise CellError(f"{where}: backend {tr['backend']!r}; the window "
                        "drives the scan engine's chunks")


def workload(name: str, repo: Path = REPO) -> Cell:
    """The cell `name` of BENCHMARK.json, with its files resolved."""
    repo = Path(repo)
    bench = load_benchmark(repo)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise CellError(f"no workload {name!r} in BENCHMARK.json; have "
                        f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    per_layer = [m for m in bench["per_layer"]
                 if "workloads" not in m or name in m["workloads"]]
    return from_files(name, repo / cfg_entry["file"], entry["traffic"],
                      int(entry["chips"]), per_layer, repo)


def from_files(name: str, config_file: Path, traffic: str, chips: int,
               per_layer: List[Dict] = (), repo: Path = REPO) -> Cell:
    """A cell from its configuration file, its traffic mix and its limits
    file, whether or not BENCHMARK.json lists it yet."""
    root = Path(repo) / BENCH_DIR
    cfg = _read_json(config_file)
    _check_config(cfg, str(config_file))
    tr_path = root / "traffic" / f"{traffic}.json"
    tr = _read_json(tr_path)
    _check_traffic(tr, str(tr_path))
    if int(tr["chips"]) != int(chips):
        raise CellError(f"{name}: asked for {chips} chips, {tr_path.name} "
                        f"for {tr['chips']}")
    lim_path = root / "limits" / f"{name}.json"
    lim = _read_json(lim_path)
    _refuse_unknown(str(lim_path), lim, LIMIT_KEYS)
    return Cell(name=name, chips=int(chips), config=cfg, traffic=tr,
                limits=dict(lim["limits"]), per_layer=list(per_layer))


def dataset_name(cell: Cell) -> str:
    """The name the benchmark's generator is registered under."""
    return f"chipbench.{cell.config['name']}"


def experiment_spec(cell: Cell, seed: int):
    """The cell's ExperimentSpec at `seed` (data, partition, population
    and weights are drawn from it; the run's batch order, realizations
    and quantizer noise from the same seed at `Simulator.init`)."""
    from repro.configs.base import FedConfig
    from repro.federated.experiment import (CohortSpec, ExperimentSpec,
                                            PopulationSpec)

    cfg, tr = cell.config, cell.traffic
    fl, pop = cfg["fl"], tr["population"]
    fed = FedConfig(n_devices=int(pop["M"]), epsilon=fl["epsilon"],
                    nu=fl["nu"], c=fl["c"], lr=fl["lr"],
                    compress_updates=bool(tr["compress_updates"]))
    population = None
    if pop.get("K") is not None:
        population = PopulationSpec(M=int(pop["M"]),
                                    cohort=CohortSpec(K=int(pop["K"])))
    scenario = tr.get("scenario")
    return ExperimentSpec(
        fed=fed, population=population,
        shard_clients=bool(tr["shard_clients"]), model=cfg["model"],
        dataset=dataset_name(cell), n_train=int(cfg["n_train"]),
        n_test=int(cfg["n_test"]), alpha=float(fl["alpha"]), seed=int(seed),
        scenario=None if scenario is None else scenario["name"],
        plan=bool(fl["plan"]), batch_cap=fl["batch_cap"],
        backend=tr["backend"], impl=tr["impl"], label=cell.name)


def with_overrides(cell: Cell, config: Optional[Dict] = None,
                   traffic: Optional[Dict] = None) -> Cell:
    """A copy of `cell` with some config/traffic keys replaced (tests run
    a cell's own path at CPU size this way)."""
    cfg = {**cell.config, **(config or {})}
    tr = {**cell.traffic, **(traffic or {})}
    return dataclasses.replace(cell, config=cfg, traffic=tr)
