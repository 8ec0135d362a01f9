"""A cell's own path at a size the CPU test run can hold: the small
FedAvg CNN (conv 8 and 16, FC 64), 600 training rows, 20 rounds a step
(60 over the checked steps: enough for the loss to settle, where a lower
precision shows), and the plan the program solves at that size."""
from __future__ import annotations

from chipbench import cells
from chipbench import run as bench

SMALL_CONFIG = {
    "model": "mnist_cnn_small",
    "architecture": {"input_hw": [28, 28], "in_channels": 1, "n_classes": 10,
                     "conv_channels": [8, 16], "kernel": 5, "fc_dim": 64},
    "n_train": 600, "n_test": 100}


# Cells whose traffic files are ready but which BENCHMARK.json does not
# list yet: (config, traffic, chips).
UNLISTED = {"mnist.cohort_100k": ("fedavg_cnn_mnist", "cohort_100k", 1),
            "mnist.cohort_100k_x4": ("fedavg_cnn_mnist", "cohort_100k_x4", 4)}


def small_cell(name: str, seed: int = 123) -> cells.Cell:
    from repro.federated import experiment

    if name in UNLISTED:
        config, traffic, chips = UNLISTED[name]
        cell = cells.from_files(
            name, cells.REPO / "chipbench" / "configs" / f"{config}.json",
            traffic, chips)
    else:
        cell = cells.workload(name)
    traffic = {"eval_every": 20}
    if cell.cohort:
        traffic["population"] = {"M": 2000, "K": 8}
    cell = cells.with_overrides(cell, config=SMALL_CONFIG, traffic=traffic)
    experiment.DATASETS[cells.dataset_name(cell)] = bench.data_maker(cell)
    fed = cells.experiment_spec(cell, seed).resolve_fed()
    return cells.with_overrides(cell, traffic={"plan_expected": {
        "b": fed.batch_size, "V": fed.local_rounds}})
