"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with the chips the cell asks
for. It needs a TPU: without one, or with fewer chips than the cell
asks for, it exits 2 and prints no result.

Set-up builds the cell's `ExperimentSpec` and its `Simulator` through
the program's public API, with data drawn by the benchmark's own
generator (the model family's, chipbench/families/) from `--seed`, and
drives it through the cell's first `check_steps` steps. A step is one
window call, `Simulator.run(state, max_rounds=eval_every,
eval_every=eval_every)`: one compiled chunk of `eval_every` rounds plus
its eval, so the first step compiles (or loads from the persistent
cache) everything the window runs. Their readings are what `correct`
compares with the reference (chipbench/check.py). Then the window drives
the same simulator and state call after call, each call under a host annotation, until
`--seconds` have passed; the last call that started is allowed to end,
and the window ends with it.

With `--trace 0` the result holds the end-to-end metrics:
  samples_per_s  client training samples (lanes x V x b per round, for
                 every round of the window) over the window's seconds
  setup_s        process start to window start
With `--trace 1` the window runs under the profiler, and the result
holds the cell's per-layer metrics (chipbench/metrics/<name>.py), the
device's busy and window seconds and a breakdown of device time and
idle gaps.

After the window, the peak memory is read and the program's state
freed; then the plain reference (chipbench/reference.py) runs the same
steps, and each compared number is printed beside its limit: as the last
lines of standard error, and as the key "check" of the result, the last
line of standard output.
"""
from __future__ import annotations

import time

PROCESS_START = time.time()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
# Run as a script, Python puts this directory first on the path, where
# chipbench/trace.py would shadow the standard library's `trace`.
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
for _p in (str(REPO / "src"), str(REPO)):
    if _p not in sys.path:
        sys.path.insert(0, _p)
# The compile cache lives in the checkout (the program's `<repo>/.jax_cache`,
# chosen by enable_compile_cache() when no other directory is named), so
# two checkouts never share one. JAX reads this variable as it is imported.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Callable, Dict, List, Optional, Tuple  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import cells, check, flops, reference, trace  # noqa: E402

NO_CHIP = 2


class SimProgram:
    """The system under test: the cell's Simulator, built through
    `ExperimentSpec.build()`, and its run state."""

    def __init__(self, cell: cells.Cell, seed: int, maker: Callable):
        from repro.federated import experiment, scenarios

        experiment.DATASETS[cells.dataset_name(cell)] = maker
        spec = cells.experiment_spec(cell, seed)
        _check_program_matches(cell, spec, scenarios)
        self.sim = spec.build()
        self.state = self.sim.init(seed)
        fed = self.sim.fed
        self.plan = (int(fed.batch_size), int(fed.local_rounds))
        self.R = cell.eval_every

    def params(self):
        return jax.tree.map(lambda a: np.asarray(a, np.float32),
                            jax.device_get(self.sim.params(self.state)))

    def step(self) -> Tuple[List[float], float]:
        self.state, res = self.sim.run(self.state, max_rounds=self.R,
                                       eval_every=self.R)
        return ([float(r.train_loss) for r in res.history],
                float(res.history[-1].test_acc))

    def block(self) -> None:
        self.sim.block_until_ready(self.state)

    @property
    def trace_count(self) -> int:
        return int(self.sim.trace_count)

    def close(self) -> None:
        self.sim = self.state = None


class ReferenceProgram:
    """The reference put in the program's place: the control (a lower
    precision) or a planted fault, driven exactly like SimProgram."""

    def __init__(self, cell: cells.Cell, seed: int, maker: Callable,
                 dtype=None, fault: Optional[str] = None):
        data, test = _datasets(cell, seed, maker)
        b, V = (cell.traffic["plan_expected"][k] for k in ("b", "V"))
        self.ref = reference.Reference(
            cell, seed, data, test, b, V,
            dtype=dtype or reference.jnp.float32, fault=fault,
            shards=cell.chips)
        self.plan = (int(b), int(V))
        self.R = cell.eval_every
        self.trace_count = 0

    def params(self):
        return self.ref.host_params()

    def step(self) -> Tuple[List[float], float]:
        return self.ref.rounds(self.R), self.ref.accuracy()

    def block(self) -> None:
        jax.block_until_ready(self.ref.params)

    def close(self) -> None:
        self.ref = None


def _check_program_matches(cell, spec, scenarios) -> None:
    """The program's model and scenario must be the ones the cell's files
    state: the reference is built from those files."""
    arch = cell.config["architecture"]
    got = cell.family.program_architecture(spec.model_config())
    want = {k: (list(v) if isinstance(v, (list, tuple)) else v)
            for k, v in arch.items()}
    if got != want:
        raise cells.CellError(f"model {cell.config['model']!r} is {got}; "
                              f"the config states {want}")
    sc = cell.traffic.get("scenario")
    if sc is not None:
        s = scenarios.get(sc["name"])
        if (s.dropout, s.link_failure) != (sc["dropout"], sc["link_failure"]):
            raise cells.CellError(
                f"scenario {sc['name']!r} has dropout {s.dropout}, link "
                f"failure {s.link_failure}; the traffic states {sc}")


def _datasets(cell: cells.Cell, seed: int, maker: Callable):
    cfg = cell.config
    data = maker(int(cfg["n_train"]), seed=seed)
    return data, maker(int(cfg["n_test"]), seed=seed + 1, task=data)


def data_maker(cell: cells.Cell) -> Callable:
    """The family's generator of the cell's data, in the signature of the
    program's dataset registry."""
    return cell.family.data_maker(cell.config)


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader (chipbench/metrics/<name>.py) sees."""

    trace: trace.Trace
    window_s: float
    chips: int
    rounds: int
    evals: int
    train_samples: int
    eval_samples: int
    train_flops_per_sample: int
    eval_flops_per_sample: int
    peaks: Dict[str, float]
    retraces: int


def _load_reader(name: str, root: Path) -> Callable:
    path = root / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    if spec is None:
        raise cells.CellError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _peak_bytes(chips: int) -> int:
    """The fullest chip's peak: its arrays (`peak_bytes_in_use`) plus what
    it reserved for compiled programs' temporaries (`peak_bytes_reserved`,
    where the chunk's 2-11 GB of temporaries live)."""
    peaks = []
    for d in jax.local_devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def _stamp(what: str) -> None:
    print(f"chipbench: {what} at {time.time() - PROCESS_START:.3f} s",
          file=sys.stderr, flush=True)


def _finite(xs) -> bool:
    return all(math.isfinite(x) for x in xs)


def setup_steps(prog, steps: int) -> Dict[str, list]:
    """Drive `prog` through its first `steps` window calls; what `correct`
    compares: every round's loss, the model before and after each step,
    and the accuracy after each step."""
    got = {"losses": [], "params": [prog.params()], "acc": []}
    for _ in range(steps):
        losses, acc = prog.step()
        got["losses"] += losses
        got["acc"].append(acc)
        got["params"].append(prog.params())
        _stamp("set-up step")
    return got


def run_cell(cell: cells.Cell, seed: int, seconds: float, traced: bool,
             make_program: Callable = SimProgram,
             root: Path = HERE) -> Dict[str, Any]:
    """One run of `cell`: set-up, window, reference; the result object.
    The look for a chip is main()'s, so tests call this directly."""
    maker = data_maker(cell)
    _stamp("imports done")
    prog = make_program(cell, seed, maker)
    _stamp("built")
    R = cell.eval_every
    got = setup_steps(prog, cell.check_steps)
    plan = prog.plan
    traces_before = prog.trace_count

    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    setup_s = time.time() - PROCESS_START
    t0 = time.perf_counter()
    calls = failed = 0
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        while True:
            with jax.profiler.TraceAnnotation(trace.CALL):
                losses, _ = prog.step()
            calls += 1
            failed += not _finite(losses)
            if time.perf_counter() - t0 >= seconds:
                break
        prog.block()
    window_s = time.perf_counter() - t0
    retraces = prog.trace_count - traces_before
    if traced:
        jax.profiler.stop_trace()
    peak_bytes = _peak_bytes(cell.chips)
    prog.close()
    del prog
    gc.collect()

    b, V = plan
    rounds = calls * R
    samples = rounds * cell.lanes * V * b
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    arch = cell.config["architecture"]
    out: Dict[str, Any] = {"correct": None, "attempted": calls,
                           "failed": failed}
    if traced:
        print(f"memory_peak_bytes {peak_bytes} (peak_bytes_in_use + "
              "peak_bytes_reserved)", flush=True)
        print(f"memory_stats {jax.local_devices()[0].memory_stats()}",
              file=sys.stderr)
        try:
            reduced = trace.from_xspace(trace.find_xspace(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        ctx = Context(
            trace=reduced, window_s=window_s, chips=cell.chips,
            rounds=rounds, evals=calls, train_samples=samples,
            eval_samples=calls * int(cell.config["n_test"]),
            train_flops_per_sample=cell.family.train_flops_per_sample(arch),
            eval_flops_per_sample=cell.family.eval_flops_per_sample(arch),
            peaks=flops.peaks(devs[0].device_kind), retraces=retraces)
        metrics = {}
        for m in cell.per_layer:
            v = _load_reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = trace.busy_s(reduced)
        device["window_s"] = reduced.window_s
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in trace.top_ops(reduced)],
            "idle_gaps": [[k, v] for k, v in trace.idle_gaps(reduced)]}
    else:
        metrics = {"samples_per_s": {"value": samples / window_s,
                                     "unit": "samples/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
    out["metrics"] = metrics
    out["device"] = device

    _stamp("window and readings done")
    data, test = _datasets(cell, seed, maker)
    ref = reference.Reference(cell, seed, data, test,
                              *(cell.traffic["plan_expected"][k]
                                for k in ("b", "V")))
    want = reference.readings(ref, cell.check_steps, R)
    _stamp("reference done")
    nums = check.numbers(got, want, plan, cell.traffic["plan_expected"])
    ok, shown = check.judge(nums, cell.limits)
    out["correct"] = bool(ok)
    out["check"] = shown
    for line in check.lines(shown):
        print(line, file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.workload(args.workload)

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # Every program of the cell is cached, however quick its compile and
    # however large (the program's eval holds the test set as constants:
    # 0.6 GB on MNIST), so a run's set-up finds all of them after the
    # first run.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    devs = jax.devices()
    _stamp("devices found")
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX has {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return NO_CHIP
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
