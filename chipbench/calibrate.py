"""Readings that a cell's limits are set from (chipbench/limits/).

    python3 chipbench/calibrate.py --workload <cell> --seeds 11 12 13 \
        [--control 3] [--faults half_batch,no_exchange] [--out FILE]

For every seed, in one process: the reference run once, then the
program's set-up steps (exactly as a benchmark run drives them, without
the window) against it, and for the first `--control` seeds the control
(the reference in bfloat16, put in the program's place) and each planted
fault (the reference with the fault, in the program's place) against the
same reference. Each reading is one JSON line on standard output (and in
`--out`): {"workload", "seed", "who", "numbers"}; `--raw` also writes
every round's loss, each step's accuracy and leaf-norms of both sides.
The benchmark's own runs never run this. Needs the chips the cell asks
for, like run.py.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from pathlib import Path

sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() != Path(__file__).resolve().parent]
_REPO = Path(__file__).resolve().parent.parent
for _p in (str(_REPO / "src"), str(_REPO)):
    if _p not in sys.path:
        sys.path.insert(0, _p)
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)  # as run.py

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import cells, check, reference  # noqa: E402
from chipbench import run as bench  # noqa: E402


def _raw(r):
    norms = [check.leaf_norms(r["params"][i + 1], r["params"][0]).tolist()
             for i in range(len(r["params"]) - 1)]
    return {"losses": r["losses"], "acc": r["acc"], "leaf_norms": norms}


def seed_readings(cell, seed, makers, raw=None):
    """(who, numbers) for each of `makers` ({who: make_program}) at one
    seed, each driven through a run's set-up steps and compared with the
    one reference run of that seed."""
    maker = bench.data_maker(cell)
    data, test = bench._datasets(cell, seed, maker)
    b, V = (cell.traffic["plan_expected"][k] for k in ("b", "V"))
    want = reference.readings(reference.Reference(cell, seed, data, test,
                                                  b, V),
                              cell.check_steps, cell.eval_every)
    del data, test
    gc.collect()
    for who, make in makers.items():
        prog = make(cell, seed, maker)
        got = bench.setup_steps(prog, cell.check_steps)
        plan = prog.plan
        prog.close()
        del prog
        gc.collect()
        if raw is not None:
            raw.write(json.dumps({"seed": seed, "who": who,
                                  "program": _raw(got),
                                  "reference": _raw(want)}) + "\n")
            raw.flush()
        yield who, check.numbers(got, want, plan,
                                 cell.traffic["plan_expected"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=0,
                    help="seeds (the first ones) for the control and faults")
    ap.add_argument("--faults", default="",
                    help="comma-separated faults of chipbench.reference")
    ap.add_argument("--out", default=None)
    ap.add_argument("--raw", default=None,
                    help="also write every reading's losses and leaf norms")
    args = ap.parse_args(argv)
    cell = cells.workload(args.workload)

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"calibrate: {args.workload} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return bench.NO_CHIP
    faults = [f for f in args.faults.split(",") if f]
    out = open(args.out, "a") if args.out else None
    raw = open(args.raw, "a") if args.raw else None

    def emit(seed, who, nums):
        line = json.dumps({"workload": cell.name, "seed": seed, "who": who,
                           "numbers": nums})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    try:
        for i, seed in enumerate(args.seeds):
            makers = {"program": bench.SimProgram}
            if i < args.control:
                makers["control"] = lambda c, s, m: bench.ReferenceProgram(
                    c, s, m, dtype=jnp.bfloat16)
                for fault in faults:
                    makers[f"fault:{fault}"] = (
                        lambda c, s, m, f=fault:
                            bench.ReferenceProgram(c, s, m, fault=f))
            for who, nums in seed_readings(cell, seed, makers, raw):
                emit(seed, who, nums)
    finally:
        for f in (out, raw):
            if f:
                f.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
