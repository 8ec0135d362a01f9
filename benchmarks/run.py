"""Benchmark entry point: one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig1a,...] \
      [--scenario <name>] [--seeds N] [--json PATH]

Emits ``name,...`` CSV blocks per benchmark. ``--scenario`` restricts the
scenario-aware benchmarks (fig2, straggler) to one registered edge
scenario (federated/scenarios.py); ``--seeds N`` runs seed-aware
benchmarks (fig2) as a vmapped N-seed fleet per method and reports
mean +/- std confidence bands instead of single-run numbers. Benchmarks
that don't take a flag run unchanged, with a note.

``--json PATH`` additionally writes one machine-readable JSON document
for everything that ran: Study-backed figures emit their full
`StudyResult.to_json()` payload (per-arm histories, grouping report,
summaries — what the CI study gate consumes), other benchmarks emit
their header/rows. The roofline table reads the dry-run dumps in
experiments/dryrun (run launch/dryrun.py first for the full 40-pair
baseline)."""
from __future__ import annotations

import argparse
import inspect
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from benchmarks import (  # noqa: E402
    ablation_compression,
    ablation_straggler,
    async_vs_sync,
    bench_round_step,
    bench_study,
    fig1a_epsilon,
    fig1b_batch,
    fig1c_theta,
    fig1d_rounds,
    fig2_defl_vs_fedavg,
    roofline_table,
)
from repro.federated import scenarios  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

BENCHES = {
    "fig1a": fig1a_epsilon.run,
    "fig1b": fig1b_batch.run,
    "fig1c": fig1c_theta.run,
    "fig1d": fig1d_rounds.run,
    "fig2": fig2_defl_vs_fedavg.run,
    "async": async_vs_sync.run,
    "straggler": ablation_straggler.run,
    "compression": ablation_compression.run,
    "roofline": roofline_table.run,
    "round_step": bench_round_step.run,
    "study": bench_study.run,
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced round budgets (single-core CPU container)")
    ap.add_argument("--only", default="")
    ap.add_argument("--scenario", default="", choices=("",) + scenarios.names(),
                    help="restrict scenario-aware benchmarks to one "
                         "registered edge scenario")
    ap.add_argument("--seeds", type=int, default=1,
                    help="run seed-aware benchmarks as a vmapped N-seed "
                         "fleet per configuration (mean +/- std bands)")
    ap.add_argument("--json", default="",
                    help="write a machine-readable JSON document of every "
                         "benchmark that ran (StudyResult payloads for "
                         "study-backed figures)")
    ap.add_argument("--checkpoint-dir", default="",
                    help="crash-safe per-(arm, seed) autosave for study-"
                         "backed benchmarks (Study.run(checkpoint_dir=...)): "
                         "a killed run resumes from the saved members "
                         "bit-identically")
    ap.add_argument("--no-resume", action="store_true",
                    help="with --checkpoint-dir: ignore existing member "
                         "checkpoints and re-run everything (files are "
                         "overwritten)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    names = args.only.split(",") if args.only else list(BENCHES)
    payloads = {}
    for name in names:
        fn = BENCHES[name]
        kw = {"quick": args.quick}
        if args.scenario:
            if "scenario" in inspect.signature(fn).parameters:
                kw["scenario"] = args.scenario
            else:
                print(f"# === {name}: not scenario-aware; running as-is ===",
                      flush=True)
        if args.seeds > 1:
            if "seeds" in inspect.signature(fn).parameters:
                kw["seeds"] = args.seeds
            else:
                print(f"# === {name}: not seed-aware; running as-is ===",
                      flush=True)
        if args.checkpoint_dir:
            if "checkpoint_dir" in inspect.signature(fn).parameters:
                kw["checkpoint_dir"] = args.checkpoint_dir
                kw["resume"] = not args.no_resume
            else:
                print(f"# === {name}: not checkpoint-aware; running "
                      "as-is ===", flush=True)
        t0 = time.time()
        out = fn(**kw)
        header, rows = out[0], out[1]
        payloads[name] = (out[2] if len(out) > 2
                          else {"header": header, "rows": [list(r) for r in rows]})
        print(f"# === {name} ({time.time() - t0:.1f}s) ===", flush=True)
        print(header)
        for r in rows:
            print(",".join(map(str, r)))
        print(flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(payloads, f, indent=2, default=float)
            f.write("\n")
        print(f"# wrote {args.json}", flush=True)


if __name__ == "__main__":
    main()
