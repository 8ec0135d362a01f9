"""Where a local step's time goes, by the TPU compiler's own estimate.

    JAX_PLATFORMS=cpu python scripts/chunk_cycles.py                 # mnist_paper, cifar_paper
    JAX_PLATFORMS=cpu python scripts/chunk_cycles.py --experiments mnist_paper --top 40

Compiles each experiment's scan chunk (`Simulator._chunk_fn`, with the
first chunk call's argument shapes) for a described TPU v5e chip; no chip
is needed. Every instruction of the compiled program carries the
compiler's `estimated_cycles`. The script sums them over the body of
the local-step loop (the computation named `region_1`, run V times a
round) and prints one JSON line per experiment:

  total       estimated cycles of one local step
  by_op       shares by instruction name without its `.n` suffix (the
              names a device trace shows: `fusion`, `copy`, `pad`, ...)
  top         the largest instructions: name, opcode, result shape,
              share, and the tail of the jax op that made it

Custom calls (the Pallas kernels) carry no estimate and count 0: their
time is only seen in a chip trace. Shares are of estimated cycles, not
of measured time.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402

import chip_smoke  # noqa: E402
from repro.federated import experiment  # noqa: E402

_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = (.+?) ([a-z][a-z_\-]*)\(")


def compile_chunk_text(name: str) -> str:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    sim = experiment.get(name).build()
    shapes = chip_smoke.chunk_arg_shapes(sim, rounds=5, sharding=chip)
    return sim._chunk_fn.lower(*shapes).compile().as_text()


def loop_body(hlo: str) -> list:
    """Instruction lines of the largest computation named `region_1`."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%(\S+) \(", line)
        if m and line.rstrip().endswith("{"):
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    bodies = [n for n in comps if re.search(r"(^|\.)region_1\.\d", n)]
    if not bodies:
        raise SystemExit("no local-step loop body (region_1) in the program")
    return comps[max(bodies, key=lambda n: len(comps[n]))]


def cycle_shares(lines: list, top: int) -> dict:
    rows = []
    for line in lines:
        m = _INSTR.match(line)
        c = re.search(r'estimated_cycles":"(\d+)"', line)
        if not (m and c):
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        rows.append((m.group(1), m.group(3), m.group(2), int(c.group(1)),
                     op.group(1).split("closed_call/")[-1] if op else ""))
    total = sum(r[3] for r in rows) or 1
    by_op = collections.Counter()
    for name, *_, cyc, _ in rows:
        by_op[re.sub(r"\.\d+(\.clone.*)?$", "", name)] += cyc
    return {
        "total": total,
        "by_op": {k: round(v / total, 4) for k, v in by_op.most_common(15)},
        "top": [{"name": n, "opcode": o, "shape": s.split("{")[0].lstrip("("),
                 "share": round(c / total, 4), "op": src[-80:]}
                for n, o, s, c, src in sorted(rows, key=lambda r: -r[3])[:top]],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--experiments", default="mnist_paper,cifar_paper")
    ap.add_argument("--top", type=int, default=20)
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    for name in filter(None, args.experiments.split(",")):
        shares = cycle_shares(loop_body(compile_chunk_text(name)), args.top)
        print(json.dumps({"experiment": name, **shares}), flush=True)


if __name__ == "__main__":
    main()
