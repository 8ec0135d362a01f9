"""Smoke run of the DEFL simulator's main path on a TPU.

    python chip_smoke.py              # phases a-e on one chip
    python chip_smoke.py --chips 4    # only: client-axis sharding, 4 chips vs 1

Every phase goes through the user entry point, `ExperimentSpec.build()`
-> `Simulator`, at `mnist_cnn`'s published widths (1.66M parameters) with
weights drawn from the seed:

  a  `mnist_paper` on backend='scan' (M=10, DEFL plan b*=32, V=4): 10
     rounds, eval every 5. One compiled chunk, finite falling loss,
     accuracy above chance; compile, per-round and peak-memory readings.
  b  the same spec on backend='loop' (the plain per-client reference) for
     2 rounds, against scan.
  c  int8 uplink through the compiled Pallas quantizer (its HLO must hold
     `tpu_custom_call`), against the XLA quantizer under the same key.
  d  cross-device sampling: M=100,000 clients, K=64-client cohorts,
     scenario 'dropout', plain SGD.
  e  the async engine: `mnist_async` on mnist_cnn, and its synchronous
     limit (buffer of all M clients, constant staleness) against phase
     b's scan run; a 4-seed fleet, whose seed-0 member must match it too.

Weights and data come from seed 0.

With `--chips 4` the script runs phase d with `shard_clients=True` on a
4-chip ("clients",) mesh and the same spec on one chip, and nothing else.

The script needs a TPU: without one it exits 1 before any phase. Any
failing phase exits nonzero. Only when every phase passed is the last
stdout line the JSON object {"ok": true, "device": {...}}. Times printed
here are one-off smoke readings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.federated import experiment  # noqa: E402
from repro.federated.events import AsyncSpec  # noqa: E402
from repro.federated.experiment import CohortSpec, PopulationSpec  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

MODEL = "mnist_cnn"
CHANCE = 0.1  # 10 balanced classes

SEED = 0

# Agreement bounds between two training graphs of the same spec and seed.
# On the TPU, f32 matmuls at default precision run as bf16 passes, and
# the compared graphs (vmapped scan vs per-client loop, psum over shards
# vs one in-graph sum, vmapped fleet vs solo, the async event engine at
# its synchronous limit vs scan) fuse and reduce in different orders. So
# they agree to bf16 rounding, not bit for bit: the bit-identity
# contracts are kept on XLA:CPU only. On a v5e, loop vs scan after two
# mnist_paper rounds differed by 6.3e-4 in params and 1.2e-4 relative in
# loss. Planted faults land above the bounds (PERF.md, Findings):
# dropping one of the ten clients from the aggregate moves params by
# 8.5e-2 (the 2-round losses only by 2.3e-4: the params bound catches
# it), and an unquantized uplink moves losses by 3e-1.
PARAM_ATOL = 2e-3
LOSS_RTOL = 1e-3
# Pallas vs XLA quantizer (phase c): the training graph is the same, only
# the quantizer differs, and both round the same scaled values. On a v5e
# they differed by 1.5e-8 in params and 0.0 in loss, so the bounds are a
# few f32 ulps, not bf16 rounding. An unquantized uplink moves params by
# 1.2e-1.
INT8_PARAM_ATOL = 1e-6
INT8_LOSS_RTOL = 1e-6


class SmokeFailure(AssertionError):
    """A phase produced a wrong result."""


def require(ok, msg: str) -> None:
    # Not `assert`: the checks must hold under `python -O` too.
    if not ok:
        raise SmokeFailure(msg)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def max_abs_diff(a, b) -> float:
    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                    - np.asarray(y, np.float64))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def losses(res) -> np.ndarray:
    return np.array([r.train_loss for r in res.history], np.float64)


def check_finite(phase: str, res, rounds: int) -> np.ndarray:
    loss = losses(res)
    require(len(loss) == rounds, f"[{phase}] {len(loss)} rounds, not {rounds}")
    require(np.all(np.isfinite(loss)), f"[{phase}] non-finite losses {loss}")
    for leaf in jax.tree.leaves(res.params):
        require(np.all(np.isfinite(np.asarray(leaf))),
                f"[{phase}] non-finite params")
    return loss


def compare(phase: str, what: str, ref, got, param_atol=PARAM_ATOL,
            loss_rtol=LOSS_RTOL) -> dict:
    """Params and losses of two runs of one spec and seed, within bounds."""
    dp = max_abs_diff(ref.params, got.params)
    lr, lg = losses(ref), losses(got)
    dl = float(np.max(np.abs(lr - lg) / np.abs(lr)))
    log(phase, f"{what}: max |dparams| {dp!r} (bound {param_atol}), "
               f"max rel |dloss| {dl!r} (bound {loss_rtol})")
    require(dp <= param_atol, f"[{phase}] {what}: params differ by {dp}")
    require(dl <= loss_rtol, f"[{phase}] {what}: losses differ by {dl}")
    return {"max_param_diff": dp, "max_rel_loss_diff": dl}


class _Captured(Exception):
    pass


def chunk_arg_shapes(sim, rounds: int, sharding=None):
    """Shapes of the first chunk call's arguments in `sim.run` (placed on
    `sharding` when given), to lower `sim._chunk_fn` with: the host
    prepares a real chunk, and the call is stopped before it runs."""
    calls = []

    def spy(*args):
        calls.append(args)
        raise _Captured

    chunk, sim._chunk_fn = sim._chunk_fn, spy
    try:
        sim.run(sim.init(SEED), max_rounds=rounds, eval_every=rounds)
    except _Captured:
        pass
    finally:
        sim._chunk_fn = chunk
    require(calls, "the run made no chunk call")
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), calls[0])


def paper_spec(model: str, **kw) -> experiment.ExperimentSpec:
    return experiment.get("mnist_paper").replace(model=model, **kw)


def sampled_spec(model: str, M: int, K: int, shard: bool = False):
    return paper_spec(model, scenario="dropout", shard_clients=shard,
                      population=PopulationSpec(M=M, cohort=CohortSpec(K=K)))


def phase_main(model: str = MODEL, rounds: int = 10,
               eval_every: int = 5) -> dict:
    """a: the scan engine, chunk by chunk, timed to block_until_ready."""
    sim = paper_spec(model).build()
    fed = sim.fed
    log("a", f"mnist_paper on {model}: M={fed.n_devices} b={fed.batch_size} "
             f"V={fed.local_rounds} backend={sim.backend}")
    state = sim.init(SEED)
    t0 = time.perf_counter()
    state, first = sim.run(state, max_rounds=eval_every, eval_every=eval_every)
    sim.block_until_ready(state)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    state, rest = sim.run(state, max_rounds=rounds - eval_every,
                          eval_every=eval_every)
    sim.block_until_ready(state)
    t_round = (time.perf_counter() - t0) / (rounds - eval_every)
    history = first.history + rest.history
    loss = check_finite("a", dataclasses.replace(rest, history=history),
                        rounds)
    acc = history[-1].test_acc
    require(sim.trace_count == 1, f"[a] {sim.trace_count} chunk traces")
    require(loss[-1] < loss[0], f"[a] loss did not fall: {loss}")
    require(acc is not None and acc > CHANCE, f"[a] accuracy {acc} at chance")
    stats = jax.devices()[0].memory_stats() or {}
    out = {"first_chunk_s": t_first, "steady_s_per_round": t_round,
           "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
           "device_data": sim._data_dev is not None,
           "loss_first": float(loss[0]), "loss_last": float(loss[-1]),
           "test_acc": acc, "trace_count": sim.trace_count}
    log("a", f"losses {loss.tolist()}")
    log("a", f"first chunk ({eval_every} rounds, compile included) "
             f"{t_first!r} s; steady {t_round!r} s/round (second chunk); "
             f"peak_bytes_in_use {out['peak_bytes_in_use']}; "
             f"device-resident data {out['device_data']}; "
             f"test acc {acc!r}")
    return out


def phase_reference(model: str = MODEL, rounds: int = 2):
    """b: scan against the per-client loop reference. Returns the scan run
    (phase e compares a fleet member with it)."""
    spec = paper_spec(model)
    runs = {}
    for backend in ("scan", "loop"):
        sim = spec.replace(backend=backend).build()
        _, runs[backend] = sim.run(sim.init(SEED), max_rounds=rounds,
                                   eval_every=rounds)
        check_finite("b", runs[backend], rounds)
    return runs["scan"], compare("b", "loop vs scan", runs["loop"],
                                 runs["scan"])


def phase_int8(model: str = MODEL, rounds: int = 2) -> dict:
    """c: compressed uplink, Pallas quantizer vs XLA quantizer."""
    base = paper_spec(model)
    spec = base.replace(fed=dataclasses.replace(base.fed,
                                                compress_updates=True))
    sim = spec.replace(impl="pallas").build()
    _, pallas = sim.run(sim.init(SEED), max_rounds=rounds, eval_every=rounds)
    hlo = sim._chunk_fn.lower(*chunk_arg_shapes(sim, rounds)
                              ).compile().as_text()
    sim = spec.replace(impl="xla").build()
    _, xla = sim.run(sim.init(SEED), max_rounds=rounds, eval_every=rounds)
    for res in (pallas, xla):
        check_finite("c", res, rounds)
    kernel = "tpu_custom_call" in hlo
    log("c", f"tpu_custom_call in the compressed chunk: {kernel}")
    if jax.default_backend() == "tpu":
        require(kernel, "[c] the Pallas quantizer did not compile to Mosaic")
    out = compare("c", "pallas vs xla quantizer", xla, pallas,
                  INT8_PARAM_ATOL, INT8_LOSS_RTOL)
    out["tpu_custom_call"] = kernel
    return out


def phase_sampled(model: str = MODEL, rounds: int = 4,
                  M: int = 100_000, K: int = 64, shard: bool = False,
                  phase: str = "d"):
    """d: K-client cohorts drawn per round from an M-client population."""
    sim = sampled_spec(model, M, K, shard).build()
    fed = sim.fed
    chips = sim._mesh.devices.size if shard else 1
    log(phase, f"M={M} K={K} b={fed.batch_size} V={fed.local_rounds} "
               f"shard_clients={shard} on {chips} chip(s)")
    t0 = time.perf_counter()
    state, res = sim.run(sim.init(SEED), max_rounds=rounds, eval_every=2)
    sim.block_until_ready(state)
    wall = time.perf_counter() - t0
    loss = check_finite(phase, res, rounds)
    parts = [r.n_participants for r in res.history]
    require(all(0 < p <= K for p in parts), f"[{phase}] participants {parts}")
    require(sim.trace_count == 1, f"[{phase}] {sim.trace_count} traces")
    log(phase, f"losses {loss.tolist()}; participants {parts}; "
               f"{rounds} rounds in {wall!r} s (compile included)")
    return sim, state, res


def phase_engines(solo, model: str = MODEL,
                  aggregations: int = 4) -> dict:
    """e: the async event engine and a vmapped multi-seed fleet, each held
    to `solo`, phase b's scan run of mnist_paper at SEED.

    At its synchronous limit (a buffer of all M clients, constant
    staleness, the uniform scenario) the async engine is FedAvg on the
    event clock: each aggregation consumes one update from every client,
    all dispatched from the same global model. It must then reproduce
    `solo`. The fleet member at SEED must reproduce it too."""
    sim = experiment.get("mnist_async").replace(model=model).build()
    _, res = sim.run(sim.init(SEED), max_rounds=aggregations, eval_every=2)
    loss = check_finite("e", res, aggregations)
    require(sim.trace_count == 1, f"[e] async: {sim.trace_count} traces")
    log("e", f"mnist_async: {aggregations} aggregations, losses "
             f"{loss.tolist()}")
    rounds = len(solo.history)
    M = paper_spec(model).fed.n_devices
    sim = paper_spec(model, scenario="uniform", backend="async",
                     async_spec=AsyncSpec(buffer_size=M,
                                          staleness="constant")).build()
    _, sync = sim.run(sim.init(SEED), max_rounds=rounds, eval_every=rounds)
    check_finite("e", sync, rounds)
    parts = [r.n_participants for r in sync.history]
    require(parts == [M] * rounds, f"[e] sync limit: participants {parts}")
    out = {"sync_limit": compare("e", "async sync limit vs scan", solo,
                                 sync)}
    seeds = list(range(4))  # SEED among them
    fleet = paper_spec(model).build().run_fleet(
        seeds=seeds, max_rounds=rounds, eval_every=rounds)
    require(len(fleet.results) == len(seeds), "[e] fleet lost members")
    for res in fleet.results:
        check_finite("e", res, rounds)
    log("e", f"fleet of {len(seeds)} seeds: final losses "
             f"{[float(r.history[-1].train_loss) for r in fleet.results]}")
    out["fleet"] = compare("e", "fleet member vs solo scan", solo,
                           fleet.results[seeds.index(SEED)])
    return out


def phase_shard(model: str = MODEL, rounds: int = 4,
                M: int = 100_000, K: int = 64) -> dict:
    """--chips 4: phase d with the client axis sharded over every device,
    against the same spec unsharded on the first device."""
    devices = jax.devices()
    _, _, ref = phase_sampled(model, rounds, M, K, shard=False,
                              phase="d1")
    sim, state, got = phase_sampled(model, rounds, M, K, shard=True,
                                    phase="d4")
    mesh = sim._mesh
    require(mesh.axis_names == ("clients",), f"mesh axes {mesh.axis_names}")
    require(list(mesh.devices.flat) == devices,
            f"mesh {mesh.devices} is not the run's devices {devices}")
    rows = K // len(devices)
    for leaf in jax.tree.leaves(state.params_C):
        shards = leaf.addressable_shards
        on = {s.device for s in shards}
        require(on == set(devices), f"client lanes live on {on} only")
        require(all(s.data.shape[0] == rows for s in shards),
                f"shards {[s.data.shape for s in shards]}, not {rows} lanes")
    log("d4", f"mesh {mesh.shape} over {len(devices)} chips; "
              f"{rows} client lanes per chip")
    for a, b in zip(ref.history, got.history):
        require(a.n_participants == b.n_participants
                and a.sim_time == b.sim_time,
                f"[d4] round {a.round}: participation or clock differs")
    return compare("d4", "sharded vs one chip", ref, got)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the shard_clients comparison")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"device {dev.device_kind} x{len(devices)}; jax {jax.__version__}; "
          f"compile cache {cache}", flush=True)
    def timed(name, phase, *args):
        t0 = time.perf_counter()
        out = phase(*args)
        log(name, f"passed in {time.perf_counter() - t0!r} s")
        return out

    if args.chips == 4:
        timed("d4", phase_shard)
    else:
        timed("a", phase_main)
        solo, _ = timed("b", phase_reference)
        timed("c", phase_int8)
        timed("d", phase_sampled)
        timed("e", phase_engines, solo)
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile cache {cache}: {entries} entries", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
