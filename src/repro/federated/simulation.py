"""Host-level FL simulator: Algorithm 1 with the paper's delay accounting.

Runs real training (JAX) while advancing a *simulated* wall clock from the
paper's delay models (Eqs. 5, 7, 8) — exactly how the paper reports
"overall time" for DEFL vs FedAvg vs Rand (Fig. 2). Heterogeneous device
populations, non-IID partitions and update compression are supported, and
a named `scenario` (federated/scenarios.py) layers per-round partial
participation (Bernoulli dropout / link failure) and channel drift on top.

The public API is two layers:

  `Simulator` — a pure functional core. All run state (stacked client
      params/opt, PRNG key, sim clock, round cursor, scenario-stream and
      data-iterator positions) lives in an immutable `SimState` pytree;
      every method is state-in/state-out:

          sim   = Simulator(loss_fn, params, data, sizes, fed, opt, pop)
          state = sim.init(seed)
          state, result  = sim.run(state, max_rounds=100, eval_every=10)
          state, records = sim.run_chunk(state, rounds=10)
          fleet = sim.run_fleet(seeds=range(8), max_rounds=100)

      Because `SimState` is a pytree and the compiled chunk function is
      pure, `run_fleet` vmaps the existing scan chunk over an extra
      leading axis: S seeds execute in ONE dispatch per chunk instead of
      S sequential runs, bit-identical per seed to sequential `run()`
      calls. `SimState` round-trips through `jax.device_get` and
      `save_state`/`load_state` for checkpoint/resume — a restored state
      continues the loss/clock/participation history bit-identically.

      One caveat to the value semantics: the compiled steps DONATE the
      input state's device buffers (the peak-memory contract of the
      batched/scan backends), so passing a state into
      run/run_round/run_chunk/run_fleet CONSUMES it — always rebind to
      the returned state; a reused input fails with JAX's
      deleted-buffer error. To branch several runs off one state,
      snapshot it first: `jax.device_get(state)` (host copies are
      re-uploaded, never donated away from you) or
      `save_state`/`load_state`.

  `repro.federated.experiment.ExperimentSpec` — a frozen declarative
      description (model, data/partition, population, wireless,
      plan-or-fed, scenario, compression, backend) whose `build()`
      returns a `Simulator`; replaces hand-wiring this constructor at
      every call site.

`FLSimulation` remains as a thin deprecated shim (one `DeprecationWarning`
per process) holding a (Simulator, SimState) pair behind the old mutable
interface.

Three execution backends share the same math:

  backend='scan' (default): an entire `eval_every`-round chunk is one
      compiled `jax.lax.scan` over the batched round step
      (mesh_rounds.build_round_chunk). The host touches the device once
      per chunk — scenario masks/clocks ride in as stacked (R, C) arrays
      (ScenarioStream.draw_chunk), batches either pre-stack to
      (R, C, V, ...) or, when the client iterators share one dataset
      (data.BatchIterator), stay device-resident and are gathered
      in-graph from (R, C, V, B) index arrays — and per-round metrics
      come back as stacked scan outputs in a single device_get. Carry
      buffers (params/opt/PRNG key) are donated across chunks; ragged
      final chunks are padded under a `valid` flag so a whole run costs
      exactly one trace (Simulator.trace_count).
  backend='batched': all M clients live on a stacked leading C axis and
      one jit-compiled round step (mesh_rounds.build_round_step) runs V
      vmapped local steps + weighted FedAvg + optional in-graph int8
      stochastic quantization per round — one dispatch and one host
      batch-feed per round. Host syncs happen only at `eval_every`
      boundaries — train losses stay on device in between. Kept as the
      per-round parity reference for 'scan' (bit-identical under a fixed
      seed — tests/test_scan_backend.py).
  backend='loop': the original per-client Python loop (one jitted
      local_update dispatch per client, host-side compress/decompress
      roundtrip, per-client host sync). Kept as the reference
      implementation; backends agree to fp32 tolerance under a fixed
      seed (bit-for-bit on the quantizer noise — see
      compression.sequential_client_keys).

Each `Simulator.run` call is one profiler span, `defl.run`. Inside it
the chunked drivers ('scan' and 'async') mark their host steps:
`defl.materialize`, `defl.chunk_inputs`, `defl.dispatch`, `defl.fetch`,
`defl.records`, `defl.eval` and `defl.snapshot`. The spans cost nothing
unless a profiler is running (`jax.profiler.trace`).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import pickle
import tempfile
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import FedConfig, WirelessConfig
from repro.core import delay
from repro.federated import compression, mesh_rounds, scenarios
from repro.federated.faults import DivergenceError, FaultModel, RecoveryPolicy
from repro.federated.client import (
    client_round,
    make_local_update,
    stack_batches,
    stack_chunk_batches,
    stack_chunk_indices,
    stack_client_batches,
    stack_cohort_batches,
    stack_cohort_indices,
)
from repro.federated.server import aggregate_updates
from repro.optim.api import Optimizer
from repro.utils.tree import tree_bytes


@dataclass
class RoundRecord:
    round: int
    sim_time: float  # cumulative simulated seconds (Eq. 8 accumulated)
    T_cm: float
    T_cp: float
    train_loss: float  # may hold a device scalar until the next host sync
    test_acc: Optional[float] = None
    test_loss: Optional[float] = None
    # Scenario rounds: how many client updates reached the aggregator
    # (None on the no-scenario path — implicitly all M).
    n_participants: Optional[int] = None
    # Total uplink bits the round actually carried (participants x bits
    # per update, exact compression.compressed_bits accounting).
    uplink_bits: Optional[float] = None
    # Quorum gate (faults.FaultModel.min_quorum): True when this round's
    # participation fell below quorum. Under quorum_policy='reject' the
    # round's params/opt update was a no-op and sim_time additionally
    # paid the re-dispatch cost. None on quorum-less runs.
    rejected: Optional[bool] = None


@dataclass
class SimResult:
    history: List[RoundRecord]
    params: Any
    label: str
    fed: FedConfig
    # Auto-recovery audit trail (Simulator.run(recovery=...)): one dict
    # per restart — attempt, offending/resume rounds, the cumulative lr
    # scale and guard norm applied, and the error message. Empty on runs
    # that never diverged.
    restarts: List[dict] = field(default_factory=list)

    @property
    def total_time(self) -> float:
        return self.history[-1].sim_time if self.history else 0.0

    @property
    def rounds(self) -> int:
        return len(self.history)

    @property
    def rounds_rejected(self) -> int:
        """Rounds the quorum gate rejected (0 on quorum-less runs)."""
        return sum(1 for r in self.history if r.rejected)

    def time_to_accuracy(self, acc: float) -> Optional[float]:
        for r in self.history:
            if r.test_acc is not None and r.test_acc >= acc:
                return r.sim_time
        return None


# ---------------------------------------------------------------------------
# SimState: the immutable run state
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SimState:
    """Everything a run mutates, as one immutable value.

    Device leaves (pytree children — what `run_fleet` stacks and vmaps,
    and what `jax.device_get` materializes):
      params_C  stacked (C, ...) client params ('batched'/'scan'); the
                plain global param tree on 'loop'
      opt_C     stacked per-client optimizer state ('batched'/'scan'); a
                tuple of per-client states on 'loop'
      key       the run's PRNG key (compression noise schedule)

    Host fields (pytree aux data — position of the host-side streams):
      seed      the seed `Simulator.init` was called with; rebuilds the
                data iterators / scenario stream that `data` / `stream`
                snapshots are restored into
      round     global round cursor (continues across run() calls — a
                resumed run numbers its history after the saved one)
      sim_time  cumulative Eq. 8 simulated seconds
      stream    ScenarioStream.state() snapshot. None = "fresh at
                `seed`": a freshly-seeded stream with no fast-forward
                (initial states; also any scenario-less sim).
      data      per-client BatchIterator.state() snapshots. None =
                "factory-fresh at `seed`" (initial states), and also
                what a post-run state stores when the iterators don't
                expose the snapshot protocol (then the data source is
                assumed stateless/deterministic).

    Asynchronous backend (backend='async') extension — all None/0 on the
    synchronous backends, so sync states flatten/signature/checkpoint
    exactly as before:
      async_c     the device-side event-queue carry (a 4th pytree child):
                  global model, staleness-weighted buffer, per-client
                  finish times / dispatch versions / drop flags — see
                  mesh_rounds.build_async_chunk. Mid-buffer states
                  checkpoint/resume bit-identically because the whole
                  pending-update structure lives here.
      event       arrival-event cursor (host int): how many events the
                  run has consumed (state.round counts AGGREGATIONS).
      async_host  f64 dispatch bookkeeping for the history records
                  {'t_cm_disp' (C,), 'attempts_disp' (C,)}: each
                  in-flight update's effective uplink seconds and
                  attempt count, fixed at its dispatch.

    States are produced by `Simulator.init` and threaded through
    state-in/state-out methods; `save_state`/`load_state` round-trip one
    through disk for checkpoint/resume.

    NOTE: the value is immutable, but its device buffers are donated to
    the compiled step — a state passed into run/run_round/run_chunk/
    run_fleet is consumed. Rebind to the returned state; to keep a
    branch point, take a host snapshot first (`jax.device_get(state)`
    or `save_state`).

    Pytree support is intentionally shallow: the host fields live in
    aux_data (so `jax.device_get`, `tree.map` over ONE state, and
    serialization work), but aux holds numpy-laden snapshot dicts —
    multi-tree ops (`tree.map(f, state_a, state_b)`) and passing a
    SimState across a jit boundary are unsupported; operate on
    `(params_C, opt_C, key)` directly for that.
    """

    params_C: Any
    opt_C: Any
    key: Any
    seed: int = 0
    round: int = 0
    sim_time: float = 0.0
    stream: Optional[dict] = None
    data: Optional[tuple] = None
    async_c: Optional[Any] = None
    event: int = 0
    async_host: Optional[dict] = None


def _simstate_flatten(s: SimState):
    # async_c joins the device children (None is an empty subtree, so a
    # synchronous state's treedef carries no extra leaves).
    return ((s.params_C, s.opt_C, s.key, s.async_c),
            (s.seed, s.round, s.sim_time, s.stream, s.data, s.event,
             s.async_host))


def _simstate_unflatten(aux, children):
    params_C, opt_C, key, async_c = children
    seed, rnd, sim_time, stream, data, event, async_host = aux
    return SimState(params_C=params_C, opt_C=opt_C, key=key, seed=seed,
                    round=rnd, sim_time=sim_time, stream=stream, data=data,
                    async_c=async_c, event=event, async_host=async_host)


jax.tree_util.register_pytree_node(
    SimState, _simstate_flatten, _simstate_unflatten)


# Checkpoint schema version: bump when the on-disk payload layout changes.
_STATE_VERSION = 1


def _state_signature(state: SimState) -> tuple:
    """Shape signature of a state's device trio: the (params, opt, key)
    treedef plus every leaf's shape/dtype. Pure metadata — np.shape and
    .dtype never transfer device buffers — so it is cheap to compute at
    save AND load and catches a checkpoint fed to the wrong spec (or a
    truncated/corrupt payload) before JAX hits a cryptic unflatten or
    donation shape error deep in the first compiled step."""
    trio = (state.params_C, state.opt_C, state.key)
    if getattr(state, "async_c", None) is not None:
        # Async states append the event-queue carry; synchronous states
        # keep the historical 3-tuple signature byte-identical, so every
        # pre-async checkpoint still validates.
        trio = trio + (state.async_c,)
    treedef = str(jax.tree.structure(trio))
    leaves = tuple(
        (tuple(np.shape(x)), str(getattr(x, "dtype", type(x).__name__)))
        for x in jax.tree.leaves(trio))
    return (treedef, leaves)


def _atomic_pickle(path: str, payload: Any) -> None:
    """Crash-safe pickle write: serialize into a temp file in the
    TARGET's directory (os.replace must not cross filesystems), fsync,
    then atomically rename into place. A kill at any instant leaves
    either the previous file or none — never a torn pickle that would
    surface as a confusing UnpicklingError instead of the versioned-
    envelope ValueError."""
    d = os.path.dirname(os.path.abspath(path))
    fd, tmp = tempfile.mkstemp(
        dir=d, prefix=os.path.basename(path) + ".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            pickle.dump(payload, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def save_state(path: str, state: SimState) -> None:
    """Checkpoint a SimState: device leaves are fetched with
    `jax.device_get` and the whole value (host stream/iterator snapshots
    included) is serialized under a versioned envelope carrying the
    state's shape signature, written crash-safely (temp file + fsync +
    atomic rename — `_atomic_pickle`). `load_state` + `Simulator.run`
    continues the run bit-identically (tests/test_checkpoint_resume.py)."""
    host = jax.device_get(state)
    payload = {"__repro_simstate__": _STATE_VERSION,
               "signature": _state_signature(host),
               "state": host}
    _atomic_pickle(path, payload)


def load_state(path: str, like: Optional[SimState] = None) -> SimState:
    """Restore a `save_state` checkpoint. Leaves come back as numpy; the
    first compiled step re-uploads them (and re-donates from there).

    The payload is validated up front — schema version, held type, and
    the saved shape signature against the actual leaves — so corruption
    or a version skew fails here with a clear ValueError instead of as a
    pytree/unflatten failure deep in JAX. Pass `like=` (any SimState from
    the target Simulator, e.g. `sim.init()`) to additionally verify the
    checkpoint matches that simulator's shapes before running it.
    Legacy raw-pickle checkpoints (pre-envelope) still load; checkpoints
    written before the async backend existed (no async_c/event fields in
    the pickled dataclass) are fixed up with the synchronous defaults."""
    try:
        with open(path, "rb") as f:
            payload = pickle.load(f)
    except (pickle.UnpicklingError, EOFError, AttributeError) as e:
        raise ValueError(
            f"{path!r} is not a readable checkpoint "
            f"(corrupt or truncated pickle): {e}") from e
    if isinstance(payload, SimState):  # legacy: raw SimState pickle
        state = payload
    elif isinstance(payload, dict) and "__repro_simstate__" in payload:
        version = payload["__repro_simstate__"]
        if version != _STATE_VERSION:
            raise ValueError(
                f"{path!r} holds checkpoint schema v{version}, this build "
                f"reads v{_STATE_VERSION} — re-save the state with this "
                "version (or load it with the matching build)")
        state = payload.get("state")
        if not isinstance(state, SimState):
            raise ValueError(f"{path!r} does not hold a SimState")
        sig = payload.get("signature")
        if sig is not None and sig != _state_signature(state):
            raise ValueError(
                f"{path!r} is corrupt: its stored shape signature does not "
                "match the payload's leaves")
    else:
        raise ValueError(f"{path!r} does not hold a SimState")
    if not hasattr(state, "async_c"):
        # Pre-async checkpoint: pickle restored the old dataclass __dict__
        # (bypassing __init__), so the new fields are absent entirely —
        # install the synchronous defaults so dataclasses.replace and the
        # pytree flatten see a complete instance.
        object.__setattr__(state, "async_c", None)
        object.__setattr__(state, "event", 0)
        object.__setattr__(state, "async_host", None)
    if like is not None:
        want, got = _state_signature(like), _state_signature(state)
        if want != got:
            raise ValueError(
                f"checkpoint {path!r} was saved from a different spec: its "
                "(params, opt, key) shape signature does not match the "
                "target simulator's states")
    return state


@dataclass
class FleetResult:
    """`run_fleet` output: per-member final states and SimResults, in
    input order (member s = seed/state s)."""

    states: List[SimState]
    results: List[SimResult]

    def __len__(self) -> int:
        return len(self.results)

    def __iter__(self):
        return iter(self.results)

    def loss_history(self) -> np.ndarray:
        """(S, R) train-loss matrix across the fleet."""
        return np.asarray(
            [[r.train_loss for r in res.history] for res in self.results])

    def total_times(self) -> np.ndarray:
        return np.asarray([res.total_time for res in self.results])

    def summary(self) -> Dict[str, float]:
        """Mean/std over the fleet of final train loss and overall time —
        the confidence-band numbers multi-seed FL papers report."""
        losses = self.loss_history()[:, -1]
        times = self.total_times()
        return {"final_loss_mean": float(np.nanmean(losses)),
                "final_loss_std": float(np.nanstd(losses)),
                "total_time_mean": float(times.mean()),
                "total_time_std": float(times.std())}


@functools.partial(jax.jit, static_argnums=1)
def _unstack_members(tree, S: int):
    """Split stacked (S, ...) fleet buffers into S per-member trees in ONE
    compiled dispatch (eager per-member indexing costs S x leaves separate
    device ops — measurable against a whole fleet chunk)."""
    return tuple(
        jax.tree.map(lambda x, s=s: x[s], tree) for s in range(S))


def _validate_run_args(max_rounds: int, eval_every: int) -> None:
    """Up-front validation on every backend (no silent clamping)."""
    if not isinstance(max_rounds, (int, np.integer)) or max_rounds < 1:
        raise ValueError(
            f"max_rounds must be an int >= 1, got {max_rounds!r}")
    if not isinstance(eval_every, (int, np.integer)) or eval_every < 1:
        raise ValueError(
            f"eval_every must be an int >= 1, got {eval_every!r}")


def _scaled_optimizer(opt: Optimizer, scale: float) -> Optimizer:
    """`opt` with every update scaled by `scale` — exact learning-rate
    backoff for SGD-family optimizers (updates are linear in lr), used by
    the recovery path (`RecoveryPolicy.lr_backoff`). Deterministic: the
    scale is a compiled constant of the restarted run's graphs."""
    s = jnp.float32(scale)

    def update(grads, state, params=None):
        updates, state = opt.update(grads, state, params)
        return jax.tree.map(lambda u: u * s, updates), state

    return Optimizer(init=opt.init, update=update)


# ---------------------------------------------------------------------------
# Simulator: the pure functional core
# ---------------------------------------------------------------------------


class Simulator:
    """One FL system: M clients with data + a delay model, as pure
    state-in/state-out methods over `SimState`.

    `data` is either a list of per-client batch iterators (shared, legacy
    style) or a factory `seed -> list of iterators` — the factory form is
    what makes `init(seed)` / `run_fleet(seeds=...)` give every member its
    own independently-seeded data stream. Everything else (population,
    wireless, compiled step functions, the device-resident dataset upload)
    is immutable and shared across all states and fleet members.
    """

    def __init__(
        self,
        loss_fn: Callable,  # (params, batch) -> (loss, metrics)
        init_params: Any,
        data: Any,  # List[iterator] | Callable[[int], List[iterator]]
        data_sizes: np.ndarray,  # D_m
        fed: FedConfig,
        opt: Optimizer,
        pop: delay.DevicePopulation,
        wireless: Optional[WirelessConfig] = None,
        eval_fn: Optional[Callable] = None,  # (params) -> {'acc','loss'}
        label: str = "defl",
        backend: str = "scan",
        impl: str = "xla",  # quantize kernel: 'xla' | 'pallas'
        scenario: Optional[Any] = None,  # scenarios.Scenario | name | None
        eval_batch_fn: Optional[Callable] = None,  # stacked (S,...) params
        masked_loss_fn: Optional[Callable] = None,  # (p, batch, mask, n)
        envelope_key: Optional[Any] = None,  # study.py graph-cache key
        faults: Optional[FaultModel] = None,  # fault/recovery overlay
        cohort: Optional[int] = None,  # K-client sampled participation
        cohort_sampler: str = "uniform",  # 'uniform' | 'weighted' (by D_m)
        cohort_spare: int = 0,  # over-provisioned candidates per round
        shard_clients: bool = False,  # shard the client axis over devices
        async_spec: Optional[Any] = None,  # events.AsyncSpec (backend='async')
    ):
        """eval_batch_fn evaluates a whole stacked member axis at once —
        (S, ...) param leaves -> dict of (S,) metrics — so fleet/study
        time-to-accuracy sweeps don't serialize on a host eval loop at
        chunk boundaries. masked_loss_fn is the (V, b)-envelope form of
        loss_fn (see mesh_rounds.envelope_local_steps_fn) and
        envelope_key a hashable graph signature; both are optional
        capabilities the Study API (federated/study.py) uses to group
        this simulator's arm with others — ExperimentSpec.build provides
        all three.

        `faults` overlays a faults.FaultModel on the scenario (deadline-
        bounded rounds, uplink retransmission with backoff, crash/rejoin
        lifecycle, divergence guards — see the faults module). A
        fault-bearing scenario (e.g. the registered 'unreliable_edge')
        works without this argument; the explicit kwarg layers faults on
        any scenario — including none, which overlays onto 'uniform' so
        the realization stream exists. An inactive FaultModel is ignored
        entirely: the compiled graphs, RNG streams and accounting are
        bit-identical to not passing one.

        `cohort=K` turns on sampled participation: each round a K-client
        cohort is drawn from the M-client population (uniformly, or
        D_m-weighted with cohort_sampler='weighted') and only its members
        compute/upload. Device client-state shrinks to O(K) — the stacked
        params/opt carry K lanes, re-initialized from the global model
        every round (FedAvg broadcasts it, so this is automatic for
        params; the local optimizer must be stateless) — while the
        population model (data partitions, scenario masks, channel
        state) stays O(M) host-side. K = M runs the sampled machinery
        over the full population and is bit-identical to the dense path.

        `shard_clients=True` shards the stacked client axis over all
        JAX devices (scan backend): FedAvg aggregation becomes a
        shard_map psum (mesh_rounds._psum_shardmap_sync). Prototype on
        CPU via XLA_FLAGS=--xla_force_host_platform_device_count=N."""
        # Original constructor arguments, captured before any overlay/
        # promotion below mutates the derived views: the recovery path
        # (_recovery_variant) rebuilds a near-identical Simulator from
        # these with only the optimizer scale / guard norm changed.
        self._ctor = dict(
            loss_fn=loss_fn, init_params=init_params, data=data,
            data_sizes=data_sizes, fed=fed, opt=opt, pop=pop,
            wireless=wireless, eval_fn=eval_fn, label=label,
            backend=backend, impl=impl, scenario=scenario,
            eval_batch_fn=eval_batch_fn, masked_loss_fn=masked_loss_fn,
            envelope_key=envelope_key, faults=faults, cohort=cohort,
            cohort_sampler=cohort_sampler, cohort_spare=cohort_spare,
            shard_clients=shard_clients, async_spec=async_spec)
        if backend not in ("scan", "batched", "loop", "async"):
            raise ValueError(f"unknown backend {backend!r}")
        if backend == "async" and async_spec is None:
            raise ValueError(
                "backend='async' needs an aggregation policy — pass "
                "async_spec=events.AsyncSpec(buffer_size=K, ...)")
        if (async_spec is not None
                and async_spec.buffer_size > fed.n_devices):
            raise ValueError(
                f"AsyncSpec.buffer_size ({async_spec.buffer_size}) must "
                f"not exceed n_devices ({fed.n_devices}): accepted "
                "updates block their client until the consuming "
                "aggregation, so a buffer larger than the population "
                "could never fill")
        if async_spec is not None and backend != "async":
            raise ValueError(
                f"async_spec is only meaningful with backend='async' "
                f"(got backend={backend!r}) — drop it or switch backends")
        if backend == "async":
            if cohort is not None:
                raise ValueError(
                    "backend='async' and cohort=K (sampled participation) "
                    "are mutually exclusive: the event queue already "
                    "schedules per-client work continuously, so there is "
                    "no per-round cohort to draw. Drop cohort (every "
                    "client stays in flight) or use backend='scan'.")
            if shard_clients:
                raise ValueError(
                    "backend='async' and shard_clients are mutually "
                    "exclusive: the event scan runs ONE client per event "
                    "(nothing to shard over a client mesh). Drop "
                    "shard_clients or use backend='scan'.")
        self._async = async_spec if backend == "async" else None
        if cohort_sampler not in ("uniform", "weighted"):
            raise ValueError(
                f"unknown cohort_sampler {cohort_sampler!r}; "
                "expected 'uniform' or 'weighted'")
        if cohort is not None:
            if backend == "loop":
                raise ValueError(
                    "cohort (sampled participation) requires backend "
                    "'scan' or 'batched' — the loop reference is dense-only")
            if not 1 <= int(cohort) <= pop.n:
                raise ValueError(
                    f"cohort must be in [1, {pop.n}], got {cohort}")
        self._cohort = None if cohort is None else int(cohort)
        self._sampled = self._cohort is not None
        if not isinstance(cohort_spare, (int, np.integer)) or cohort_spare < 0:
            raise ValueError(
                f"cohort_spare must be an int >= 0, got {cohort_spare!r}")
        if cohort_spare and not self._sampled:
            raise ValueError(
                "cohort_spare (over-provisioned cohorts) requires sampled "
                "participation — pass cohort=K as well")
        if self._sampled and self._cohort + int(cohort_spare) > pop.n:
            raise ValueError(
                f"cohort + cohort_spare ({cohort} + {cohort_spare}) must "
                f"not exceed the population size {pop.n}")
        self._spare = int(cohort_spare)
        # Candidate-draw width: each round draws K + spare candidates and
        # keeps the K deadline-feasible-fastest (_select_cohorts).
        self._cohort_draw = (
            None if self._cohort is None else self._cohort + self._spare)
        self._cohort_weights = (
            np.asarray(np.asarray(data_sizes), np.float64)
            if (self._sampled and cohort_sampler == "weighted") else None)
        self.loss_fn = loss_fn
        self._data_src = data
        self.data_sizes = data_sizes
        self.fed = fed
        self.opt = opt
        self.pop = pop
        self.wireless = wireless or WirelessConfig()
        self.eval_fn = eval_fn
        self.eval_batch_fn = eval_batch_fn
        self.masked_loss_fn = masked_loss_fn
        self.envelope_key = envelope_key
        self.label = label
        self.backend = backend
        self.impl = impl
        self.scenario = scenarios.get(scenario) if scenario is not None else None
        if faults is not None and faults.active:
            base = self.scenario or scenarios.get("uniform")
            self.scenario = base.replace(faults=faults)
        if self._sampled and self.scenario is None:
            # Cohort draws live on the ScenarioStream: promote to the
            # neutral 'uniform' scenario so the stream exists (same
            # pattern as the faults overlay above).
            self.scenario = scenarios.get("uniform")
        if self._async is not None and self.scenario is None:
            # The event queue draws per-dispatch service times from the
            # realization stream — promote like the sampled path does.
            self.scenario = scenarios.get("uniform")
        fm = self.scenario.faults if self.scenario is not None else None
        self._faults = fm if (fm is not None and fm.active) else None
        self._guard = None
        if self._faults is not None:
            self._faults.validate()
            g = self._faults.guard_spec()
            # A trivial guard (no clipping, no rejection) builds no ops at
            # all — the graph stays byte-identical to the guard-less one.
            self._guard = None if (g[0] == float("inf") and not g[1]) else g
        # Quorum gate: resolved to an absolute participant count against
        # the round's cohort size (K when sampled, M dense). None when no
        # quorum is configured — then NO quorum ops/inputs are built and
        # the compiled graphs stay byte-identical to a quorum-less sim.
        self._quorum = self._quorum_policy = None
        if self._faults is not None:
            q = self._faults.resolve_quorum(
                self._cohort if self._sampled else fed.n_devices)
            if q is not None:
                self._quorum = q
                self._quorum_policy = self._faults.quorum_policy
        if self._async is not None and self._quorum is not None:
            raise ValueError(
                "backend='async' and FaultModel.min_quorum are mutually "
                "exclusive: the buffered server aggregates whenever "
                "buffer_size updates arrive — there is no per-round "
                "participant count to gate. Drop min_quorum from the "
                "FaultModel (AsyncSpec.buffer_size IS the async quorum) "
                "or use backend='scan'.")
        if (self._async is not None and self._faults is not None
                and self._faults.max_update_norm is not None):
            raise ValueError(
                "backend='async' and FaultModel.max_update_norm are "
                "mutually exclusive: update sanitation runs at the sync "
                "round step's participant axis, which the event scan "
                "does not have. Drop max_update_norm or use "
                "backend='scan'. (The always-on defaults "
                "reject_nonfinite/divergence_guard are round-level "
                "guards and are inert on the async backend.)")
        # Envelope-form graphs: when the masked loss is available, the
        # compiled batched/scan graphs run mesh_rounds' (V, b)-envelope
        # round step at the TRIVIAL envelope (V_env=V, B_env=b, all-ones
        # masks as traced inputs). The masking ops change XLA's fusion of
        # the loss computation by an ulp relative to the plain form, and
        # fusion follows op structure, not mask values — so sharing the
        # structure is what makes a native run() bit-identical to the same
        # arm running padded inside a Study group (observed: padded ==
        # trivial-envelope bit-for-bit; plain == neither). The loop
        # backend keeps the plain loss (its parity is tolerance-based).
        # (The async event scan runs one client per event — there is no
        # member axis to envelope-pad, so async arms run solo in a Study.)
        self._envelope = (masked_loss_fn is not None
                          and backend not in ("loop", "async"))
        self._env_cache: Optional[dict] = None
        probe = self._make_iters(fed.seed)
        assert len(probe) == fed.n_devices == pop.n
        if hasattr(probe, "client") and not self._sampled:
            raise ValueError(
                "a ClientDataPool data source requires cohort sampling "
                "(cohort=K) — the dense backends stack every client's "
                "batches, which is exactly what the pool exists to avoid")
        self._init_params = jax.tree.map(jnp.asarray, init_params)
        if self._sampled and jax.tree.leaves(opt.init(self._init_params)):
            raise ValueError(
                "sampled participation carries no per-client optimizer "
                "state between rounds (cohort lanes change owners every "
                "round; clients re-initialize from the global model) — "
                "use a stateless local optimizer (plain SGD)")
        if (self._async is not None
                and jax.tree.leaves(opt.init(self._init_params))):
            raise ValueError(
                "backend='async' re-dispatches every client from the "
                "current global model, so per-client optimizer state "
                "carried across stale dispatches is ill-defined — use a "
                "stateless local optimizer (plain SGD)")
        # Sharded client axis: FedAvg aggregation as a shard_map psum
        # over a 1-D 'clients' device mesh.
        self._mesh = self._param_specs = self._carry = None
        self.shard_clients = bool(shard_clients)
        if shard_clients:
            if backend != "scan":
                raise ValueError(
                    f"shard_clients requires backend='scan', not {backend!r}")
            if fed.compress_updates:
                raise ValueError(
                    "shard_clients with compress_updates is unsupported: "
                    "the int8 quantizer uses its own aggregation path")
            n_dev = jax.device_count()
            C = self._cohort if self._sampled else fed.n_devices
            if C % n_dev:
                raise ValueError(
                    f"client axis ({C} lanes) must divide evenly over the "
                    f"{n_dev} available devices")
            self._mesh = jax.sharding.Mesh(
                np.array(jax.devices()), ("clients",))
            spec = jax.sharding.PartitionSpec("clients")
            self._param_specs = jax.tree.map(
                lambda _: spec, self._init_params)
            # Where the scan chunk's carry lives, in and out: params/opt
            # lanes split over 'clients', the key replicated.
            lanes = jax.sharding.NamedSharding(self._mesh, spec)
            self._carry = (lanes, lanes, jax.sharding.NamedSharding(
                self._mesh, jax.sharding.PartitionSpec()))
        # Static per-client compute times (Eq. 4); uplink times depend on
        # the realized per-round channel and are computed per round.
        self._t_cp_clients = delay.per_client_compute_time(
            fed.batch_size, pop.G, pop.f)
        # Host f32 twin of the FedAvg size-weight vector: the sampled path
        # gathers per-round (R, K) cohort rows from it instead of
        # uploading M-sized arrays per chunk. The cast matches the dense
        # path's jnp.float32 conversion exactly, so a gathered K=M row is
        # bit-identical to the dense chunk constant.
        self._sizes_host = np.asarray(np.asarray(data_sizes), np.float32)
        # Shape-only view of the global model: _update_bits computes wire
        # sizes from this, so delay accounting never dispatches a device op
        # or blocks the async queue (see the _update_bits docstring).
        self._param_struct = jax.eval_shape(lambda p: p, init_params)
        self._bits_cache: Optional[float] = None
        # Round deadline in simulated seconds: a `deadline_factor` resolves
        # against THIS sim's nominal full-population Eq. 8 round time, so
        # the same FaultModel ports across models/populations.
        self._deadline = None
        if self._faults is not None:
            nominal = delay.round_time(*self.round_times(), fed.local_rounds)
            self._deadline = self._faults.resolve_deadline(nominal)
        self._fleet_fn = None
        self._fleet_base = None
        if backend == "loop":
            self.local_update = make_local_update(loss_fn, opt)
        elif backend == "async":
            # The event scan renormalizes size weights in-graph per
            # aggregation; only the raw sizes ship.
            self._sizes_f32 = jnp.asarray(np.asarray(data_sizes), jnp.float32)
        else:
            w = jnp.asarray(np.asarray(data_sizes), jnp.float32)
            # Legacy path: host-normalized FedAvg weights. The scenario path
            # instead ships the raw sizes and renormalizes in-graph over the
            # round's participation mask (mesh_rounds._participation_weights).
            self._weights = w / jnp.sum(w)
            self._sizes_f32 = w
            self._round_fn = self._build_batched_round()
        if backend == "scan":
            self._detect_device_data(probe)
            self._t_cp_dev = jnp.asarray(self._t_cp_clients, jnp.float32)
            self._chunk_raw = self._build_scan_chunk()
            # Same donation contract as the batched round step, amortized
            # over a whole chunk: XLA reuses the carry buffers across
            # chunks. All per-chunk inputs are traced arrays of fixed
            # (R, ...) shape and a ragged final chunk pads to R under the
            # valid flag, so a whole run compiles exactly once.
            self._chunk_fn = jax.jit(self._chunk_raw, donate_argnums=(0, 1, 2))
        if backend == "async":
            from repro.federated import events as _events

            self._events_mod = _events
            self._detect_device_data(probe)
            # Static per-chunk event budget E: every chunk pads its event
            # axis to E (the ragged-tail trick on the event axis), so one
            # trace serves the whole run. The default covers several full
            # population turnovers (or buffer fills) per dispatch.
            self._async_E = int(
                self._async.event_budget
                if self._async.event_budget is not None
                else 8 * max(fed.n_devices, self._async.buffer_size))
            self._chunk_raw = mesh_rounds.build_async_chunk(
                loss_fn, self.opt, fed.local_rounds, fed.n_devices,
                self._async, impl=self.impl, batch_from=self._batch_from,
                compress=fed.compress_updates)
            # params/opt/key AND the async carry are donated: the event
            # queue's finish-time/buffer leaves reuse their buffers across
            # chunks exactly like the sync carry trio.
            self._chunk_fn = jax.jit(
                self._chunk_raw, donate_argnums=(0, 1, 2, 3))

    def _detect_device_data(self, its) -> None:
        """Device-resident data path: when every client iterator draws
        from one shared dataset and speaks the index protocol
        (data.BatchIterator), upload the backing arrays once and gather
        batches in-graph — per chunk only int32 index arrays cross the
        host->device boundary. Anything else falls back to pre-stacked
        host batches per chunk."""
        self._data_dev = self._batch_from = None
        if hasattr(its, "client"):  # ClientDataPool: one shared dataset
            self._data_dev = jax.tree.map(
                jnp.asarray, its.device_arrays())
            self._batch_from = its.batch_from
        elif (its
                and all(hasattr(it, "next_indices")
                        and hasattr(it, "device_arrays") for it in its)
                and getattr(its[0], "data", None) is not None
                and len({id(getattr(it, "data", None))
                         for it in its}) == 1):
            self._data_dev = jax.tree.map(
                jnp.asarray, its[0].device_arrays())
            self._batch_from = type(its[0]).batch_from

    # -- state construction -------------------------------------------------
    def init(self, seed: Optional[int] = None) -> SimState:
        """A fresh run state at `seed` (default: fed.seed): replicated
        client params/opt, PRNGKey(seed), round 0, clock 0, and the
        seed's scenario-stream / data-iterator start positions.

        Data-stream caveat for the legacy fixed-list form: when the
        Simulator was built with a list of live iterators (instead of a
        `seed -> iterators` factory), `seed` cannot reseed the data —
        init() snapshots the shared iterators' CURRENT position, so a
        second init() after a run starts where the run left off (the
        deprecated FLSimulation's semantics, which constructs one state
        per instance). For reproducible multi-state/multi-seed work,
        build with a factory (ExperimentSpec does)."""
        seed = int(self.fed.seed if seed is None else seed)
        M = self.fed.n_devices
        # Sampled participation: the stacked device state carries K cohort
        # lanes, not M clients — O(K) regardless of population size.
        C = self._cohort if self._sampled else M
        if self.backend == "loop":
            params = self._init_params
            opt_C: Any = tuple(self.opt.init(params) for _ in range(M))
        else:
            params = mesh_rounds.replicate_clients(self._init_params, C)
            opt_C = jax.vmap(
                lambda _: self.opt.init(self._init_params))(jnp.arange(C))
        if self.backend == "async":
            # The initial dispatch hands every client version-0 work at
            # t=0, which consumes ONE realization draw — so the stream
            # position is snapshotted into the state here (unlike the
            # sync backends' "factory-fresh" None).
            stream = self.scenario.stream(self.pop, seed)
            t_svc0, drop0, t_cm0, att0 = self._async_dispatch_draw(stream)
            async_c = {
                "params_g": jax.tree.map(lambda x: x.copy(),
                                         self._init_params),
                "buf": jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32),
                    self._init_params),
                "buf_w": jnp.float32(0.0),
                "cnt": jnp.int32(0),
                "loss_sum": jnp.float32(0.0),
                "t_finish": jnp.asarray(t_svc0),
                "t_next": jnp.zeros(C, jnp.float32),
                "now": jnp.float32(0.0),
                "version": jnp.int32(0),
                "version_C": jnp.zeros(C, jnp.int32),
                "drop_C": jnp.asarray(drop0),
            }
            async_host = {"t_cm_disp": np.asarray(t_cm0, np.float64),
                          "attempts_disp": np.asarray(att0, np.float64),
                          "bits_acc": 0.0}
            return SimState(params_C=params, opt_C=opt_C,
                            key=jax.random.PRNGKey(seed), seed=seed,
                            stream=stream.state(), async_c=async_c,
                            async_host=async_host)
        # stream/data stay None — "factory-fresh at `seed`", which is
        # exactly what _materialize constructs with no fast-forward, so
        # init() never has to build (and immediately discard) the
        # iterators/stream just to snapshot their start position.
        return SimState(params_C=params, opt_C=opt_C,
                        key=jax.random.PRNGKey(seed), seed=seed)

    def _make_iters(self, seed: int):
        src = (self._data_src(seed) if callable(self._data_src)
               else self._data_src)
        # A ClientDataPool is one lazy object, not a per-client list.
        return src if hasattr(src, "client") else list(src)

    @staticmethod
    def _snapshot_iters(iters) -> Optional[Any]:
        if hasattr(iters, "client"):  # ClientDataPool: O(touched clients)
            return iters.state()
        if all(hasattr(it, "state") and hasattr(it, "set_state")
               for it in iters):
            return tuple(it.state() for it in iters)
        return None

    @staticmethod
    def _restore_iters(iters, snap) -> None:
        if hasattr(iters, "client"):
            iters.set_state(snap)
        else:
            for it, s in zip(iters, snap):
                it.set_state(s)

    def _materialize(self, state: SimState):
        """Live host-side streams positioned at `state`: data iterators
        (factory-fresh, then fast-forwarded from the state's snapshots)
        and the scenario realization stream (cohort-configured when
        sampled — its snapshot carries the cohort-RNG cursor too)."""
        iters = self._make_iters(state.seed)
        if state.data is not None:
            self._restore_iters(iters, state.data)
        stream = None
        if self.scenario is not None:
            stream = self.scenario.stream(
                self.pop, state.seed, cohort_size=self._cohort_draw,
                cohort_weights=self._cohort_weights)
            if state.stream is not None:
                stream.set_state(state.stream)
        return iters, stream

    def _rebuild_state(self, state, params_C, opt_C, key, rnd, sim_time,
                       iters, stream, **extra) -> SimState:
        return dataclasses.replace(
            state, params_C=params_C, opt_C=opt_C, key=key, round=int(rnd),
            sim_time=float(sim_time),
            stream=stream.state() if stream is not None else None,
            data=self._snapshot_iters(iters), **extra)

    # -- state views --------------------------------------------------------
    def params(self, state: SimState) -> Any:
        """The global model in `state` (post-aggregation every client row
        is equal, so row 0 of the stacked state is the global model; the
        async backend carries it explicitly — client rows are dispatch
        snapshots that differ between aggregations)."""
        if self.backend == "loop":
            return state.params_C
        if self.backend == "async":
            return state.async_c["params_g"]
        return jax.tree.map(lambda x: x[0], state.params_C)

    @staticmethod
    def block_until_ready(state: SimState) -> None:
        """Drain the async dispatch queue (benchmarking / checkpoint use)."""
        jax.block_until_ready(state.params_C)

    @property
    def trace_count(self) -> int:
        """Number of compiled traces so far (batched: the round step; scan:
        the chunk step plus any direct run_round calls; +1 once a fleet fn
        is compiled). Scenario masking and chunking must stay at 1 across
        a run — per-round masks, delay inputs and the ragged-final-chunk
        padding are traced values, never new shapes/constants."""
        if self.backend == "loop":
            return 0
        if self.backend == "async":
            # One compiled event-scan chunk serves the whole run: every
            # chunk pads its event axis to the static budget E.
            return int(self._chunk_fn._cache_size())
        count = int(self._round_fn._cache_size())
        if self.backend == "scan":
            count += int(self._chunk_fn._cache_size())
            if self._fleet_fn is not None:
                count += int(self._fleet_fn._cache_size())
        return count

    # -- delay accounting ---------------------------------------------------
    def _update_bits(self) -> float:
        # Memoized, and computed from the shape-only _param_struct captured
        # at init: wire accounting is a pure function of the (static) param
        # structure, so it must never slice device buffers or enqueue work —
        # on the scenario path it feeds every round's realized uplink times,
        # and any device touch here would sit between dispatches and defeat
        # the async round pipeline.
        if self._bits_cache is None:
            if self.fed.update_bytes is not None:
                self._bits_cache = self.fed.update_bytes * 8.0
            elif self.fed.compress_updates:
                # Exact wire accounting for the int8 quantizer: 8-bit payload
                # plus one fp32 scale per 1024-chunk
                # (compression.compressed_bits), not the bits/4 approximation.
                self._bits_cache = float(
                    compression.compressed_bits(self._param_struct))
            else:
                self._bits_cache = float(tree_bytes(self._param_struct) * 8.0)
        return self._bits_cache

    def round_times(self) -> tuple:
        T_cm = delay.round_comm_time(
            self._update_bits(), self.wireless, self.pop.p, self.pop.h)
        T_cp = delay.round_compute_time(
            self.fed.batch_size, self.pop.G, self.pop.f)
        return T_cm, T_cp

    # -- envelope plumbing ---------------------------------------------------
    def _trivial_env(self) -> dict:
        """The all-ones (V, b)-envelope masks for this sim's native
        shapes, passed as TRACED inputs into the compiled steps (closing
        over them would constant-fold the masking and change fusion — the
        exact divergence the envelope form exists to avoid)."""
        if self._env_cache is None:
            fed = self.fed
            self._env_cache = {
                "v_mask": jnp.ones(fed.local_rounds, jnp.float32),
                "sample_mask": jnp.ones(fed.batch_size, jnp.float32),
                "n_samples": jnp.float32(fed.batch_size),
                "v_count": jnp.float32(fed.local_rounds),
                "update_bits": jnp.float32(self._update_bits()),
            }
        return self._env_cache

    # -- compiled step builders ---------------------------------------------
    def _build_batched_round(self):
        fed = self.fed
        M, V = fed.n_devices, fed.local_rounds
        if self._sampled:
            M = self._cohort  # K cohort lanes (PRNG keys are lane-indexed)
        compress = fed.compress_updates
        agg = "int8_stochastic" if compress else "allreduce"
        envelope = self._envelope
        step = mesh_rounds.build_round_step(
            self.masked_loss_fn if envelope else self.loss_fn, self.opt, V,
            aggregation=agg, impl=self.impl, envelope=envelope,
            guard=self._guard)
        q_min, q_policy = self._quorum, self._quorum_policy

        def fault_tail(new_p, new_s, old_p, old_s, key, loss, n, metrics):
            """Shared fault-path epilogue: the per-lane finite mask (the
            DivergenceError diagnostic) plus the quorum gate — below
            quorum under policy 'reject' the params/opt write reverts to
            the round's inputs (the batched twin of the scan body's
            ok-gated keep mask; same jnp.where, bit-identical)."""
            extras = {"finite": jnp.isfinite(metrics["per_client_loss"])}
            if q_min is not None:
                rejected = n < jnp.float32(q_min)
                if q_policy == "reject":
                    rv = lambda nw, old: jnp.where(  # noqa: E731
                        rejected, old.astype(nw.dtype), nw)
                    new_p = jax.tree.map(rv, new_p, old_p)
                    new_s = jax.tree.map(rv, new_s, old_s)
                extras["rejected"] = rejected
            return new_p, new_s, key, loss, n, extras

        if self.scenario is None:
            weights = self._weights

            def round_fn(params_C, opt_C, key, batches, env=None):
                keys_C = None
                if compress:
                    key, keys_C = compression.sequential_client_keys(key, M)
                new_p, new_s, metrics = step(
                    params_C, opt_C, batches, weights, keys=keys_C, env=env)
                # Unweighted client mean, matching the loop backend's metric.
                return new_p, new_s, key, jnp.mean(metrics["per_client_loss"])
        elif self._sampled:
            fault = self._faults is not None

            # Sampled form: cohort lanes change owners every round, so
            # the FedAvg size-weights arrive as a traced argument (the
            # gathered (K,) cohort row) instead of a closed-over constant.
            def round_fn(params_C, opt_C, key, batches, sizes,
                         mask, clock_mask, t_cp, t_cm, env=None):
                keys_C = None
                if compress:
                    key, keys_C = compression.sequential_client_keys(key, M)
                new_p, new_s, metrics = step(
                    params_C, opt_C, batches, sizes, keys=keys_C,
                    mask=mask, clock_mask=clock_mask, t_cp=t_cp, t_cm=t_cm,
                    env=env)
                msk = metrics.get("mask_eff", mask)
                n = jnp.sum(msk)
                loss = (jnp.sum(metrics["per_client_loss"] * msk)
                        / jnp.where(n > 0, n, 1.0))
                loss = jnp.where(n > 0, loss, jnp.nan)
                if fault:
                    return fault_tail(new_p, new_s, params_C, opt_C, key,
                                      loss, n, metrics)
                return new_p, new_s, key, loss
        else:
            sizes = self._sizes_f32
            fault = self._faults is not None

            def round_fn(params_C, opt_C, key, batches,
                         mask, clock_mask, t_cp, t_cm, env=None):
                keys_C = None
                if compress:
                    key, keys_C = compression.sequential_client_keys(key, M)
                new_p, new_s, metrics = step(
                    params_C, opt_C, batches, sizes, keys=keys_C,
                    mask=mask, clock_mask=clock_mask, t_cp=t_cp, t_cm=t_cm,
                    env=env)
                # Mean over *participating* clients (the loop backend never
                # runs dropped clients); NaN on a zero-participation round.
                # With a divergence guard, participation is the post-
                # sanitation mask (rejected clients count as dropped).
                msk = metrics.get("mask_eff", mask)
                n = jnp.sum(msk)
                loss = (jnp.sum(metrics["per_client_loss"] * msk)
                        / jnp.where(n > 0, n, 1.0))
                loss = jnp.where(n > 0, loss, jnp.nan)
                if fault:
                    # Guard rejections are decided in-graph, so the true
                    # participant count is a device scalar here (synced at
                    # eval boundaries like the train losses).
                    return fault_tail(new_p, new_s, params_C, opt_C, key,
                                      loss, n, metrics)
                return new_p, new_s, key, loss

        # Donating the stacked params/opt/key buffers lets XLA write round
        # N+1's state into round N's memory: peak HBM stays ~1x the stacked
        # state regardless of round count. The per-round scenario inputs
        # (mask/clock_mask/t_cp/t_cm) are plain traced arrays of fixed
        # shape: new values every round, ONE trace for the whole run.
        return jax.jit(round_fn, donate_argnums=(0, 1, 2))

    def _build_scan_chunk(self):
        """The pure chunk fn (mesh_rounds.build_round_chunk): closure-free
        over run state — params/opt/key and all per-round inputs ride in
        as arguments, which is what lets run_fleet vmap it over a fleet
        axis (mesh_rounds.build_fleet_chunk)."""
        fed = self.fed
        agg = ("int8_stochastic" if fed.compress_updates
               else ("allreduce_shardmap" if self._mesh is not None
                     else "allreduce"))
        n_lanes = self._cohort if self._sampled else fed.n_devices
        return mesh_rounds.build_round_chunk(
            self.masked_loss_fn if self._envelope else self.loss_fn,
            self.opt, fed.local_rounds, n_lanes,
            aggregation=agg, impl=self.impl,
            scenario=self.scenario is not None,
            batch_from=self._batch_from,
            update_bits=self._update_bits(),
            envelope=self._envelope,
            guard=self._guard,
            faults=self._faults is not None,
            sampled=self._sampled,
            quorum=None if self._quorum is None else self._quorum_policy,
            mesh=self._mesh,
            param_specs_tree=self._param_specs,
            client_axes=("clients",) if self._mesh is not None else None)

    def _chunk_call(self, params_C, opt_C, key, weights, t_cp_arg, xs):
        """One compiled chunk dispatch, threading the trivial envelope
        masks on envelope-form sims."""
        if self._carry is not None:
            # A trace keys on each argument's placement, so a carry from
            # init() or from a load_state checkpoint (numpy leaves) is put
            # where the previous chunk leaves it: one compile per run.
            # `in_shardings` on the jit alone does not do this (the first
            # chunk still traced apart). On a carry that is already there
            # this is a no-op.
            params_C, opt_C, key = jax.device_put((params_C, opt_C, key),
                                                  self._carry)
        if self._envelope:
            return self._chunk_fn(params_C, opt_C, key, weights, t_cp_arg,
                                  self._data_dev, xs, self._trivial_env())
        return self._chunk_fn(params_C, opt_C, key, weights, t_cp_arg,
                              self._data_dev, xs)

    def _get_fleet_fn(self):
        if self._fleet_fn is None:
            self._fleet_fn = jax.jit(
                mesh_rounds.build_fleet_chunk(self._chunk_raw,
                                              envelope=self._envelope,
                                              sampled=self._sampled),
                donate_argnums=(0, 1, 2))
        return self._fleet_fn

    def _fleet_init_base(self):
        """The (params_C, opt_C) every fresh member starts from, cached —
        never donated itself (run_fleet broadcasts a new stacked buffer
        out of it per call), so reuse across calls is safe."""
        if self._fleet_base is None:
            C = self._cohort if self._sampled else self.fed.n_devices
            self._fleet_base = (
                mesh_rounds.replicate_clients(self._init_params, C),
                jax.vmap(lambda _: self.opt.init(self._init_params))(
                    jnp.arange(C)))
        return self._fleet_base

    # -- fault semantics (host f64 side) ------------------------------------
    def _fault_round(self, real):
        """Resolve a realization's retransmission + deadline semantics:
        (real', t_cm_clients, attempts_total).

        t_cm_clients is the effective per-client uplink time — the SUM of
        every attempt's Eq. 6 airtime plus backoff waits (f64, the host
        clock twin). With a deadline, clients whose V*t_cp + effective
        uplink exceeds it are cut from the aggregation mask (they stay in
        clock_mask: the server waited on them until the deadline). Both
        decisions are host-side f64 — the compiled graph only consumes
        their traced results — and idempotent, so re-applying to an
        already-resolved realization is a no-op."""
        fm = self._faults
        t_cm = delay.effective_uplink_times(
            self._update_bits(), self.wireless, self.pop.p,
            real.h_att, real.attempts, fm.backoff_base, fm.backoff_factor)
        if self._deadline is not None:
            finish = self.fed.local_rounds * self._t_cp_clients + t_cm
            mask = np.asarray(real.mask, bool) & (finish <= self._deadline)
            real = dataclasses.replace(real, mask=mask)
        return real, t_cm, int(real.attempts.sum())

    @staticmethod
    def _gather_real(real, cohort):
        """Restrict an M-wide realization to the cohort's columns. Fault
        semantics (retransmission clocks, deadline cuts) are resolved
        M-wide FIRST, then gathered — sampling selects who participates,
        it never changes what would have happened to them."""
        return dataclasses.replace(
            real,
            mask=np.asarray(real.mask)[cohort],
            clock_mask=np.asarray(real.clock_mask)[cohort],
            h=np.asarray(real.h)[cohort],
            attempts=(None if real.attempts is None
                      else np.asarray(real.attempts)[cohort]),
            h_att=(None if real.h_att is None
                   else np.asarray(real.h_att)[cohort]))

    def _chunk_uplink(self, chunk):
        """M-wide (mask, t_cm) for a chunk realization: the effective
        per-client uplink times (retransmission sums on the fault path,
        single-shot Eq. 6 otherwise) and the aggregation mask after the
        deadline cut. f64 host twin, vectorized over the round axis —
        each row bit-identical to the per-round _fault_round resolution.
        Fault semantics resolve POPULATION-wide even under sampling, so
        cohort gathers see exactly the rows a dense run would."""
        mask = np.asarray(chunk.mask, bool)
        if self._faults is not None:
            fm = self._faults
            t_cm = delay.effective_uplink_times(
                self._update_bits(), self.wireless, self.pop.p,
                chunk.h_att, chunk.attempts,
                fm.backoff_base, fm.backoff_factor)
            if self._deadline is not None:
                finish = delay.finish_times(
                    self._t_cp_clients, t_cm, self.fed.local_rounds)
                mask = mask & (finish <= self._deadline)
        else:
            t_cm = delay.per_client_uplink_time(
                self._update_bits(), self.wireless, self.pop.p, chunk.h)
        return mask, t_cm

    def _select_cohorts(self, cands: np.ndarray, t_cm: np.ndarray,
                        ) -> np.ndarray:
        """Over-provisioned cohort selection: keep the K deadline-
        feasible-fastest of each round's (K + spare) candidate draw.

        Ranking is by the f64 per-client finish time V*t_cp + t_cm
        (delay.finish_times) with deadline-infeasible candidates sorted
        last and ties broken by client index; the kept K are returned
        sorted ascending (the cohort-index convention draw_cohort
        establishes). Selection happens AFTER the M-wide fault
        resolution (t_cm is the effective uplink time) and BEFORE any
        cohort gather — sampling selects who participates, it never
        changes what would have happened to them."""
        K = self._cohort
        finish_all = delay.finish_times(
            self._t_cp_clients, t_cm, self.fed.local_rounds)
        finish = np.take_along_axis(finish_all, cands, axis=1)
        infeas = (finish > self._deadline if self._deadline is not None
                  else np.zeros(finish.shape, bool))
        out = np.empty((cands.shape[0], K), np.int32)
        for r in range(cands.shape[0]):
            # lexsort: LAST key is primary — feasible first, then
            # fastest, ties by client id.
            order = np.lexsort((cands[r], finish[r], infeas[r]))
            out[r] = np.sort(cands[r][order[:K]])
        return out

    def _raise_if_diverged(self, history, start: int, snap,
                           finites=None) -> int:
        """run()-level divergence guard: a non-finite train loss on a
        round that HAD participants means the aggregate itself is
        poisoned (zero-participation rounds are legitimately NaN and
        pass). Raises DivergenceError carrying the last-good snapshot —
        plus the offending round's per-lane finite mask (`finites`,
        aligned with `history`, when the backend collected them) and the
        FaultModel / guard spec in force, so a diagnosing caller sees
        WHICH clients went non-finite without re-running. Returns the
        new checked-up-to index otherwise."""
        for i in range(start, len(history)):
            rec = history[i]
            n_p = rec.n_participants
            if (isinstance(rec.train_loss, float)
                    and not np.isfinite(rec.train_loss)
                    and (n_p is None or n_p > 0)):
                fin = finites[i] if finites is not None and i < len(finites) else None
                raise DivergenceError(
                    f"train loss became non-finite ({rec.train_loss}) at "
                    f"round {rec.round} with "
                    f"{'all' if n_p is None else n_p} participating "
                    "clients; .state holds the last-good SimState "
                    "snapshot, .history the records up to the failure",
                    state=snap, history=history[:i + 1], round=rec.round,
                    faults=self._faults, guard=self._guard,
                    finite_mask=(None if fin is None
                                 else jax.device_get(fin)))
        return len(history)

    # -- per-round execution ------------------------------------------------
    def run_round(self, state: SimState, real=None, t_cm_clients=None):
        """One communication round: (state, metrics-dict). `real` is the
        scenario's per-round realization (drawn from the state's stream
        when omitted); passing it on a scenario-less simulation raises —
        there is no participation/channel semantics to apply it to.
        `t_cm_clients` lets run() share its per-client uplink-time vector
        instead of recomputing. The scan backend shares the batched
        backend's per-round step here (same stacked state layout);
        chunking only applies inside run()."""
        if self.backend == "async":
            raise ValueError(
                "run_round is round-synchronous; backend='async' advances "
                "by arrival events, not rounds — use run() (aggregation "
                "cadence) or run_events() (exact event counts).")
        if real is not None and self.scenario is None:
            raise ValueError(
                "run_round(real=...) was given a scenario realization but "
                "this simulation has no scenario — the mask/channel inputs "
                "would be silently ignored. Construct the Simulator with "
                "scenario=... or drop the argument.")
        if real is not None and self._sampled:
            raise ValueError(
                "run_round(real=...) is unsupported with sampled cohorts: "
                "an externally supplied M-wide realization has no cohort "
                "to condition on. Drop the argument (the state's stream "
                "draws both) or run dense.")
        iters, stream = self._materialize(state)
        cohort = None
        if self.scenario is not None and real is None:
            if self._sampled:
                cohort = stream.draw_cohort()
            real = stream.next_round()
        if self._faults is not None and real is not None:
            real, t_cm_fault, _ = self._fault_round(real)
            if t_cm_clients is None:
                t_cm_clients = t_cm_fault
        if cohort is not None:
            if self._spare:
                # Rank the K+spare candidates by effective finish time
                # (M-wide fault semantics already resolved above).
                if t_cm_clients is None:
                    t_cm_clients = delay.per_client_uplink_time(
                        self._update_bits(), self.wireless, self.pop.p,
                        real.h)
                cohort = self._select_cohorts(
                    np.asarray(cohort)[None],
                    np.asarray(t_cm_clients, np.float64)[None])[0]
            real = self._gather_real(real, cohort)
            if t_cm_clients is not None:
                t_cm_clients = np.asarray(t_cm_clients)[cohort]
        if self.backend == "loop":
            params, opt_C, key, metrics = self._round_loop(
                state.params_C, state.opt_C, state.key, iters, real)
        else:
            params, opt_C, key, metrics = self._round_batched(
                state.params_C, state.opt_C, state.key, iters, real,
                t_cm_clients, cohort)
        new_state = self._rebuild_state(
            state, params, opt_C, key, state.round + 1, state.sim_time,
            iters, stream)
        return new_state, metrics

    def _round_batched(self, params_C, opt_C, key, iters, real,
                       t_cm_clients=None, cohort=None):
        V = self.fed.local_rounds
        batches = (stack_cohort_batches(iters, cohort, V)
                   if cohort is not None else stack_client_batches(iters, V))
        env = self._trivial_env() if self._envelope else None
        if self.scenario is None:
            params_C, opt_C, key, loss = self._round_fn(
                params_C, opt_C, key, batches, env)
            return params_C, opt_C, key, {"train_loss": loss}  # device scalar
        if t_cm_clients is None:  # direct run_round callers; run() shares its vector
            p = self.pop.p if cohort is None else self.pop.p[cohort]
            t_cm_clients = delay.per_client_uplink_time(
                self._update_bits(), self.wireless, p, real.h)
        mask = jnp.asarray(real.mask, jnp.float32)
        clock_mask = jnp.asarray(real.clock_mask, jnp.float32)
        t_cp = jnp.asarray(self._t_cp_clients if cohort is None
                           else self._t_cp_clients[cohort], jnp.float32)
        t_cm = jnp.asarray(t_cm_clients, jnp.float32)
        if cohort is not None:
            sizes = jnp.asarray(self._sizes_host[cohort])
            if self._faults is not None:
                params_C, opt_C, key, loss, n_dev, extras = self._round_fn(
                    params_C, opt_C, key, batches, sizes, mask, clock_mask,
                    t_cp, t_cm, env)
                return params_C, opt_C, key, {
                    "train_loss": loss, "n_participants": n_dev, **extras}
            params_C, opt_C, key, loss = self._round_fn(
                params_C, opt_C, key, batches, sizes, mask, clock_mask,
                t_cp, t_cm, env)
            return params_C, opt_C, key, {
                "train_loss": loss, "n_participants": real.n_participants}
        if self._faults is not None:
            # Guard rejections happen in-graph: the participant count is
            # the compiled step's fifth output (a device scalar until the
            # next _sync_history boundary).
            params_C, opt_C, key, loss, n_dev, extras = self._round_fn(
                params_C, opt_C, key, batches, mask, clock_mask, t_cp,
                t_cm, env)
            return params_C, opt_C, key, {
                "train_loss": loss, "n_participants": n_dev, **extras}
        params_C, opt_C, key, loss = self._round_fn(
            params_C, opt_C, key, batches, mask, clock_mask, t_cp, t_cm, env)
        return params_C, opt_C, key, {
            "train_loss": loss, "n_participants": real.n_participants}

    def _round_loop(self, params, opt_states, key, iters, real):
        V = self.fed.local_rounds
        M = len(iters)
        deltas, sizes, losses = [], [], []
        keys_C = None
        if self.fed.compress_updates:
            # Keys are drawn for all M clients regardless of participation
            # (the batched backend must: vmap is shape-static), so the two
            # backends' PRNG streams stay aligned under any mask.
            key, keys_C = compression.sequential_client_keys(key, M)
        mask = np.ones(M, bool) if real is None else np.asarray(real.mask, bool)
        opt_states = list(opt_states)
        # Quorum gate reference: pre-round opt snapshot so a rejected
        # round can revert every client's local-opt advance (the loop
        # twin of the batched/scan no-op write).
        pre_opts = list(opt_states) if self._quorum is not None else None
        for m, it in enumerate(iters):
            # Data is drawn for every client every round — participating or
            # not — matching stack_client_batches on the batched backend so
            # both consume identical iterator streams.
            raw = [it.next_batch() for _ in range(V)]
            if not mask[m]:
                continue
            batches = stack_batches(
                [jax.tree.map(jnp.asarray, b) for b in raw])
            prev_opt = opt_states[m]
            delta, opt_states[m], loss_v = client_round(
                self.local_update, params, opt_states[m], batches)
            loss_m = float(jnp.mean(loss_v))
            if self._guard is not None:
                # Reference implementation of the in-graph divergence
                # guard (mesh_rounds._guard_clients): same f32 norm, same
                # reject/clip decisions, so the backends agree to the
                # usual loop tolerance.
                max_norm, reject = self._guard
                sq = jnp.float32(0.0)
                for d in jax.tree.leaves(delta):
                    sq = sq + jnp.sum(jnp.asarray(d, jnp.float32) ** 2)
                norm = float(jnp.sqrt(sq))
                finite = np.isfinite(norm) and np.isfinite(loss_m)
                if reject and not finite:
                    # Rejected = dropped this round: pre-round opt state
                    # restored, no delta, not counted a participant.
                    opt_states[m] = prev_opt
                    continue
                if np.isfinite(max_norm) and finite:
                    scale = min(1.0, max_norm / max(norm, 1e-12))
                    # Mirror the batched clip exactly: reconstruct the
                    # clipped params (o + d*scale) and re-derive the
                    # delta from them, rounding included.
                    delta = jax.tree.map(
                        lambda o, d: (o.astype(jnp.float32)
                                      + d.astype(jnp.float32) * scale)
                        - o.astype(jnp.float32),
                        params, delta)
            if self.fed.compress_updates:
                delta = compression.decompress_update(
                    compression.compress_update(delta, keys_C[m], impl=self.impl),
                    impl=self.impl)
            deltas.append(delta)
            sizes.append(self.data_sizes[m])
            losses.append(loss_m)
        rejected = None
        if self._quorum is not None and real is not None:
            # Same participant count the batched/scan gates compare:
            # post-guard when a guard is in force, the raw mask otherwise.
            n_q = (len(deltas) if self._guard is not None
                   else int(mask.sum()))
            rejected = n_q < self._quorum
        if rejected and self._quorum_policy == "reject":
            # Below quorum: the whole round is a no-op write — no
            # aggregation, pre-round opt states restored. (The clock
            # still advances; run() pays the re-dispatch cost.)
            opt_states = pre_opts
        elif deltas:  # zero-participation round: params unchanged
            params = aggregate_updates(params, deltas, sizes)
        out = {"train_loss": float(np.mean(losses)) if losses else float("nan")}
        if real is not None:
            out["n_participants"] = (len(deltas) if self._guard is not None
                                     else int(mask.sum()))
            if rejected is not None:
                out["rejected"] = rejected
        return params, tuple(opt_states), key, out

    # -- chunked execution (scan backend) -----------------------------------
    @staticmethod
    def _pad_rounds(a: np.ndarray, R: int) -> np.ndarray:
        """Pad a round-stacked array to R rounds with zeros (ragged final
        chunk; the padded tail is masked out in-graph via `valid`)."""
        n = a.shape[0]
        if n == R:
            return a
        return np.concatenate([a, np.zeros((R - n, *a.shape[1:]), a.dtype)])

    def _chunk_inputs(self, iters, stream, R: int, n: int,
                      envelope: Optional[tuple] = None):
        """Host-side prep for one chunk: draw n rounds of data (+ scenario
        realizations), pad to R, and return (xs pytree for the scan — all
        numpy leaves so run_fleet can stack members before the single
        upload — plus a host dict with the f64 clock accounting for the
        history records). With `envelope=(V_env, B_env)` (the Study
        group executor) the native draws are additionally zero-padded
        into the group envelope — never extra draws, so the
        iterator/stream consumption is identical to a native run's."""
        V, b = self.fed.local_rounds, self.fed.batch_size
        M = self.fed.n_devices
        L = self._cohort if self._sampled else M  # lanes in the xs leaves
        V_env, B_env = envelope if envelope is not None else (V, b)
        pad = self._pad_rounds

        def pad_env(a):
            a = np.asarray(a)
            if (V_env, B_env) == (V, b):
                return pad(a, R)
            out = np.zeros((R, L, V_env, B_env) + a.shape[4:], a.dtype)
            out[:n, :, :V, :b] = a
            return out

        # Cohort candidates are drawn first (dedicated RNG, independent
        # of the realization stream) so only selected clients' data
        # iterators advance. The chunk realization is drawn NEXT — before
        # the data advance — because over-provisioned draws (spare > 0)
        # rank the K+spare candidates by realized finish time; the RNG
        # streams are independent generators, so the spare=0 draws are
        # bit-identical to the historical cohorts->data->chunk order
        # (_rewind_chunk replays this exact order).
        cohorts = chunk = mask_M = t_cm_M = None
        if self._sampled:
            with TraceAnnotation("defl.draw_cohorts"):
                cands = stream.draw_cohorts(n)
            with TraceAnnotation("defl.draw_chunk"):
                chunk = stream.draw_chunk(n)
            mask_M, t_cm_M = self._chunk_uplink(chunk)
            cohorts = (self._select_cohorts(cands, t_cm_M)
                       if self._spare else cands)
        if self._data_dev is not None:
            with TraceAnnotation("defl.batch_indices"):
                idx = (stack_cohort_indices(iters, cohorts, V)
                       if self._sampled else stack_chunk_indices(iters, n, V))
            xs = {"idx": pad_env(idx)}
        else:
            if self._sampled:
                rounds_b = [stack_cohort_batches(iters, cohorts[r], V)
                            for r in range(n)]
                batches = jax.tree.map(lambda *bs: np.stack(bs), *rounds_b)
            else:
                batches = stack_chunk_batches(iters, n, V)
            xs = {"batches": jax.tree.map(pad_env, batches)}
        valid = np.zeros(R, bool)
        valid[:n] = True
        xs["valid"] = valid
        host = {}
        if self.scenario is not None:
            if not self._sampled:
                with TraceAnnotation("defl.draw_chunk"):
                    chunk = stream.draw_chunk(n)
                # Retransmission sums + deadline exclusion, resolved
                # M-wide (f64 host twin — see _chunk_uplink).
                mask_M, t_cm_M = self._chunk_uplink(chunk)
            mask, t_cm = mask_M, t_cm_M
            clock_mask = np.asarray(chunk.clock_mask)
            if self._sampled:
                # Everything below the gather sees only cohort columns —
                # bits, attempts and the round clock are conditioned on
                # the sampled cohort (absent clients never hit the air).
                g = lambda a: np.take_along_axis(np.asarray(a), cohorts,
                                                 axis=1)
                mask, clock_mask, t_cm = g(mask), g(clock_mask), g(t_cm)
                t_cp_rows = np.take(self._t_cp_clients, cohorts)
                if self._faults is not None:
                    host["attempts"] = g(chunk.attempts).sum(axis=1)
            else:
                t_cp_rows = self._t_cp_clients
                if self._faults is not None:
                    host["attempts"] = chunk.attempts.sum(axis=1)
            # f64 host twin of the in-graph clock: bit-identical to the
            # per-round backends' accounting (delay.chunk_round_times).
            T_cm, T_cp = delay.chunk_round_times(t_cp_rows, t_cm, clock_mask)
            host.update({"T_cm": T_cm, "T_cp": T_cp,
                         "n_participants": mask.sum(axis=1)})
            xs["mask"] = pad(mask.astype(np.float32), R)
            xs["clock_mask"] = pad(clock_mask.astype(np.float32), R)
            xs["t_cm"] = pad(t_cm.astype(np.float32), R)
            if self._sampled:
                # Per-round cohort rows of the chunk-constant dense args:
                # FedAvg size weights (raw sizes — the step renormalizes
                # in-graph) and compute times, as the SAME f32 values the
                # dense path uploads.
                xs["weights"] = pad(np.take(self._sizes_host, cohorts), R)
                xs["t_cp"] = pad(t_cp_rows.astype(np.float32), R)
            if self._faults is not None:
                cap = np.inf if self._deadline is None else self._deadline
                xs["t_cap"] = pad(np.full(n, cap, np.float32), R)
                xs["bits_mult"] = pad(
                    host["attempts"].astype(np.float32), R)
                if self._quorum is not None:
                    # Padded tail rows carry quorum_min = 0: n >= 0 never
                    # rejects, so padding can't trip the gate.
                    xs["quorum_min"] = pad(
                        np.full(n, self._quorum, np.float32), R)
                    if self._quorum_policy == "reject":
                        xs["q_penalty"] = pad(np.full(
                            n, self._faults.redispatch_cost, np.float32), R)
        return xs, host

    def _rewind_chunk(self, iters, stream, pre_data, pre_stream, t: int):
        """Reposition the host streams as if only the first t rounds of
        the just-drawn chunk had been consumed: restore the pre-chunk
        snapshots and replay t rounds in chunk order. Iterators without
        the snapshot protocol can't be rewound — acceptable only if they
        are stateless (the same assumption checkpointing makes)."""
        V = self.fed.local_rounds
        if self._sampled:
            # Candidates -> chunk -> data, the exact _chunk_inputs order.
            # Index replay (next_indices) is RNG-identical to next_batch.
            stream.set_state(pre_stream)
            cohorts = stream.draw_cohorts(t)
            chunk = stream.draw_chunk(t)
            if self._spare:
                _, t_cm = self._chunk_uplink(chunk)
                cohorts = self._select_cohorts(cohorts, t_cm)
            if pre_data is not None:
                self._restore_iters(iters, pre_data)
                stack_cohort_indices(iters, cohorts, V)
            return
        if pre_data is not None:
            self._restore_iters(iters, pre_data)
            if self._data_dev is not None:
                stack_chunk_indices(iters, t, V)
            else:
                stack_chunk_batches(iters, t, V)
        if stream is not None:
            stream.set_state(pre_stream)
            stream.draw_chunk(t)

    def _chunk_args(self):
        """(weights, t_cp) chunk-fn arguments for this configuration.
        Sampled sims carry both as per-round xs leaves (the gathered
        cohort rows) instead of chunk-constant arguments."""
        if self._sampled:
            return None, None
        if self.scenario is None:
            return self._weights, None
        return self._sizes_f32, self._t_cp_dev

    def _chunk_records(self, ys, host, n: int, r0: int, t0: float,
                       ) -> List[RoundRecord]:
        """Build the n RoundRecords of one chunk from the fetched scan
        outputs `ys` (host numpy, leaves (R,)) and the f64 host-twin clock
        dict, starting at global round r0 and clock t0."""
        update_bits = self._update_bits()
        V = self.fed.local_rounds
        M = self.fed.n_devices
        if self.scenario is None:
            T_cm_const, T_cp_const = self.round_times()
        records = []
        sim_time = t0
        for i in range(n):
            if self.scenario is None:
                T_cm, T_cp, n_part = T_cm_const, T_cp_const, None
                bits = float(M * update_bits)
            elif self._faults is not None:
                T_cm = float(host["T_cm"][i])
                T_cp = float(host["T_cp"][i])
                # With a guard the true participant count is the in-graph
                # post-sanitation one; client counts are exact in fp32.
                n_part = int(ys["n_participants"][i])
                # Every retransmission attempt's bits hit the air.
                bits = float(host["attempts"][i] * update_bits)
            else:
                T_cm = float(host["T_cm"][i])
                T_cp = float(host["T_cp"][i])
                n_part = int(host["n_participants"][i])
                bits = float(n_part * update_bits)
            rej = bool(ys["rejected"][i]) if "rejected" in ys else None
            sim_time += delay.round_time(T_cm, T_cp, V,
                                         deadline=self._deadline)
            if rej and self._quorum_policy == "reject":
                # Rejected rounds pay wall time AND the re-dispatch
                # penalty (host f64 twin of the in-graph T_round term).
                sim_time += self._faults.redispatch_cost
            records.append(RoundRecord(
                round=r0 + i + 1, sim_time=sim_time, T_cm=T_cm, T_cp=T_cp,
                train_loss=float(ys["loss"][i]),
                n_participants=n_part, uplink_bits=bits, rejected=rej))
        return records

    def run_chunk(self, state: SimState, rounds: int):
        """Run `rounds` rounds as ONE compiled scan dispatch (scan backend
        only): (state', [RoundRecord]). The building block `run()` drives
        at eval_every cadence; exposed for custom drivers (schedulers,
        in-graph stopping rules) that want chunk-level control."""
        if self.backend != "scan":
            raise ValueError(
                f"run_chunk requires backend='scan', not {self.backend!r}")
        _validate_run_args(rounds, 1)
        iters, stream = self._materialize(state)
        weights, t_cp_arg = self._chunk_args()
        xs, host = self._chunk_inputs(iters, stream, rounds, rounds)
        params_C, opt_C, key, ys = self._chunk_call(
            state.params_C, state.opt_C, state.key, weights, t_cp_arg, xs)
        ys = jax.device_get(ys)
        records = self._chunk_records(ys, host, rounds, state.round,
                                      state.sim_time)
        new_state = self._rebuild_state(
            state, params_C, opt_C, key, state.round + rounds,
            records[-1].sim_time, iters, stream)
        return new_state, records

    # -- asynchronous (event-driven) execution ------------------------------
    def _async_dispatch_draw(self, stream):
        """One M-wide dispatch realization from the scenario stream:
        (t_svc f32, drop f32, t_cm f64, attempts f64), all (M,).

        t_svc is the full service time V*t_cp + effective uplink (f32 —
        it feeds the f32 finish-time schedule, host twin and in-graph
        alike). drop marks dispatches whose update will be LOST: the
        scenario participation mask, composed with the fault model's
        deadline cut (a dispatch whose service time exceeds the deadline
        never lands — _fault_round resolves that M-wide in f64 exactly as
        the sync path does). Retransmission attempts/backoff waits are
        already inside the effective uplink time, so a retrying client
        simply finishes later."""
        real = stream.next_round()
        if self._faults is not None:
            real, t_cm, _ = self._fault_round(real)
            attempts = np.asarray(real.attempts, np.float64)
        else:
            t_cm = delay.per_client_uplink_time(
                self._update_bits(), self.wireless, self.pop.p, real.h)
            attempts = np.ones(self.fed.n_devices, np.float64)
        t_svc = (self.fed.local_rounds * self._t_cp_clients
                 + t_cm).astype(np.float32)
        drop = (~np.asarray(real.mask, bool)).astype(np.float32)
        return t_svc, drop, np.asarray(t_cm, np.float64), attempts

    def _async_twin(self, state: SimState):
        """The host f32 schedule twin positioned at `state`: a numpy
        replay of the device carry's scheduling slice (events.TwinState).
        One small fetch of the scheduling leaves — params never leave the
        device."""
        a = jax.device_get({k: state.async_c[k] for k in (
            "t_finish", "t_next", "drop_C", "version", "version_C",
            "cnt", "now")})
        h = state.async_host
        return self._events_mod.TwinState(
            t_finish=np.asarray(a["t_finish"], np.float32).copy(),
            t_next=np.asarray(a["t_next"], np.float32).copy(),
            drop=np.asarray(a["drop_C"], np.float32).copy(),
            version=int(a["version"]),
            version_disp=np.asarray(a["version_C"], np.int32).copy(),
            cnt=int(a["cnt"]),
            now=np.float32(a["now"]),
            t_cm_disp=np.asarray(h["t_cm_disp"], np.float64).copy(),
            attempts_disp=np.asarray(h["attempts_disp"], np.float64).copy())

    def _async_chunk_inputs(self, iters, stream, twin, stop_aggs=None,
                            stop_events=None, max_sim_time=None):
        """Host-side prep for one event chunk: advance the schedule twin
        event by event — drawing one M-wide dispatch realization and the
        arriving client's V batches per event — until `stop_aggs`
        aggregations have fired (chunks end exactly at aggregation
        boundaries, the async analogue of eval_every chunking), an
        aggregation crosses `max_sim_time`, `stop_events` events have run
        (run_events' exact-event mode), or the static budget E is full.
        Returns (xs padded to E, [TwinEvent], n_events). The twin is
        mutated in place; because np and jnp share f32 arithmetic and
        first-min argmin, its predicted arrival order is exact (asserted
        against the scan ys in _async_records)."""
        E = self._async_E
        V = self.fed.local_rounds
        limit = E if stop_events is None else min(E, int(stop_events))
        t_svc_rows, drop_rows, data_rows, evs = [], [], [], []
        n_aggs = 0
        while len(evs) < limit:
            c = int(np.argmin(twin.t_finish))
            # The arriving client's batches: its iterator advances at
            # arrival (per-client streams are independent, so client c's
            # k-th dispatch consumes its k-th V-block — the same
            # sequence a dispatch-time draw would produce).
            it = iters[c]
            if self._data_dev is not None:
                data_rows.append(
                    np.stack([it.next_indices() for _ in range(V)]).astype(
                        np.int32))
            else:
                bs = [it.next_batch() for _ in range(V)]
                data_rows.append(
                    jax.tree.map(lambda *x: np.stack(x), *bs))
            t_svc, drop, t_cm, att = self._async_dispatch_draw(stream)
            e = self._events_mod.twin_step(
                self._async, twin, t_svc, drop, t_cm, att)
            assert e.client == c
            t_svc_rows.append(t_svc)
            drop_rows.append(drop)
            evs.append(e)
            if e.aggregated and stop_events is None:
                n_aggs += 1
                if stop_aggs is not None and n_aggs >= stop_aggs:
                    break
                if (max_sim_time is not None
                        and float(e.t_event) >= max_sim_time):
                    break
        n_ev = len(evs)
        pad = self._pad_rounds
        xs = {
            "t_svc": pad(np.stack(t_svc_rows), E),
            "drop_next": pad(np.stack(drop_rows), E),
        }
        valid = np.zeros(E, bool)
        valid[:n_ev] = True
        xs["valid"] = valid
        if self._data_dev is not None:
            xs["idx"] = pad(np.stack(data_rows), E)
        else:
            xs["batches"] = jax.tree.map(
                lambda *r: pad(np.stack(r), E), *data_rows)
        return xs, evs, n_ev

    def _async_records(self, ys, evs, n_ev, r0: int, bits_acc: float):
        """Per-AGGREGATION RoundRecords from one event chunk's fetched
        scan outputs, plus the carried-over uplink-bits accumulator
        (bits of arrivals since the previous aggregation — it spans
        chunk/checkpoint boundaries via SimState.async_host).

        Clock semantics (EXPERIMENTS.md §Asynchronous execution): an
        async 'round' is one buffer fill; sim_time is the ABSOLUTE f32
        event clock at the filling update's arrival (not a per-round f64
        delta sum — the event clock IS the schedule, so the record clock
        deliberately shares its f32 arithmetic). T_cm/T_cp are the
        FILLING update's own f64 uplink and compute times."""
        clients = np.asarray(ys["client"][:n_ev])
        twin_clients = np.array([e.client for e in evs], np.int32)
        if not np.array_equal(clients, twin_clients):
            j = int(np.argmin(clients == twin_clients))
            raise RuntimeError(
                "async schedule twin diverged from the compiled event "
                f"queue at event {j}: twin predicted client "
                f"{int(twin_clients[j])}, the scan popped "
                f"{int(clients[j])}. The f32 replay contract "
                "(events.twin_step) is broken — records would be "
                "misattributed, refusing to continue.")
        update_bits = self._update_bits()
        records = []
        k = 0
        for j, e in enumerate(evs):
            # Wire accounting: every arrival's dispatch paid its uplink.
            # Fault path: every retransmission attempt hit the air,
            # dropped or not (the sync chunk's attempts-sum rule).
            # Plain path: one upload per non-dropped arrival.
            if self._faults is not None:
                bits_acc += float(e.attempts_done) * update_bits
            elif not e.dropped:
                bits_acc += update_bits
            if e.aggregated:
                k += 1
                records.append(RoundRecord(
                    round=r0 + k,
                    sim_time=float(e.t_event),
                    T_cm=float(e.t_cm_done),
                    T_cp=float(self._t_cp_clients[e.client]),
                    train_loss=float(ys["loss_agg"][j]),
                    n_participants=int(self._async.buffer_size),
                    uplink_bits=bits_acc))
                bits_acc = 0.0
        return records, bits_acc

    def _async_state(self, state, params_C, opt_C, key, async_c, twin,
                     rnd, n_events, bits_acc, iters, stream) -> SimState:
        """Rebuild a SimState after async chunks: the device carry plus
        the twin's f64 dispatch bookkeeping and the event cursor."""
        return self._rebuild_state(
            state, params_C, opt_C, key, rnd, float(twin.now), iters,
            stream, async_c=async_c, event=int(state.event) + int(n_events),
            async_host={"t_cm_disp": twin.t_cm_disp.copy(),
                        "attempts_disp": twin.attempts_disp.copy(),
                        "bits_acc": float(bits_acc)})

    def _run_async(self, state, max_rounds, target_acc, eval_every,
                   max_sim_time):
        """Event-driven driver: one compiled event-scan dispatch + one
        device_get per chunk, chunk boundaries at aggregation (round)
        boundaries so eval cadence matches the sync drivers'. A 'round'
        is a buffer fill; max_rounds counts fills."""
        with TraceAnnotation("defl.materialize"):
            iters, stream = self._materialize(state)
            twin = self._async_twin(state)
        params_C, opt_C, key = state.params_C, state.opt_C, state.key
        async_c = state.async_c
        bits_acc = float(state.async_host.get("bits_acc", 0.0))
        history: List[RoundRecord] = []
        r0 = state.round
        n_events = 0
        done, stop, idle_chunks = 0, False, 0
        while done < max_rounds and not stop:
            n_t = min(eval_every - done % eval_every, max_rounds - done)
            with TraceAnnotation("defl.chunk_inputs"):
                xs, evs, n_ev = self._async_chunk_inputs(
                    iters, stream, twin, stop_aggs=n_t,
                    max_sim_time=max_sim_time)
            with TraceAnnotation("defl.dispatch"):
                params_C, opt_C, key, async_c, ys = self._chunk_fn(
                    params_C, opt_C, key, async_c, self._sizes_f32,
                    self._data_dev, xs)
            # The chunk's only device->host sync, same as the sync scan.
            with TraceAnnotation("defl.fetch"):
                ys = jax.device_get(ys)
            with TraceAnnotation("defl.records"):
                records, bits_acc = self._async_records(
                    ys, evs, n_ev, r0 + done, bits_acc)
            n_events += n_ev
            history.extend(records)
            done += len(records)
            # Aggregation-progress watchdog: a scenario that drops every
            # update (or a buffer larger than the surviving arrival rate
            # can ever fill) would otherwise burn event chunks forever.
            idle_chunks = 0 if records else idle_chunks + 1
            if idle_chunks >= 1000:
                raise RuntimeError(
                    f"async run made no aggregation progress over "
                    f"{idle_chunks * self._async_E} consecutive events "
                    f"(buffer_size={self._async.buffer_size}) — the "
                    "scenario drops too many updates to ever fill the "
                    "buffer. Shrink buffer_size or fix the scenario.")
            if max_sim_time and float(twin.now) >= max_sim_time:
                stop = True
            at_boundary = done > 0 and (done % eval_every == 0
                                        or done == max_rounds)
            if self.eval_fn and records and (at_boundary or stop):
                rec = history[-1]
                with TraceAnnotation("defl.eval"):
                    ev = self.eval_fn(async_c["params_g"])
                rec.test_acc = float(ev.get("acc", np.nan))
                rec.test_loss = float(ev.get("loss", np.nan))
                if (target_acc and rec.test_acc is not None
                        and rec.test_acc >= target_acc):
                    stop = True
        with TraceAnnotation("defl.snapshot"):
            new_state = self._async_state(
                state, params_C, opt_C, key, async_c, twin, r0 + done,
                n_events, bits_acc, iters, stream)
        return new_state, SimResult(
            history=history, params=async_c["params_g"],
            label=self.label, fed=self.fed)

    def run_events(self, state: SimState, events: int):
        """Run EXACTLY `events` arrival events (async backend only):
        (state', [RoundRecord]). Unlike run(), this may stop mid-buffer —
        pending updates, the partial buffer and the event cursor all live
        in the returned SimState, and a save/load/resume from it is
        bit-identical to the uninterrupted run (the mid-buffer
        checkpoint contract, tests/test_async_events.py)."""
        if self.backend != "async":
            raise ValueError(
                f"run_events requires backend='async', not {self.backend!r}")
        if not isinstance(events, (int, np.integer)) or events < 1:
            raise ValueError(f"events must be an int >= 1, got {events!r}")
        iters, stream = self._materialize(state)
        twin = self._async_twin(state)
        params_C, opt_C, key = state.params_C, state.opt_C, state.key
        async_c = state.async_c
        bits_acc = float(state.async_host.get("bits_acc", 0.0))
        history: List[RoundRecord] = []
        done_ev = 0
        while done_ev < events:
            xs, evs, n_ev = self._async_chunk_inputs(
                iters, stream, twin, stop_events=events - done_ev)
            params_C, opt_C, key, async_c, ys = self._chunk_fn(
                params_C, opt_C, key, async_c, self._sizes_f32,
                self._data_dev, xs)
            ys = jax.device_get(ys)
            records, bits_acc = self._async_records(
                ys, evs, n_ev, state.round + len(history), bits_acc)
            history.extend(records)
            done_ev += n_ev
        new_state = self._async_state(
            state, params_C, opt_C, key, async_c, twin,
            state.round + len(history), done_ev, bits_acc, iters, stream)
        return new_state, history

    def _run_scan(self, state, max_rounds, target_acc, eval_every,
                  max_sim_time):
        """Chunked driver: one compiled scan call + one device_get per
        eval_every rounds. Chunk boundaries coincide exactly with the
        per-round driver's eval boundaries (k % eval_every == 0 or the
        final round). On a max_sim_time stop the history is truncated at
        the first exceeding round, matching the per-round backends; the
        device state is end-of-chunk (documented deviation — the chunk is
        already in flight)."""
        with TraceAnnotation("defl.materialize"):
            iters, stream = self._materialize(state)
        guard_on = (self._faults is not None
                    and self._faults.divergence_guard)
        # Last-good snapshot for DivergenceError recovery: taken BEFORE
        # the chunk consumes (donates) the state, refreshed per chunk.
        snap = jax.device_get(state) if guard_on else None
        checked = 0
        # Per-round (C,) finite masks aligned with `history` — the
        # DivergenceError diagnostic payload (fault-path scan output).
        finites: List[Any] = []
        params_C, opt_C, key = state.params_C, state.opt_C, state.key
        history: List[RoundRecord] = []
        sim_time = state.sim_time
        r0 = state.round
        weights, t_cp_arg = self._chunk_args()
        R = min(eval_every, max_rounds)
        done, stop = 0, False
        while done < max_rounds and not stop:
            n = min(R, max_rounds - done)
            if max_sim_time:
                # Pre-chunk host-stream positions: if the budget stop
                # truncates mid-chunk, the streams are rewound to the
                # truncation round so the returned state's snapshots
                # agree with its round cursor (see below).
                pre_data = self._snapshot_iters(iters)
                pre_stream = stream.state() if stream is not None else None
            with TraceAnnotation("defl.chunk_inputs"):
                xs, host = self._chunk_inputs(iters, stream, R, n)
            with TraceAnnotation("defl.dispatch"):
                params_C, opt_C, key, ys = self._chunk_call(
                    params_C, opt_C, key, weights, t_cp_arg, xs)
            # The chunk's only device->host sync: one stacked fetch of all
            # per-round scan outputs.
            with TraceAnnotation("defl.fetch"):
                ys = jax.device_get(ys)
            with TraceAnnotation("defl.records"):
                records = self._chunk_records(ys, host, n, r0 + done,
                                              sim_time)
            if max_sim_time:
                for j, rec in enumerate(records):
                    if rec.sim_time >= max_sim_time:
                        if j + 1 < n:
                            # The host streams consumed the whole chunk
                            # but the run stops after j+1 of its rounds:
                            # restore the pre-chunk positions and replay
                            # exactly j+1 rounds, so a resume from the
                            # returned state draws round j+2's data and
                            # realization (not round n+1's). The device
                            # params remain end-of-chunk — the documented
                            # deviation; the stream-driven accounting
                            # (clocks, participation) stays exact.
                            self._rewind_chunk(iters, stream, pre_data,
                                               pre_stream, j + 1)
                        records = records[:j + 1]
                        stop = True
                        break
            history.extend(records)
            done = history[-1].round - r0
            sim_time = history[-1].sim_time
            if guard_on:
                if "finite" in ys:
                    finites.extend(ys["finite"][:len(records)])
                checked = self._raise_if_diverged(
                    history, checked, snap,
                    finites=finites if finites else None)
                with TraceAnnotation("defl.snapshot"):
                    snap = jax.device_get(self._rebuild_state(
                        state, params_C, opt_C, key, r0 + done, sim_time,
                        iters, stream))
            rec = history[-1]
            k = rec.round - r0
            at_boundary = k % eval_every == 0 or k == max_rounds
            if self.eval_fn and at_boundary:
                with TraceAnnotation("defl.eval"):
                    ev = self.eval_fn(self._params_from(params_C))
                rec.test_acc = float(ev.get("acc", np.nan))
                rec.test_loss = float(ev.get("loss", np.nan))
                if (target_acc and rec.test_acc is not None
                        and rec.test_acc >= target_acc):
                    stop = True
        with TraceAnnotation("defl.snapshot"):
            new_state = self._rebuild_state(
                state, params_C, opt_C, key, r0 + len(history), sim_time,
                iters, stream)
        return new_state, SimResult(
            history=history, params=self._params_from(params_C),
            label=self.label, fed=self.fed)

    def _params_from(self, params_C):
        if self.backend == "loop":
            return params_C
        return jax.tree.map(lambda x: x[0], params_C)

    # -- training -----------------------------------------------------------
    @staticmethod
    def _sync_history(history: List[RoundRecord]) -> None:
        """Host-sync boundary: materialize any still-on-device train losses
        (and, on the fault path, participant counts)."""
        for rec in history:
            if not isinstance(rec.train_loss, float):
                rec.train_loss = float(rec.train_loss)
            if rec.n_participants is not None and not isinstance(
                    rec.n_participants, int):
                rec.n_participants = int(rec.n_participants)

    @functools.partial(jax.profiler.annotate_function, name="defl.run")
    def run(
        self,
        state: SimState,
        max_rounds: int = 200,
        target_acc: Optional[float] = None,
        eval_every: int = 1,
        max_sim_time: Optional[float] = None,
        recovery: Optional[RecoveryPolicy] = None,
    ):
        """Run up to `max_rounds` MORE rounds from `state`:
        (state', SimResult). Round numbering and the Eq. 8 clock continue
        from the state's cursors, so a run resumed from a checkpointed
        state produces exactly the history an uninterrupted run would.
        The input state's device buffers are donated (consumed) — rebind
        to the returned state; branch points need a host snapshot first
        (`jax.device_get(state)` / `save_state`).

        `recovery=RecoveryPolicy(...)` arms the auto-recovering driver:
        a DivergenceError (divergence-guarded fault runs) is caught, the
        run rewinds to the error's last-good SimState snapshot, the
        learning rate is deterministically backed off (and the guard
        norm optionally tightened), and the run resumes — up to
        max_restarts attempts, each logged in SimResult.restarts."""
        _validate_run_args(max_rounds, eval_every)
        if self.backend == "async":
            if recovery is not None:
                raise ValueError(
                    "recovery=RecoveryPolicy requires the divergence-"
                    "guarded sync backends — backend='async' has no "
                    "in-graph guard to raise from. Use backend='scan'.")
            return self._run_async(state, max_rounds, target_acc,
                                   eval_every, max_sim_time)
        if recovery is not None:
            return self._run_recovering(state, recovery, max_rounds,
                                        target_acc, eval_every, max_sim_time)
        if self.backend == "scan":
            return self._run_scan(state, max_rounds, target_acc, eval_every,
                                  max_sim_time)
        iters, stream = self._materialize(state)
        guard_on = (self._faults is not None
                    and self._faults.divergence_guard)
        snap = jax.device_get(state) if guard_on else None
        checked = 0
        finites: List[Any] = []
        params_C, opt_C, key = state.params_C, state.opt_C, state.key
        history: List[RoundRecord] = []
        sim_time = state.sim_time
        r0 = state.round
        T_cm, T_cp = self.round_times()
        V = self.fed.local_rounds
        update_bits = self._update_bits()
        for k in range(1, max_rounds + 1):
            real = None
            t_cm_clients = None
            n_attempts = None
            cohort = None
            if self.scenario is not None:
                # Realize the round (host-side numpy: mask + channel), take
                # the Eq. 8 clock as the straggler max over participating
                # clients, and feed the same realization to the round step.
                if self._sampled:
                    cohort = stream.draw_cohort()
                real = stream.next_round()
                if self._faults is not None:
                    real, t_cm_clients, n_attempts = self._fault_round(real)
                else:
                    t_cm_clients = delay.per_client_uplink_time(
                        update_bits, self.wireless, self.pop.p, real.h)
                if cohort is not None:
                    if self._spare:
                        # K+spare candidates -> the K feasible-fastest,
                        # ranked on the M-wide effective uplink times.
                        cohort = self._select_cohorts(
                            np.asarray(cohort)[None],
                            np.asarray(t_cm_clients, np.float64)[None])[0]
                    # Fault semantics above resolved M-wide; everything
                    # from here on (clock, bits, attempts, the step) is
                    # conditioned on the cohort's columns.
                    real = self._gather_real(real, cohort)
                    t_cm_clients = np.asarray(t_cm_clients)[cohort]
                    if n_attempts is not None:
                        n_attempts = int(real.attempts.sum())
                t_cp_vec = (self._t_cp_clients if cohort is None
                            else self._t_cp_clients[cohort])
                T_cm, T_cp = delay.masked_round_times(
                    t_cp_vec, t_cm_clients, real.clock_mask)
            if self.backend == "loop":
                params_C, opt_C, key, metrics = self._round_loop(
                    params_C, opt_C, key, iters, real)
            else:
                params_C, opt_C, key, metrics = self._round_batched(
                    params_C, opt_C, key, iters, real, t_cm_clients, cohort)
            sim_time += delay.round_time(T_cm, T_cp, V,
                                         deadline=self._deadline)
            rej = metrics.get("rejected")
            if rej is not None:
                # Device scalar on the batched backend — the host sync is
                # the per-round parity reference's price; the scan
                # backend reads it from the chunk's stacked outputs.
                rej = bool(rej)
                if rej and self._quorum_policy == "reject":
                    sim_time += self._faults.redispatch_cost
            n_part = metrics.get("n_participants")
            if n_attempts is not None:
                bits = float(n_attempts * update_bits)
            else:
                bits = float(
                    (self.fed.n_devices if n_part is None else n_part)
                    * update_bits)
            rec = RoundRecord(
                round=r0 + k, sim_time=sim_time, T_cm=T_cm, T_cp=T_cp,
                train_loss=metrics["train_loss"],
                n_participants=n_part,
                uplink_bits=bits, rejected=rej)
            history.append(rec)
            if guard_on:
                finites.append(metrics.get("finite"))
            at_boundary = k % eval_every == 0 or k == max_rounds
            if self.eval_fn and at_boundary:
                ev = self.eval_fn(self._params_from(params_C))
                rec.test_acc = float(ev.get("acc", np.nan))
                rec.test_loss = float(ev.get("loss", np.nan))
            if at_boundary:
                self._sync_history(history)
                if guard_on:
                    checked = self._raise_if_diverged(
                        history, checked, snap, finites=finites)
                    snap = jax.device_get(self._rebuild_state(
                        state, params_C, opt_C, key, r0 + k, sim_time,
                        iters, stream))
            if target_acc and rec.test_acc is not None and rec.test_acc >= target_acc:
                break
            if max_sim_time and sim_time >= max_sim_time:
                break
        self._sync_history(history)
        if guard_on:
            self._raise_if_diverged(history, checked, snap, finites=finites)
        new_state = self._rebuild_state(
            state, params_C, opt_C, key, r0 + len(history), sim_time,
            iters, stream)
        return new_state, SimResult(
            history=history, params=self._params_from(params_C),
            label=self.label, fed=self.fed)

    # -- crash-safe auto-recovery -------------------------------------------
    def _recovery_variant(self, lr_scale: float, fm) -> "Simulator":
        """A rebuilt Simulator for a restart attempt: identical to this
        one except the optimizer's updates are scaled by `lr_scale`
        (exact lr backoff for SGD-family optimizers) and the FaultModel
        is replaced by `fm` (guard-tightened when the policy asks).
        Rebuilding recompiles the round graphs — acceptable on the rare
        recovery path, and the only way the scale/guard become compiled
        constants (determinism over cleverness)."""
        kw = dict(self._ctor)
        kw["opt"] = _scaled_optimizer(kw["opt"], lr_scale)
        if fm is not None:
            if kw.get("faults") is not None and kw["faults"].active:
                kw["faults"] = fm
            elif kw.get("scenario") is not None:
                sc = scenarios.get(kw["scenario"])
                if sc.faults is not None and sc.faults.active:
                    kw["scenario"] = sc.replace(faults=fm)
        return Simulator(**kw)

    def _run_recovering(self, state, recovery, max_rounds, target_acc,
                        eval_every, max_sim_time):
        """The auto-recovering driver behind run(recovery=...): run,
        catch DivergenceError, rewind to the carried last-good SimState,
        deterministically back off the learning rate (and optionally
        tighten the guard norm), re-run — bounded by
        recovery.max_restarts, with every restart logged in the returned
        SimResult.restarts audit trail. The error's .state is a HOST
        snapshot (never donated away), so resuming from it is safe."""
        recovery.validate()
        sim = self
        fm = self._faults
        lr_scale = 1.0
        restarts: List[dict] = []
        prefix: List[RoundRecord] = []
        r_start = int(state.round)
        attempt = 0
        while True:
            rounds_left = max_rounds - (int(state.round) - r_start)
            try:
                state, res = sim.run(
                    state, max_rounds=rounds_left, target_acc=target_acc,
                    eval_every=eval_every, max_sim_time=max_sim_time)
            except DivergenceError as e:
                attempt += 1
                if e.state is None or attempt > recovery.max_restarts:
                    raise
                good = int(e.state.round)
                # Keep only the records the snapshot actually covers —
                # the rounds past it (same chunk as the failure) re-run.
                prefix.extend(r for r in e.history if r.round <= good)
                lr_scale *= recovery.lr_backoff
                if (recovery.tighten_guard is not None and fm is not None
                        and fm.max_update_norm is not None
                        and np.isfinite(fm.max_update_norm)):
                    fm = dataclasses.replace(
                        fm,
                        max_update_norm=(fm.max_update_norm
                                         * recovery.tighten_guard))
                restarts.append({
                    "attempt": attempt,
                    "round": int(e.round),
                    "resume_round": good,
                    "lr_scale": lr_scale,
                    "max_update_norm": (
                        None if fm is None else fm.max_update_norm),
                    "error": str(e)})
                sim = self._recovery_variant(lr_scale, fm)
                state = e.state
                continue
            res.history = prefix + res.history
            res.restarts = restarts
            return state, res

    # -- fleet execution (vmapped multi-seed / multi-state) ------------------
    def run_fleet(
        self,
        seeds: Optional[Iterable[int]] = None,
        states: Optional[Sequence[SimState]] = None,
        max_rounds: int = 200,
        eval_every: int = 1,
        target_acc: Optional[float] = None,
        max_sim_time: Optional[float] = None,
    ) -> FleetResult:
        """Run S member states in lockstep with ONE vmapped dispatch per
        chunk (scan backend only): the compiled chunk fn is mapped over a
        leading fleet axis (mesh_rounds.build_fleet_chunk), so S seeds
        cost one compiled call per eval_every rounds instead of S.

        Pass `seeds` (each becomes `init(seed)`) or pre-built `states`
        (e.g. restored checkpoints — they must share a round cursor so the
        lockstep chunking lines up). Per-member results are bit-identical
        to sequential `run()` calls at the same seeds: host-side draws
        (data indices, masks, channel drift) are per-member and vmap only
        batches the already-pure device graph.

        Early stopping (target_acc / max_sim_time) is per-member: a
        member that reaches the target (or exhausts the simulated-time
        budget) is marked done and rides along FROZEN — its subsequent
        chunks feed an all-False `valid` mask, the in-graph done-mask
        that turns every state write (params/opt/PRNG advance) into a
        no-op, while its host streams stop being consumed. The frozen
        member's history and final state match a solo early-stopped
        `run()` bit for bit (tests/test_study.py). Eval at chunk
        boundaries goes through `eval_batch_fn` (one vmapped dispatch for
        the whole stacked member axis) when the Simulator has one,
        falling back to a per-member host loop otherwise."""
        if self.backend != "scan":
            raise ValueError(
                f"run_fleet requires backend='scan', not {self.backend!r}")
        if target_acc and self.eval_fn is None and self.eval_batch_fn is None:
            raise ValueError(
                "run_fleet(target_acc=...) needs an eval_fn/eval_batch_fn "
                "(build the spec with with_eval=True)")
        if not callable(self._data_src):
            # A fixed iterator list is ONE set of live objects: every
            # member's _materialize would alias it, so members would
            # consume each other's batch stream and the per-seed
            # bit-identity contract would silently break.
            raise ValueError(
                "run_fleet needs a per-seed data factory: this Simulator "
                "was built with a fixed iterator list, which all fleet "
                "members would share (and advance past each other). "
                "Construct it with data=lambda seed: [...fresh iterators...] "
                "or via ExperimentSpec.build().")
        _validate_run_args(max_rounds, eval_every)
        if states is None:
            if seeds is None:
                raise ValueError("run_fleet needs seeds=... or states=...")
            seeds = [int(s) for s in seeds]
            if not seeds:
                raise ValueError("run_fleet needs at least one member")
            # Fresh-seed fast path: every member starts from the SAME
            # replicated params/opt (only the PRNG key differs), so the
            # stacked (S, C, ...) device state is one broadcast per leaf
            # instead of S eager init() + a per-leaf stack — at S=8 that
            # is hundreds of small dispatches saved per call.
            base_p, base_o = self._fleet_init_base()
            S = len(seeds)
            bcast = lambda x: jnp.broadcast_to(x[None], (S, *x.shape))  # noqa: E731
            params_S = jax.tree.map(bcast, base_p)
            opt_S = jax.tree.map(bcast, base_o)
            key_S = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
            states = [SimState(params_C=None, opt_C=None, key=None, seed=s)
                      for s in seeds]
        else:
            states = list(states)
            if not states:
                raise ValueError("run_fleet needs at least one member")
            if len({st.round for st in states}) != 1:
                raise ValueError(
                    "fleet members must share a round cursor (got rounds "
                    f"{sorted({st.round for st in states})}) — lockstep "
                    "chunking has no per-member ragged tails")
            S = len(states)
            params_S, opt_S, key_S = jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[(st.params_C, st.opt_C, st.key) for st in states])
            # Fleet-memory ceiling fix: drop our references to the members'
            # unstacked device buffers now that the stacked (S, C, ...)
            # copies exist — otherwise S per-member state trios stay alive
            # alongside the (donated) stacked fleet state for the whole
            # run, doubling peak device memory. The caller's own state
            # objects are unaffected; the returned states carry fresh
            # slices of the final stacked buffers.
            states = [dataclasses.replace(
                st, params_C=None, opt_C=None, key=None) for st in states]
        mats = [self._materialize(st) for st in states]
        weights, t_cp_arg = self._chunk_args()
        fleet_fn = self._get_fleet_fn()
        histories: List[List[RoundRecord]] = [[] for _ in range(S)]
        times = [st.sim_time for st in states]
        r0 = states[0].round
        R = min(eval_every, max_rounds)
        done = 0
        finished = [False] * S
        last_xs: List[Any] = [None] * S
        can_eval = self.eval_fn is not None or self.eval_batch_fn is not None
        env_S = t_cp_S = None
        if self._envelope:
            # Loop-invariant: the envelope fleet maps t_cp and env per
            # member (the Study's arms differ in b); a same-spec fleet
            # broadcasts its shared values onto the member axis once.
            bcast = lambda x: jnp.broadcast_to(x[None], (S, *x.shape))  # noqa: E731
            env_S = jax.tree.map(bcast, self._trivial_env())
            t_cp_S = None if t_cp_arg is None else bcast(t_cp_arg)
        # LOCKSTEP NOTE: the per-chunk member bookkeeping below mirrors
        # study._run_group's (multi-arm) driver — both are bit-parity
        # tested against solo runs; change them together.
        while done < max_rounds and not all(finished):
            n = min(R, max_rounds - done)
            per: List[Any] = []
            pre: List[Any] = []
            for s in range(S):
                if finished[s]:
                    # Done-mask: an all-zero xs (valid=False rows) makes
                    # the member's whole chunk an in-graph no-op — params,
                    # opt state and PRNG key ride along untouched — and
                    # its host streams are not consumed.
                    per.append((jax.tree.map(np.zeros_like, last_xs[s]),
                                None))
                    pre.append(None)
                    continue
                if max_sim_time:
                    pre.append((self._snapshot_iters(mats[s][0]),
                                mats[s][1].state()
                                if mats[s][1] is not None else None))
                else:
                    pre.append(None)
                per.append(self._chunk_inputs(mats[s][0], mats[s][1], R, n))
                last_xs[s] = per[s][0]
            # One stacked (S, R, ...) upload per chunk for the whole fleet.
            xs = jax.tree.map(lambda *ls: np.stack(ls), *[p[0] for p in per])
            if self._envelope:
                params_S, opt_S, key_S, ys = fleet_fn(
                    params_S, opt_S, key_S, weights, t_cp_S,
                    self._data_dev, xs, env_S)
            else:
                params_S, opt_S, key_S, ys = fleet_fn(
                    params_S, opt_S, key_S, weights, t_cp_arg,
                    self._data_dev, xs)
            ys = jax.device_get(ys)  # leaves (S, R): ONE fetch per chunk
            for s in range(S):
                if finished[s]:
                    continue
                recs = self._chunk_records(
                    {k2: v[s] for k2, v in ys.items()}, per[s][1], n,
                    r0 + done, times[s])
                if max_sim_time:
                    for j, rec in enumerate(recs):
                        if rec.sim_time >= max_sim_time:
                            if j + 1 < n:
                                # Same semantics as the solo driver: the
                                # history truncates at the first exceeding
                                # round and the member's host streams
                                # rewind to it (device state stays
                                # end-of-chunk, the documented deviation).
                                self._rewind_chunk(
                                    mats[s][0], mats[s][1], pre[s][0],
                                    pre[s][1], j + 1)
                            recs = recs[:j + 1]
                            finished[s] = True
                            break
                histories[s].extend(recs)
                times[s] = histories[s][-1].sim_time
            done += n
            if can_eval and (done % eval_every == 0 or done == max_rounds):
                evs = self._eval_members(params_S, S)
                for s in range(S):
                    rec = histories[s][-1]
                    # Only members whose history reaches this boundary get
                    # the eval record — a member truncated mid-chunk by
                    # max_sim_time did not (its solo run would not eval
                    # there either).
                    if rec.round != r0 + done:
                        continue
                    rec.test_acc = float(evs[s].get("acc", np.nan))
                    rec.test_loss = float(evs[s].get("loss", np.nan))
                    if (target_acc and rec.test_acc is not None
                            and rec.test_acc >= target_acc):
                        finished[s] = True
        # One jitted call slices every member's (params, opt, key, global
        # model) out of the stacked buffers — per-member eager indexing
        # would cost S x leaves separate dispatches.
        members = _unstack_members(
            (params_S, opt_S, key_S,
             jax.tree.map(lambda x: x[:, 0], params_S)), S)
        out_states, results = [], []
        for s in range(S):
            p_s, o_s, k_s, global_s = members[s]
            st = self._rebuild_state(
                states[s], p_s, o_s, k_s, r0 + len(histories[s]), times[s],
                mats[s][0], mats[s][1])
            out_states.append(st)
            results.append(SimResult(
                history=histories[s], params=global_s,
                label=f"{self.label}[seed={st.seed}]", fed=self.fed))
        return FleetResult(states=out_states, results=results)

    def _eval_members(self, params_S, S: int) -> List[Dict]:
        """Chunk-boundary eval for a stacked fleet: ONE vmapped dispatch
        over the member axis via eval_batch_fn when available (each dict
        value comes back (S,)), else the host-loop fallback over unstacked
        globals."""
        globals_S = jax.tree.map(lambda x: x[:, 0], params_S)
        if self.eval_batch_fn is not None:
            ev = self.eval_batch_fn(globals_S)
            return [{k: v[s] for k, v in ev.items()} for s in range(S)]
        members = _unstack_members(globals_S, S)
        return [self.eval_fn(members[s]) for s in range(S)]


# ---------------------------------------------------------------------------
# Deprecated stateful facade
# ---------------------------------------------------------------------------

_FLSIM_WARNED = False


class FLSimulation:
    """Deprecated: the old mutable simulator interface, now a thin shim
    holding a (Simulator, SimState) pair. Prefer building a `Simulator`
    directly (or declaratively via
    `repro.federated.experiment.ExperimentSpec.build()`) and threading
    `SimState` through `run()` — that is what unlocks `run_fleet`,
    checkpoint/resume, and multi-seed sweeps. Emits one
    `DeprecationWarning` per process."""

    def __init__(
        self,
        loss_fn: Callable,
        init_params: Any,
        client_iterators: List,
        data_sizes: np.ndarray,
        fed: FedConfig,
        opt: Optimizer,
        pop: delay.DevicePopulation,
        wireless: Optional[WirelessConfig] = None,
        eval_fn: Optional[Callable] = None,
        label: str = "defl",
        backend: str = "scan",
        impl: str = "xla",
        scenario: Optional[Any] = None,
    ):
        global _FLSIM_WARNED
        if not _FLSIM_WARNED:
            warnings.warn(
                "FLSimulation is deprecated: build a "
                "repro.federated.simulation.Simulator (or an "
                "repro.federated.experiment.ExperimentSpec) and thread "
                "SimState through run()/run_fleet() instead.",
                DeprecationWarning, stacklevel=2)
            _FLSIM_WARNED = True
        self.sim = Simulator(
            loss_fn, init_params, client_iterators, data_sizes, fed, opt,
            pop, wireless=wireless, eval_fn=eval_fn, label=label,
            backend=backend, impl=impl, scenario=scenario)
        self.state = self.sim.init(fed.seed)

    def __getattr__(self, name):
        # Delegate config views (fed, pop, wireless, trace_count,
        # _update_bits, round_times, _data_dev, ...) to the core. Note
        # __getattr__ only fires for names not found on the shim itself.
        if name in ("sim", "state"):
            raise AttributeError(name)
        return getattr(self.sim, name)

    @property
    def eval_fn(self):
        return self.sim.eval_fn

    @eval_fn.setter
    def eval_fn(self, fn):
        self.sim.eval_fn = fn

    @property
    def params(self):
        return self.sim.params(self.state)

    def block_until_ready(self) -> None:
        self.sim.block_until_ready(self.state)

    def run_round(self, real=None, t_cm_clients=None) -> Dict:
        self.state, metrics = self.sim.run_round(self.state, real,
                                                 t_cm_clients)
        return metrics

    def run(
        self,
        max_rounds: int = 200,
        target_acc: Optional[float] = None,
        eval_every: int = 1,
        max_sim_time: Optional[float] = None,
    ) -> SimResult:
        self.state, res = self.sim.run(
            self.state, max_rounds=max_rounds, target_acc=target_acc,
            eval_every=eval_every, max_sim_time=max_sim_time)
        return res
