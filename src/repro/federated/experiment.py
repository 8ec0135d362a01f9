"""Declarative experiment API: `ExperimentSpec.build() -> Simulator`.

The paper's results are *comparisons* — DEFL vs FedAvg vs Rand across
heterogeneous populations (Fig. 2), swept over epsilon/batch/theta/rounds
(Fig. 1) — and every benchmark/example/test used to hand-wire the same
13-argument simulator constructor to express one of them. An
`ExperimentSpec` is the frozen value form of that wiring: model, data +
partition, population, wireless, plan-or-fed, scenario, compression and
backend, with `build()` materializing the `Simulator` and a small
registry for named configurations:

    spec = experiment.ExperimentSpec(
        fed=FedConfig(n_devices=10, epsilon=0.01, c=4.0, lr=0.05),
        model="mnist_cnn", dataset="mnist", scenario="stragglers",
        plan=True)                      # solve (b*, theta*) before running
    sim = spec.build()
    state, res = sim.run(sim.init(), max_rounds=100, eval_every=10)
    fleet = sim.run_fleet(seeds=range(8), max_rounds=100, eval_every=10)

Specs are plain frozen dataclasses: `replace(...)` derives sweeps, the
registry (`experiment.register/get/names`) shares baseline configurations
between benchmarks, examples and tests.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from dataclasses import dataclass
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ComputeConfig, FedConfig, WirelessConfig
from repro.core import defl, delay
from repro.data import BatchIterator, make_cifar_like, make_mnist_like
from repro.data.pipeline import ClientDataPool
from repro.federated import scenarios
from repro.federated.events import AsyncSpec
from repro.federated.faults import FaultModel
from repro.federated.traces import TraceSpec, replay_scenario
from repro.federated.partition import (partition_dirichlet, partition_sizes,
                                       partition_virtual)
from repro.federated.simulation import Simulator
from repro.models import cnn
from repro.optim import sgd
from repro.utils.tree import tree_bytes

# Calibration (see EXPERIMENTS.md §Claims): per-sample compute ~10 ms at
# b=1 on the 2 GHz edge GPU pins theta* ~= 0.13-0.15 (the paper's reported
# operating point, independent of c), and c ~= 4.0 then pins b* ~= 32
# (the paper's "rounded off" batch size) at eps = 0.01.
CALIBRATED_COMPUTE = ComputeConfig(bits_per_sample=6.8e5)
CALIBRATED_C = 4.0

# Model registry: named CNN configurations the spec can reference (a
# literal CNNConfig is also accepted for one-off model sweeps).
MODELS = {
    "mnist_cnn": cnn.mnist_cnn,
    "mnist_cnn_small": cnn.mnist_cnn_small,
    "mnist_cnn_tiny": cnn.mnist_cnn_tiny,
    "cifar_cnn": cnn.cifar_cnn,
}

DATASETS = {"mnist": make_mnist_like, "cifar": make_cifar_like}

# Dense device state above this many clients is almost certainly a
# mistake (the stacked params/opt carry one lane per client): emitting a
# first-party DeprecationWarning here — an ERROR under the tier-1 filter
# — pushes callers onto PopulationSpec(M, cohort=CohortSpec(K)).
DENSE_M_DEPRECATION_THRESHOLD = 4096


@dataclass(frozen=True)
class CohortSpec:
    """Per-round sampled participation: K clients drawn from the
    population each round.

    K        cohort size — the device-resident client state is O(K).
    sampler  'uniform' (each round's cohort uniform without replacement)
             | 'weighted' (D_m-weighted Gumbel top-K without
             replacement: data-rich clients are drawn more often).
    spare    over-provisioning: each round draws K + spare candidates
             from the same cohort RNG stream and keeps the K deadline-
             feasible-fastest (ties by client index) — resilience
             against deadline-cut stragglers without growing the
             device-resident cohort. spare=0 (default) is bit-identical
             to today's draw.
    """

    K: int
    sampler: str = "uniform"
    spare: int = 0

    def __post_init__(self):
        if self.K < 1:
            raise ValueError(f"CohortSpec.K must be >= 1, got {self.K}")
        if self.sampler not in ("uniform", "weighted"):
            raise ValueError(
                f"unknown CohortSpec.sampler {self.sampler!r}; expected "
                "'uniform' or 'weighted'")
        if not isinstance(self.spare, int) or self.spare < 0:
            raise ValueError(
                f"CohortSpec.spare must be an int >= 0, got {self.spare!r}")


@dataclass(frozen=True)
class PopulationSpec:
    """The client population, declaratively: its size and (optionally)
    the per-round participation regime.

    M       population size. Plain `fed.n_devices` (no PopulationSpec)
            stays sugar for a dense M-client population — identical
            simulators, bit for bit.
    cohort  None runs dense (every client computes every round, device
            state O(M)); CohortSpec(K) runs sampled participation
            (device state O(K), population model host-side O(M)) —
            required above DENSE_M_DEPRECATION_THRESHOLD clients.
    """

    M: int
    cohort: Optional[CohortSpec] = None

    def __post_init__(self):
        if self.M < 1:
            raise ValueError(f"PopulationSpec.M must be >= 1, got {self.M}")
        if self.cohort is not None and self.cohort.K > self.M:
            raise ValueError(
                f"cohort K={self.cohort.K} exceeds population M={self.M}")
        if (self.cohort is not None
                and self.cohort.K + self.cohort.spare > self.M):
            raise ValueError(
                f"cohort K+spare={self.cohort.K + self.cohort.spare} "
                f"exceeds population M={self.M}")


@dataclass(frozen=True)
class ExperimentSpec:
    """One experiment, declaratively. All fields have paper-faithful
    defaults; `replace()` derives variants.

    fed            the federated/DEFL configuration (M, b, theta, lr,
                   compression, ...). When `plan=True`, b/theta/V are
                   re-solved against the realized population and `fed`
                   provides the problem constants (epsilon, nu, c, M).
    model          registry name (MODELS) or a literal cnn.CNNConfig.
    dataset        'mnist' | 'cifar' (synthetic *-like tasks).
    n_train/n_test dataset sizes; alpha the Dirichlet non-IID knob.
    seed           draw seed for dataset, partition and population —
                   fixed per experiment; *run* seeds (PRNG key, scenario
                   stream, batch order) are chosen at `Simulator.init` /
                   `run_fleet` time, which is what multi-seed confidence
                   bands vary.
    scenario       registered edge-scenario name (scenarios.py) or None;
                   draws the population and the per-round
                   participation/channel stream.
    trace          optional traces.TraceSpec: replay a recorded JSONL
                   device-state log as the scenario source (deterministic
                   presence/loss/channel overlay on the unchanged
                   backends). Mutually exclusive with `scenario` — the
                   log IS the realization stream, so a registry scenario
                   cannot also drive it; the validation error names both
                   fields. `scenario_ref()` resolves whichever is set.
    faults         optional faults.FaultModel overriding (or adding to)
                   the scenario's failure semantics — deadlines, uplink
                   retransmission, crash/rejoin, divergence guards. None
                   keeps the scenario's own `faults` (if any).
    heterogeneity  population lognormal spread when no scenario is given.
    population     optional PopulationSpec. When set, its M overrides
                   fed.n_devices (the M-free way to scale a registered
                   spec to 10^4-10^6 clients) and its CohortSpec turns on
                   K-client sampled participation: device state O(K),
                   per-round cohorts drawn host-side from the M-client
                   population. `PopulationSpec(M)` with no cohort is
                   exactly `fed.n_devices=M` (dense — deprecated above
                   DENSE_M_DEPRECATION_THRESHOLD clients).
    shard_clients  shard the stacked client axis over all JAX devices
                   (scan backend; prototype on CPU via
                   XLA_FLAGS=--xla_force_host_platform_device_count=N).
    plan           solve Alg. 1 for (b*, theta*) against the population
                   before building (plan-or-fed: False runs `fed` as-is).
                   Under a CohortSpec the Eq. 12 effective M is the
                   cohort's K (defl.make_plan cohort_size).
    batch_cap      dataset-bounded cap applied to a planned b* (paper
                   §VI-B discussion); None disables.
    backend        'scan' (default) | 'batched' | 'loop' | 'async'.
    async_spec     events.AsyncSpec for backend='async': buffered
                   staleness-weighted aggregation over a compiled
                   device-side event queue. Requires backend='async'
                   (and vice versa). Mutually exclusive with sampled
                   participation (population.cohort), shard_clients and
                   quorum/update-norm fault guards — the validation
                   errors name the offending fields.
    """

    fed: FedConfig = FedConfig()
    population: Optional[PopulationSpec] = None
    shard_clients: bool = False
    model: Union[str, cnn.CNNConfig] = "mnist_cnn"
    dataset: str = "mnist"
    n_train: int = 1500
    n_test: int = 400
    alpha: float = 1.0
    seed: int = 0
    scenario: Optional[str] = None
    trace: Optional[TraceSpec] = None
    faults: Optional[FaultModel] = None
    heterogeneity: float = 0.0
    compute: ComputeConfig = CALIBRATED_COMPUTE
    wireless: WirelessConfig = WirelessConfig()
    plan: bool = False
    plan_method: str = "closed_form"
    batch_cap: Optional[int] = 32
    backend: str = "scan"
    impl: str = "xla"
    with_eval: bool = True
    label: str = ""
    async_spec: Optional[AsyncSpec] = None

    def __post_init__(self):
        # Satellite knob-compatibility contract: mutually-exclusive
        # combinations fail at spec construction, naming the fields, so
        # a bad sweep dies before any build()/compile cost is paid.
        if self.trace is not None and self.scenario is not None:
            raise ValueError(
                f"ExperimentSpec: trace={self.trace.name!r} and scenario="
                f"{self.scenario!r} are mutually exclusive (fields "
                "scenario, trace) — a TraceSpec replays its own recorded "
                "device-state stream, so a registry scenario cannot also "
                "drive the population; drop one of them")
        if self.backend == "async" and self.async_spec is None:
            raise ValueError(
                "ExperimentSpec: backend='async' requires async_spec="
                "AsyncSpec(...) (fields backend, async_spec)")
        if self.async_spec is not None and self.backend != "async":
            raise ValueError(
                f"ExperimentSpec: async_spec is set but backend="
                f"{self.backend!r}; asynchronous aggregation requires "
                "backend='async' (fields backend, async_spec)")
        if self.backend != "async":
            return
        if self.population is not None and self.population.cohort is not None:
            raise ValueError(
                "ExperimentSpec: backend='async' is incompatible with "
                "sampled participation (fields backend, population.cohort) "
                "— the event queue tracks every client; use a dense "
                "PopulationSpec(M) or drop the CohortSpec")
        if self.shard_clients:
            raise ValueError(
                "ExperimentSpec: backend='async' is incompatible with "
                "client sharding (fields backend, shard_clients) — the "
                "event queue pops one client per step, which does not "
                "shard across devices")
        fm = self.effective_faults()
        if fm is not None and fm.min_quorum is not None:
            raise ValueError(
                "ExperimentSpec: backend='async' is incompatible with "
                "quorum gating (fields backend, faults.min_quorum) — "
                "rounds are buffer fills, not synchronized cohorts; use "
                "AsyncSpec.buffer_size to set the fill threshold")
        if fm is not None and fm.max_update_norm is not None:
            raise ValueError(
                "ExperimentSpec: backend='async' is incompatible with "
                "update-norm clipping (fields backend, "
                "faults.max_update_norm); deadline/retransmission/crash "
                "fault channels do compose with async")

    def replace(self, **kw) -> "ExperimentSpec":
        return dataclasses.replace(self, **kw)

    # -- resolution ---------------------------------------------------------
    def model_config(self) -> cnn.CNNConfig:
        if isinstance(self.model, str):
            try:
                return MODELS[self.model]()
            except KeyError:
                raise KeyError(
                    f"unknown model {self.model!r}; registered: "
                    f"{tuple(MODELS)}") from None
        return self.model

    def scenario_ref(self) -> Union[str, scenarios.Scenario, None]:
        """The scenario source this spec actually runs: the ReplayScenario
        materialized from `trace` when set, else the registry `scenario`
        name, else None. Every scenario consumer (faults, population,
        plan, build) resolves through this, so a trace-driven spec rides
        the identical code paths as a registry-scenario one."""
        if self.trace is not None:
            return replay_scenario(self.trace)
        return self.scenario

    def effective_faults(self) -> Optional[FaultModel]:
        """The FaultModel this spec actually runs under: the spec's own
        override when set, else the scenario's, else None. Inactive
        models normalize to None (they are bit-identical to no model)."""
        fm = self.faults
        ref = self.scenario_ref()
        if fm is None and ref is not None:
            fm = scenarios.get(ref).faults
        return fm if fm is not None and fm.active else None

    def n_devices(self) -> int:
        """Population size M: PopulationSpec.M when given (it overrides
        fed.n_devices), else fed.n_devices."""
        return (self.fed.n_devices if self.population is None
                else self.population.M)

    def cohort_spec(self) -> Optional[CohortSpec]:
        """The sampled-participation regime, or None for dense."""
        return None if self.population is None else self.population.cohort

    def base_fed(self) -> FedConfig:
        """`fed` with the PopulationSpec's M applied (the single source of
        truth every downstream consumer — plan, build, study grouping —
        resolves n_devices through)."""
        M = self.n_devices()
        if M == self.fed.n_devices:
            return self.fed
        return dataclasses.replace(self.fed, n_devices=M)

    def device_population(self) -> delay.DevicePopulation:
        """Draw the (M,) device population (compute + channel). Renamed
        from `population()`, which the PopulationSpec field now owns."""
        M = self.n_devices()
        ref = self.scenario_ref()
        if ref is not None:
            return scenarios.get(ref).population(
                M, self.compute, self.wireless, self.seed)
        return delay.draw_population(
            M, self.compute, self.wireless, self.seed, self.heterogeneity)

    def update_bits(self) -> float:
        """Raw wire size of one model update (plan input; the simulator
        separately applies compression accounting at run time)."""
        cfg = self.model_config()
        params = jax.eval_shape(
            lambda k: cnn.init_cnn(cfg, k), jax.random.PRNGKey(0))
        return tree_bytes(params) * 8.0

    def _solve_plan(self, pop: delay.DevicePopulation,
                    ) -> Optional[defl.DEFLPlan]:
        if not self.plan:
            return None
        bits = self.update_bits()
        fed = self.base_fed()
        cohort = self.cohort_spec()
        K = None if cohort is None else cohort.K
        ref = self.scenario_ref()
        if ref is not None:
            return scenarios.plan_for_scenario(
                fed, ref, bits, cc=self.compute,
                wc=self.wireless, seed=self.seed, method=self.plan_method,
                cohort_size=K,
                spare=0 if cohort is None else cohort.spare)
        return defl.make_plan(fed, pop, bits, wireless=self.wireless,
                              method=self.plan_method, cohort_size=K)

    def _fed_with_plan(self, plan: Optional[defl.DEFLPlan]) -> FedConfig:
        base = self.base_fed()
        if plan is None:
            return base
        fed = defl.plan_to_fedconfig(plan, base)
        b = fed.batch_size if self.batch_cap is None else min(
            fed.batch_size, self.batch_cap)
        return dataclasses.replace(fed, batch_size=b, update_bytes=None)

    def resolve_plan(self) -> Optional[defl.DEFLPlan]:
        """The DEFL plan this spec runs under (None when plan=False)."""
        return self._solve_plan(self.device_population())

    def resolve_fed(self) -> FedConfig:
        """Plan-or-fed: `fed` with the solved (b*, theta*) applied when
        plan=True (batch capped at `batch_cap`, wire size left to the
        simulator's exact accounting), `fed` unchanged otherwise."""
        return self._fed_with_plan(self.resolve_plan())

    def plan_request(self) -> Optional[defl.PlanRequest]:
        """The arm's Alg. 1 solve in batchable value form: a
        `defl.PlanRequest` when `resolve_plan()` reduces to a plain
        `defl.make_plan` (plan=True and no deadline re-derivation), else
        None — fixed-(b, V) baselines solve nothing and deadline-fault
        scenarios re-derive over the truncated delay model, so both keep
        their bespoke scalar paths. `Study.plans()` collects these to
        solve all batchable arms in one vectorized KKT dispatch,
        bit-identical to per-arm `analytic_plan()`."""
        if not self.plan:
            return None
        participation = 1.0
        ref = self.scenario_ref()
        if ref is not None:
            sc = scenarios.get(ref)
            fm = sc.faults
            if fm is not None and fm.active and (
                    fm.deadline is not None
                    or fm.deadline_factor is not None):
                return None
            participation = sc.expected_participation
        cohort = self.cohort_spec()
        return defl.PlanRequest(
            fed=self.base_fed(), pop=self.device_population(),
            update_bits=self.update_bits(), wireless=self.wireless,
            method=self.plan_method, participation=participation,
            cohort_size=None if cohort is None else cohort.K)

    def analytic_plan(self) -> defl.DEFLPlan:
        """The arm's delay-model operating point, always available: the
        solved DEFL plan when plan=True, otherwise Eq. 12/8 evaluated at
        the spec's fixed (b, theta) (`defl.fixed_plan` at the EXACT
        theta, so a swept theta's H is not shifted by V's integer
        quantization — the FedAvg/Rand baseline rows of the paper's
        tables). The analytic figures (fig1a/fig1d, ablation_straggler)
        read their predicted columns from this via `Study.plans()`."""
        if self.plan:
            return self.resolve_plan()
        fed = self.base_fed()
        return defl.fixed_plan(
            fed, self.device_population(), self.update_bits(),
            b=fed.batch_size, V=fed.local_rounds,
            wireless=self.wireless, theta=fed.theta)

    # -- materialization ----------------------------------------------------
    def build(self) -> Simulator:
        """Materialize the Simulator: draw data/partition/population at
        `self.seed`, wire model/loss/eval, and hand the per-client data
        factory to the functional core (each `init(seed)` / fleet member
        gets its own independently-seeded batch streams over the shared
        dataset — keeping the device-resident one-upload data path).
        The population is drawn once and the DEFL plan solved once per
        build (both are seed-deterministic, but redundancy here would
        double every plan=True build's KKT solve).

        Sampled participation (PopulationSpec.cohort) swaps the dense
        per-client iterator list for a lazy ClientDataPool: at M <=
        n_train it wraps the SAME Dirichlet partition with the SAME
        per-client seeds (so a K=M sampled build is bit-identical to the
        dense one), above that — where a disjoint split is impossible —
        each client owns a deterministic virtual shard
        (partition.partition_virtual), O(1) host state per client."""
        make = DATASETS[self.dataset]
        pop = self.device_population()
        fed = self._fed_with_plan(self._solve_plan(pop))
        cohort = self.cohort_spec()
        if (cohort is None and self.backend != "loop"
                and fed.n_devices >= DENSE_M_DEPRECATION_THRESHOLD):
            warnings.warn(
                f"dense device state with M={fed.n_devices} clients is "
                "deprecated: the stacked params/opt carry one lane per "
                "client. Use population=PopulationSpec(M=..., "
                "cohort=CohortSpec(K=...)) for O(K) device state.",
                DeprecationWarning, stacklevel=2)
        cfg = self.model_config()
        data = make(self.n_train, seed=self.seed)
        params = cnn.init_cnn(cfg, jax.random.PRNGKey(self.seed))
        if cohort is not None and fed.n_devices > self.n_train:
            # Population scale: no M-long partition list exists anywhere.
            indices_fn, sizes = partition_virtual(
                self.n_train, fed.n_devices, seed=self.seed)
            data_sizes = sizes

            def data_factory(seed: int):
                return ClientDataPool(data, indices_fn, sizes,
                                      fed.batch_size, seed=seed)
        else:
            parts = partition_dirichlet(data, fed.n_devices,
                                        alpha=self.alpha, seed=self.seed)
            data_sizes = partition_sizes(parts)
            if cohort is not None:
                def data_factory(seed: int):
                    return ClientDataPool.from_parts(data, parts,
                                                     fed.batch_size,
                                                     seed=seed)
            else:
                def data_factory(seed: int):
                    return [BatchIterator(data, p, fed.batch_size,
                                          seed=seed + i)
                            for i, p in enumerate(parts)]

        eval_fn = eval_batch_fn = None
        if self.with_eval:
            test = make(self.n_test, seed=self.seed + 1, task=data)
            xb, yb = jnp.asarray(test.x), jnp.asarray(test.y)

            @jax.jit
            def eval_acc(p):
                logits = cnn.cnn_forward(cfg, p, xb)
                return jnp.mean(
                    (jnp.argmax(logits, -1) == yb).astype(jnp.float32))

            # Vmapped twin over a stacked member axis: fleet/study eval is
            # ONE dispatch for all members instead of a host loop. Exact
            # per-member agreement with eval_acc is guaranteed: the hit
            # indicators are exact 0/1 floats whose sum is integral, so no
            # reduction order can perturb the accuracy.
            @jax.jit
            def eval_acc_S(ps):
                logits = jax.vmap(lambda p: cnn.cnn_forward(cfg, p, xb))(ps)
                hits = (jnp.argmax(logits, -1) == yb[None]).astype(
                    jnp.float32)
                return jnp.mean(hits, axis=-1)

            eval_fn = lambda p: {"acc": float(eval_acc(p))}  # noqa: E731
            eval_batch_fn = lambda ps: {  # noqa: E731
                "acc": np.asarray(jax.device_get(eval_acc_S(ps)))}

        ref = self.scenario_ref()
        label = self.label or (
            f"{self.dataset}@{scenarios.get(ref).name}" if ref is not None
            else self.dataset)
        # The study-grouping capabilities: the (V, b)-envelope form of the
        # loss and a hashable compiled-graph signature — two sims with
        # equal envelope_key (and equal envelope dims) can share one
        # compiled envelope chunk (study._chunk_for). The effective
        # FaultModel is part of the signature: guard knobs and the fault
        # branch are compiled into the chunk (an active FaultModel with
        # no scenario also promotes the sim onto the scenario path).
        eff_faults = self.effective_faults()
        envelope_key = (cfg, fed.n_devices, fed.lr, fed.compress_updates,
                        self.impl,
                        ref is not None or eff_faults is not None,
                        eff_faults, cohort, self.shard_clients,
                        self.async_spec)
        return Simulator(
            functools.partial(cnn.cnn_loss, cfg), params, data_factory,
            data_sizes, fed, sgd(fed.lr), pop,
            wireless=self.wireless, eval_fn=eval_fn, label=label,
            backend=self.backend, impl=self.impl, scenario=ref,
            faults=self.faults, eval_batch_fn=eval_batch_fn,
            masked_loss_fn=functools.partial(cnn.cnn_loss_masked, cfg),
            envelope_key=envelope_key,
            cohort=None if cohort is None else cohort.K,
            cohort_sampler="uniform" if cohort is None else cohort.sampler,
            cohort_spare=0 if cohort is None else cohort.spare,
            shard_clients=self.shard_clients,
            async_spec=self.async_spec)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, ExperimentSpec] = {}


def register(name: str, spec: ExperimentSpec) -> ExperimentSpec:
    if name in _REGISTRY:
        raise ValueError(f"experiment {name!r} already registered")
    _REGISTRY[name] = spec
    return spec


def get(name: Union[str, ExperimentSpec]) -> ExperimentSpec:
    if isinstance(name, ExperimentSpec):
        return name
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown experiment {name!r}; registered: {names()}") from None


def names() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


register("mnist_paper", ExperimentSpec(
    fed=FedConfig(n_devices=10, epsilon=0.01, nu=2.0, c=CALIBRATED_C,
                  lr=0.05),
    model="mnist_cnn", dataset="mnist", plan=True,
    label="mnist_paper"))
register("cifar_paper", ExperimentSpec(
    fed=FedConfig(n_devices=10, epsilon=0.01, nu=2.0, c=CALIBRATED_C,
                  lr=0.05),
    model="cifar_cnn", dataset="cifar", plan=True,
    label="cifar_paper"))
register("mnist_smoke", ExperimentSpec(
    fed=FedConfig(n_devices=3, batch_size=8, theta=0.62, lr=0.05),
    model="mnist_cnn_small", dataset="mnist", n_train=240, n_test=80,
    label="mnist_smoke"))
register("mnist_sampled", ExperimentSpec(
    fed=FedConfig(batch_size=8, theta=0.62, lr=0.05),
    population=PopulationSpec(M=40, cohort=CohortSpec(K=8)),
    model="mnist_cnn_small", dataset="mnist", n_train=240, n_test=80,
    scenario="dropout",
    label="mnist_sampled"))
register("mnist_async", ExperimentSpec(
    fed=FedConfig(n_devices=10, batch_size=8, theta=0.62, lr=0.05),
    model="mnist_cnn_small", dataset="mnist", n_train=240, n_test=80,
    scenario="stragglers", backend="async",
    async_spec=AsyncSpec(buffer_size=4, staleness="poly"),
    label="mnist_async"))
register("mnist_diurnal", ExperimentSpec(
    fed=FedConfig(n_devices=12, epsilon=0.01, nu=2.0, c=CALIBRATED_C,
                  lr=0.05),
    model="mnist_cnn_small", dataset="mnist", n_train=240, n_test=80,
    scenario="diurnal_edge", plan=True,
    label="mnist_diurnal"))
register("mnist_storm", ExperimentSpec(
    fed=FedConfig(n_devices=10, epsilon=0.01, nu=2.0, c=CALIBRATED_C,
                  lr=0.05),
    model="mnist_cnn", dataset="mnist", scenario="hetero_storm", plan=True,
    label="mnist_storm"))
