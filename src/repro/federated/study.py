"""Declarative multi-arm studies: whole method comparisons as grouped
vmapped dispatches.

The paper's headline results are *comparisons* — DEFL vs FedAvg vs Rand
(Fig. 2), sweeps over epsilon/b/theta (Fig. 1) — and each comparison arm
is one `ExperimentSpec`. A `Study` is the frozen value form of the whole
comparison:

    study = Study(
        arms=[("DEFL", defl_spec), ("FedAvg", fedavg_spec),
              ("Rand", rand_spec)],
        seeds=range(8), max_rounds=100, eval_every=1, target_acc=0.90)
    result = study.run()
    header, rows = result.table()
    json.dump(result.to_json(), f)

`run()` does NOT loop over arms. Arms are grouped by *shape signature* —
model shapes, client count M, dataset/partition/population draw, scenario,
lr, compression — everything that shapes the compiled graph or its shared
inputs EXCEPT the per-arm (b, V) plan. Each group executes as ONE vmapped
fleet over the (arm x seed) member axis:

  * Mixed (b, V) plans share one graph through the **(V, b) envelope**
    (mesh_rounds.build_round_chunk(envelope=True)): every member is
    padded to the group's (V_env, B_env) = (max V, max b) under traced
    validity masks. Padded local steps are in-graph no-ops (`where`
    state keeps), padded samples carry exact-zero loss/gradient
    contributions (models.cnn.cnn_loss_masked + the pad-stable conv
    backward, `_ps_matmul` on the CPU and `_ps_conv` on the TPU), and the
    native simulator runs the SAME envelope-form graph at the trivial
    all-ones masks — so each member's history and
    trained params are bit-identical to its own sequential
    `Simulator.run()` (tests/test_study.py).
  * `target_acc` / `max_sim_time` work per member through the device-side
    done-mask: a finished member's subsequent chunks feed an all-False
    `valid` mask and it rides along frozen, matching a solo early-stopped
    run bit for bit.
  * Eval at chunk boundaries is ONE vmapped dispatch over the stacked
    member axis (`Simulator.eval_batch_fn`), not a host loop.

`plans()` resolves each arm's analytic operating point (DEFL plan or the
fixed-(b, V) Eq. 12/8 evaluation) for the prediction-only figures
(fig1a/fig1d, ablation_straggler).

Compiled envelope graphs are cached per (envelope_key, V_env, B_env):
e.g. Fig. 2's five scenario studies share one compiled group graph when
their arms resolve to the same envelope.
"""
from __future__ import annotations

import dataclasses
import os
import pickle
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import defl
from repro.federated import mesh_rounds
from repro.federated.experiment import ExperimentSpec
from repro.federated.simulation import (
    SimResult,
    SimState,
    Simulator,
    _atomic_pickle,
    _unstack_members,
    _validate_run_args,
)

# Compiled envelope graphs shared across groups (and whole studies) with
# the same (graph signature, V_env, B_env) — e.g. every fig2 scenario
# study reuses one graph per dataset.
_GROUP_FNS: Dict[tuple, tuple] = {}


def _group_signature(spec: ExperimentSpec, fed) -> tuple:
    """Everything that shapes a group's compiled graph or its shared
    inputs — model, data/partition/population draw, scenario, lr,
    compression, impl — EXCEPT the per-arm (b, V) plan, which the
    envelope absorbs, and plan constants (epsilon/nu/c) that only exist
    to derive it. The effective FaultModel is part of the signature —
    guard knobs and the fault branch are compiled into the group's
    graph, and the fault inputs (attempt times, deadlines) are per-arm
    host values that must agree across a group's members."""
    return (spec.model, spec.dataset, spec.n_train, spec.n_test, spec.alpha,
            spec.seed, spec.scenario, spec.trace, spec.effective_faults(),
            spec.heterogeneity, spec.compute,
            spec.wireless, spec.backend, spec.impl, spec.with_eval,
            spec.population, spec.shard_clients,
            fed.n_devices, fed.lr, fed.compress_updates)


@dataclass
class _Member:
    """One (arm x seed) row of a group's fleet axis."""

    arm: int
    label: str
    sim: Simulator
    seed: int
    iters: Any = None
    stream: Any = None
    history: List = dataclasses.field(default_factory=list)
    sim_time: float = 0.0
    finished: bool = False
    last_xs: Any = None


def _member_env(sim: Simulator, V_env: int, B_env: int) -> dict:
    """The member's traced (V, b)-envelope masks (host numpy; stacked over
    the fleet axis before the single per-chunk upload)."""
    V, b = sim.fed.local_rounds, sim.fed.batch_size
    v_mask = np.zeros(V_env, np.float32)
    v_mask[:V] = 1.0
    s_mask = np.zeros(B_env, np.float32)
    s_mask[:b] = 1.0
    return {"v_mask": v_mask, "sample_mask": s_mask,
            "n_samples": np.float32(b), "v_count": np.float32(V),
            "update_bits": np.float32(sim._update_bits())}


def _group_fns(rep: Simulator, V_env: int, B_env: int):
    """(chunk, jitted fleet) for a group, cached on the representative's
    envelope_key + envelope dims (same-shaped groups across studies share
    one compilation)."""
    key = None
    if rep.envelope_key is not None:
        try:
            key = (rep.envelope_key, V_env, B_env)
            if key in _GROUP_FNS:
                return _GROUP_FNS[key]
        except TypeError:  # unhashable user key: build uncached
            key = None
    agg = ("int8_stochastic" if rep.fed.compress_updates
           else ("allreduce_shardmap" if rep._mesh is not None
                 else "allreduce"))
    n_lanes = rep._cohort if rep._sampled else rep.fed.n_devices
    chunk = mesh_rounds.build_round_chunk(
        rep.masked_loss_fn, rep.opt, V_env, n_lanes,
        aggregation=agg, impl=rep.impl, scenario=rep.scenario is not None,
        batch_from=rep._batch_from, envelope=True,
        guard=rep._guard, faults=rep._faults is not None,
        sampled=rep._sampled,
        quorum=None if rep._quorum is None else rep._quorum_policy,
        mesh=rep._mesh,
        param_specs_tree=rep._param_specs,
        client_axes=("clients",) if rep._mesh is not None else None)
    fns = (chunk, jax.jit(mesh_rounds.build_fleet_chunk(
               chunk, envelope=True, sampled=rep._sampled),
                          donate_argnums=(0, 1, 2)))
    if key is not None:
        _GROUP_FNS[key] = fns
    return fns


def _run_group(members: List[_Member], max_rounds: int, eval_every: int,
               target_acc: Optional[float], max_sim_time: Optional[float],
               envelope: Optional[Tuple[int, int]] = None,
               ) -> List[Tuple[SimState, SimResult]]:
    """Execute one shape group as a single vmapped fleet over its
    (arm x seed) members — the Study-side twin of `Simulator.run_fleet`
    with per-member (b, V) envelopes, per-member delay accounting and the
    same done-mask early-stop semantics. `envelope` forces the
    (V_env, B_env) dims (the bit probe pads a single member beyond its
    own shapes); by default they resolve to the group maxes.

    LOCKSTEP NOTE: the per-chunk member bookkeeping below (frozen-member
    zeroed xs, max_sim_time truncation + stream rewind, eval-boundary
    round gating, target_acc freeze) must mirror run_fleet's driver —
    both are tested for bit-parity against solo early-stopped runs
    (tests/test_study.py), so a semantics change in one that is not made
    in the other fails those tests; change them together."""
    rep = members[0].sim
    S = len(members)
    if envelope is not None:
        V_env, B_env = envelope
    else:
        V_env = max(m.sim.fed.local_rounds for m in members)
        B_env = max(m.sim.fed.batch_size for m in members)
    _, fleet_fn = _group_fns(rep, V_env, B_env)
    weights, _ = rep._chunk_args()
    scenario = rep.scenario is not None
    t_cp_S = None
    if scenario and not rep._sampled:
        # Sampled groups carry per-round (R, K) t_cp rows in xs instead
        # (lanes change owners every round); weights is None for the
        # same reason (_chunk_args).
        t_cp_S = jnp.asarray(
            np.stack([m.sim._t_cp_clients for m in members]), jnp.float32)
    env_S = jax.tree.map(
        lambda *ls: jnp.asarray(np.stack(ls)),
        *[_member_env(m.sim, V_env, B_env) for m in members])

    # Stacked fresh member states: every member starts from the SAME
    # replicated params/opt (the group signature pins model and draw
    # seed), so the (S, C, ...) state is one broadcast per leaf.
    base_p, base_o = rep._fleet_init_base()
    bcast = lambda x: jnp.broadcast_to(x[None], (S, *x.shape))  # noqa: E731
    params_S = jax.tree.map(bcast, base_p)
    opt_S = jax.tree.map(bcast, base_o)
    key_S = jnp.stack([jax.random.PRNGKey(m.seed) for m in members])
    shells = []
    for m in members:
        shell = SimState(params_C=None, opt_C=None, key=None, seed=m.seed)
        m.iters, m.stream = m.sim._materialize(shell)
        shells.append(shell)

    can_eval = (rep.eval_fn is not None or rep.eval_batch_fn is not None)
    R = min(eval_every, max_rounds)
    done = 0
    r0 = 0
    while done < max_rounds and not all(m.finished for m in members):
        n = min(R, max_rounds - done)
        per: List[Any] = []
        pre: List[Any] = []
        for m in members:
            if m.finished:
                # Device-side done-mask: all-zero xs (valid=False rows)
                # freeze the member in-graph; its host streams are not
                # consumed.
                per.append((jax.tree.map(np.zeros_like, m.last_xs), None))
                pre.append(None)
                continue
            if max_sim_time:
                pre.append((m.sim._snapshot_iters(m.iters),
                            m.stream.state() if m.stream is not None
                            else None))
            else:
                pre.append(None)
            per.append(m.sim._chunk_inputs(
                m.iters, m.stream, R, n, envelope=(V_env, B_env)))
            m.last_xs = per[-1][0]
        xs = jax.tree.map(lambda *ls: np.stack(ls), *[p[0] for p in per])
        params_S, opt_S, key_S, ys = fleet_fn(
            params_S, opt_S, key_S, weights, t_cp_S, rep._data_dev, xs,
            env_S)
        ys = jax.device_get(ys)  # leaves (S, R): ONE fetch per chunk
        for s, m in enumerate(members):
            if m.finished:
                continue
            recs = m.sim._chunk_records(
                {k: v[s] for k, v in ys.items()}, per[s][1], n, r0 + done,
                m.sim_time)
            if max_sim_time:
                for j, rec in enumerate(recs):
                    if rec.sim_time >= max_sim_time:
                        if j + 1 < n:
                            m.sim._rewind_chunk(m.iters, m.stream,
                                                pre[s][0], pre[s][1], j + 1)
                        recs = recs[:j + 1]
                        m.finished = True
                        break
            m.history.extend(recs)
            m.sim_time = m.history[-1].sim_time
        done += n
        if can_eval and (done % eval_every == 0 or done == max_rounds):
            evs = rep._eval_members(params_S, S)
            for s, m in enumerate(members):
                rec = m.history[-1]
                if rec.round != r0 + done:
                    continue  # truncated mid-chunk: solo would not eval
                rec.test_acc = float(evs[s].get("acc", np.nan))
                rec.test_loss = float(evs[s].get("loss", np.nan))
                if (target_acc and rec.test_acc is not None
                        and rec.test_acc >= target_acc):
                    m.finished = True

    unstacked = _unstack_members(
        (params_S, opt_S, key_S,
         jax.tree.map(lambda x: x[:, 0], params_S)), S)
    out = []
    for s, m in enumerate(members):
        p_s, o_s, k_s, global_s = unstacked[s]
        st = m.sim._rebuild_state(
            shells[s], p_s, o_s, k_s, len(m.history), m.sim_time,
            m.iters, m.stream)
        out.append((st, SimResult(
            history=m.history, params=global_s,
            label=f"{m.label}[seed={m.seed}]", fed=m.sim.fed)))
    return out


def _fmt(mean: float, std: float, nd: int, multi: bool) -> str:
    if not np.isfinite(mean):
        return ""
    if multi:
        return f"{mean:.{nd}f}+-{std:.{nd}f}"
    return str(round(mean, nd))


# -- study checkpointing ------------------------------------------------------
# One file per completed (arm, seed) member, written crash-safely
# (_atomic_pickle): a SIGKILL at any instant leaves only whole member
# files, and `Study.run(checkpoint_dir=..., resume=True)` skips them and
# runs the rest — the assembled StudyResult is bit-identical to an
# uninterrupted run because every member is independent (the fleet axis
# never mixes members; tests/test_chaos_resume.py proves it under a real
# mid-study kill).

_MEMBER_CKPT_VERSION = 1


def _member_ckpt_path(directory: str, arm: int, seed: int) -> str:
    return os.path.join(directory, f"arm{arm:03d}_seed{seed}.pkl")


def _save_member(path: str, label: str, seed: int,
                 state: SimState, result: SimResult) -> None:
    res = dataclasses.replace(result, params=jax.device_get(result.params))
    payload = {"__repro_study_member__": _MEMBER_CKPT_VERSION,
               "label": label, "seed": int(seed),
               "state": jax.device_get(state), "result": res}
    _atomic_pickle(path, payload)


def _load_member(path: str, label: str, seed: int,
                 ) -> Tuple[SimState, SimResult]:
    try:
        with open(path, "rb") as f:
            payload = pickle.load(f)
    except (pickle.UnpicklingError, EOFError, AttributeError) as e:
        raise ValueError(
            f"{path!r} is not a readable study checkpoint "
            f"(corrupt or truncated pickle): {e}") from e
    if not (isinstance(payload, dict)
            and "__repro_study_member__" in payload):
        raise ValueError(
            f"{path!r} does not hold a study member checkpoint")
    version = payload["__repro_study_member__"]
    if version != _MEMBER_CKPT_VERSION:
        raise ValueError(
            f"{path!r} holds member checkpoint schema v{version}, this "
            f"build reads v{_MEMBER_CKPT_VERSION}")
    if payload.get("label") != label or int(payload.get("seed", -1)) != seed:
        raise ValueError(
            f"checkpoint {path!r} holds arm {payload.get('label')!r} "
            f"seed {payload.get('seed')!r}, expected {label!r} seed {seed} "
            "— the study's arms/seeds changed since the checkpoint was "
            "written; point checkpoint_dir at a fresh directory")
    return payload["state"], payload["result"]


@dataclass
class StudyResult:
    """Per-arm frame of a study run: histories, final states,
    time-to-accuracy, confidence bands, paper-style table + JSON emit."""

    labels: Tuple[str, ...]
    seeds: Tuple[int, ...]
    results: Dict[str, List[SimResult]]  # label -> per-seed SimResults
    states: Dict[str, List[SimState]]
    groups: Tuple[Tuple[str, ...], ...]  # grouping report (labels/group)
    target_acc: Optional[float] = None
    max_sim_time: Optional[float] = None
    # label -> cohort size K for sampled-participation arms (None = dense).
    cohorts: Dict[str, Optional[int]] = dataclasses.field(
        default_factory=dict)
    # label -> "mode/K=buffer/staleness" for backend='async' arms (None =
    # synchronous): the aggregation regime column of table()/to_json().
    async_modes: Dict[str, Optional[str]] = dataclasses.field(
        default_factory=dict)

    def __getitem__(self, label: str) -> List[SimResult]:
        return self.results[label]

    def time_to_target(self, label: str) -> np.ndarray:
        """(S,) per-seed time to `target_acc` — NaN for a seed that never
        hit the target (previously its total time leaked in, silently
        deflating 'time-to-target' means for arms that never got there).
        With no target_acc every seed 'hits' at its total simulated time.
        `time_to_target_or_total` keeps the old semantics for headline
        comparisons that need a finite per-seed number."""
        if not self.target_acc:
            return np.asarray([r.total_time for r in self.results[label]])
        return np.asarray([
            t if (t := r.time_to_accuracy(self.target_acc)) is not None
            else np.nan
            for r in self.results[label]], np.float64)

    def time_to_target_or_total(self, label: str) -> np.ndarray:
        """(S,) per-seed time to target, falling back to the member's
        total simulated time for seeds that missed — the conservative
        finite bound the paper-style reduction/table columns compare on
        (a missed seed costs its whole run)."""
        tta = self.time_to_target(label)
        totals = np.asarray([r.total_time for r in self.results[label]])
        return np.where(np.isfinite(tta), tta, totals)

    def target_hit_rate(self, label: str) -> float:
        """Fraction of seeds that reached `target_acc` (1.0 when no
        target was set: every run 'completes')."""
        return float(np.isfinite(self.time_to_target(label)).mean())

    def final_accs(self, label: str) -> np.ndarray:
        return np.asarray([
            next((h.test_acc for h in reversed(r.history)
                  if h.test_acc is not None), np.nan)
            for r in self.results[label]])

    def summary(self, label: str) -> Dict[str, float]:
        times = np.asarray([r.total_time for r in self.results[label]])
        accs = self.final_accs(label)
        have_acc = bool(np.isfinite(accs).any())
        tta = self.time_to_target(label)
        have_tta = bool(np.isfinite(tta).any())
        rounds = np.asarray([r.rounds for r in self.results[label]])
        parts = [h.n_participants for r in self.results[label]
                 for h in r.history if h.n_participants is not None]
        return {
            "total_time_mean": float(times.mean()),
            "total_time_std": float(times.std()),
            "final_acc_mean": (float(np.nanmean(accs)) if have_acc
                               else float("nan")),
            "final_acc_std": (float(np.nanstd(accs)) if have_acc
                              else float("nan")),
            # Means over the seeds that HIT the target: one missed seed
            # used to poison these to NaN (or worse, count its total time
            # as a 'time to target'); the hit rate says how many made it.
            "time_to_target_mean": (float(np.nanmean(tta)) if have_tta
                                    else float("nan")),
            "time_to_target_std": (float(np.nanstd(tta)) if have_tta
                                   else float("nan")),
            "target_hit_rate": self.target_hit_rate(label),
            "rounds_mean": float(rounds.mean()),
            "mean_participants": (float(np.mean(parts)) if parts
                                  else float("nan")),
            # Resilience: quorum-rejected rounds (FaultModel.min_quorum)
            # and recovery restarts (RecoveryPolicy) summed over seeds —
            # both 0 for studies that run without those knobs.
            "rounds_rejected": int(sum(
                r.rounds_rejected for r in self.results[label])),
            "restarts": int(sum(
                len(r.restarts) for r in self.results[label])),
        }

    def reduction(self, label: str, baseline: str) -> float:
        """Paper-style '% overall-time reduction' of `label` vs `baseline`
        on mean time-to-target — like-for-like on both the solo and the
        fleet path (both early stop in-run). Missed seeds count their
        total run time (time_to_target_or_total), so the comparison stays
        finite and conservative when an arm misses the target."""
        a = float(self.time_to_target_or_total(label).mean())
        b = float(self.time_to_target_or_total(baseline).mean())
        return 100.0 * (1.0 - a / b)

    def table(self) -> Tuple[str, List[tuple]]:
        """Paper-style per-arm rows:
        label,b,V,K,agg,rounds,mean_participants,overall_time_s,acc,
        time_to_target,rounds_rejected,restarts — K is the sampled
        cohort size (blank for dense arms); agg is the aggregation
        regime ('sync', or 'mode/K=buffer/staleness' for backend='async'
        arms); time/acc as mean+-std bands
        when the study ran multiple seeds; rounds_rejected/restarts are
        seed totals of quorum-rejected rounds and recovery restarts
        (0 when those knobs are off)."""
        multi = len(self.seeds) > 1
        rows = []
        for label in self.labels:
            s = self.summary(label)
            fed = self.results[label][0].fed
            K = self.cohorts.get(label)
            mode = self.async_modes.get(label)
            tta = self.time_to_target_or_total(label)
            hit = [r.time_to_accuracy(self.target_acc) is not None
                   for r in self.results[label]] if self.target_acc else []
            rows.append((
                label, fed.batch_size, fed.local_rounds,
                K if K is not None else "",
                mode if mode is not None else "sync",
                round(s["rounds_mean"], 1),
                (round(s["mean_participants"], 1)
                 if np.isfinite(s["mean_participants"]) else ""),
                _fmt(s["total_time_mean"], s["total_time_std"], 2, multi),
                _fmt(s["final_acc_mean"], s["final_acc_std"], 4, multi),
                (_fmt(float(tta.mean()), float(tta.std()), 2, multi)
                 if (not self.target_acc or any(hit)) else ""),
                s["rounds_rejected"],
                s["restarts"],
            ))
        return ("label,b,V,K,agg,rounds,mean_participants,overall_time_s,"
                "acc,time_to_target_s,rounds_rejected,restarts", rows)

    def to_json(self) -> dict:
        """Machine-readable emit (benchmarks/run.py --json, the CI study
        artifact): study config, grouping report, per-arm summaries and
        full per-seed histories."""
        arms = {}
        for label in self.labels:
            per_seed = []
            for seed, r in zip(self.seeds, self.results[label]):
                per_seed.append({
                    "seed": int(seed),
                    "rounds": r.rounds,
                    "total_time": r.total_time,
                    "time_to_target": (r.time_to_accuracy(self.target_acc)
                                       if self.target_acc else None),
                    "rounds_rejected": r.rounds_rejected,
                    "restarts": r.restarts,
                    "history": {
                        "round": [h.round for h in r.history],
                        "sim_time": [h.sim_time for h in r.history],
                        "train_loss": [float(h.train_loss)
                                       for h in r.history],
                        "test_acc": [h.test_acc for h in r.history],
                        "n_participants": [h.n_participants
                                           for h in r.history],
                        "uplink_bits": [h.uplink_bits for h in r.history],
                        "rejected": [h.rejected for h in r.history],
                    },
                })
            fed = self.results[label][0].fed
            arms[label] = {
                "b": fed.batch_size, "V": fed.local_rounds, "lr": fed.lr,
                "K": self.cohorts.get(label),
                "async": self.async_modes.get(label),
                "compress_updates": fed.compress_updates,
                "summary": self.summary(label),
                "per_seed": per_seed,
            }
        return {"seeds": [int(s) for s in self.seeds],
                "target_acc": self.target_acc,
                "max_sim_time": self.max_sim_time,
                "groups": [list(g) for g in self.groups],
                "arms": arms}


@dataclass(frozen=True)
class Study:
    """A frozen multi-arm comparison: `(label, ExperimentSpec)` arms, run
    seeds, and the shared run/stop policy. `run()` executes the whole
    study as grouped vmapped fleets (see the module docstring);
    `plans()` resolves the arms' analytic operating points without
    training (the prediction-only figures).

    grouping='envelope' (default) fuses same-signature arms across their
    (b, V) plans; 'exact' additionally splits on (b, V) — no padding, at
    the cost of one dispatch stream per distinct shape. bit_check=True
    runs a one-round bit-probe per enveloped arm (native vs padded) and
    raises on any mismatch before spending the full budget — the padding
    is engineered to be exact and tested on the shipped configurations,
    but XLA owns fp32 fusion, so out-of-registry configs can opt into
    the self-check."""

    arms: Tuple[Tuple[str, ExperimentSpec], ...]
    seeds: Tuple[int, ...] = (0,)
    max_rounds: int = 200
    eval_every: int = 1
    target_acc: Optional[float] = None
    max_sim_time: Optional[float] = None
    grouping: str = "envelope"
    bit_check: bool = False

    def __post_init__(self):
        object.__setattr__(self, "arms",
                           tuple((str(k), v) for k, v in self.arms))
        object.__setattr__(self, "seeds",
                           tuple(int(s) for s in self.seeds))
        labels = [k for k, _ in self.arms]
        if not labels:
            raise ValueError("Study needs at least one arm")
        if len(set(labels)) != len(labels):
            raise ValueError(f"duplicate arm labels: {labels}")
        if not self.seeds:
            raise ValueError("Study needs at least one seed")
        if self.grouping not in ("envelope", "exact"):
            raise ValueError(f"unknown grouping {self.grouping!r}")
        for label, spec in self.arms:
            if not isinstance(spec, ExperimentSpec):
                raise TypeError(f"arm {label!r}: expected ExperimentSpec, "
                                f"got {type(spec).__name__}")
            if spec.backend not in ("scan", "async"):
                raise ValueError(
                    f"arm {label!r}: studies run on backend='scan' or "
                    f"'async' (got {spec.backend!r})")

    def replace(self, **kw) -> "Study":
        return dataclasses.replace(self, **kw)

    # -- analytic ------------------------------------------------------------
    def plans(self) -> Dict[str, defl.DEFLPlan]:
        """Per-arm analytic operating points (no training): the DEFL plan
        for plan=True arms, the fixed-(b, V) Eq. 12/8 evaluation
        otherwise. Arms whose solve reduces to a plain Alg. 1 problem
        (spec.plan_request() is not None) are solved together through ONE
        vectorized KKT dispatch (defl.make_plan_batch) — bit-identical to
        per-arm analytic_plan(); fixed-(b, V) baselines and deadline-
        fault arms keep their scalar paths."""
        reqs = [(label, spec.plan_request()) for label, spec in self.arms]
        batch = [(label, r) for label, r in reqs if r is not None]
        out: Dict[str, defl.DEFLPlan] = {}
        if batch:
            for (label, _), plan in zip(
                    batch, defl.make_plan_batch([r for _, r in batch])):
                out[label] = plan
        for label, spec in self.arms:
            if label not in out:
                out[label] = spec.analytic_plan()
        return out

    # -- execution -----------------------------------------------------------
    def build_sims(self) -> Dict[str, Simulator]:
        """Materialize every arm's Simulator once. `run()` builds its own
        when not given these; pass them in to amortize the per-arm build
        cost (dataset generation + upload, partition/population draw, the
        DEFL plan solve) across repeated runs of one study — what the
        bench_study timing loop does. Reuse is safe: Simulators are
        state-in/state-out and every run() materializes fresh per-seed
        host streams."""
        return {label: spec.build() for label, spec in self.arms}

    def run(self, sims: Optional[Dict[str, Simulator]] = None,
            checkpoint_dir: Optional[str] = None,
            resume: bool = True) -> StudyResult:
        """Execute the study. With `checkpoint_dir` set, every completed
        (arm, seed) member is autosaved to
        `{checkpoint_dir}/arm{a:03d}_seed{s}.pkl` via an atomic
        temp-file + fsync + rename write, and (with `resume=True`, the
        default) members whose file already exists are loaded instead of
        re-run — a killed study picks up where it left off and assembles
        a StudyResult bit-identical to an uninterrupted run. A checkpoint
        whose stored (label, seed) disagrees with the study raises
        ValueError rather than silently mixing studies."""
        _validate_run_args(self.max_rounds, self.eval_every)
        arm_of = {label: a for a, (label, _) in enumerate(self.arms)}
        done: Dict[Tuple[str, int], Tuple[SimState, SimResult]] = {}
        if checkpoint_dir is not None:
            checkpoint_dir = str(checkpoint_dir)
            os.makedirs(checkpoint_dir, exist_ok=True)
            if resume:
                for label, _ in self.arms:
                    for seed in self.seeds:
                        path = _member_ckpt_path(
                            checkpoint_dir, arm_of[label], seed)
                        if os.path.exists(path):
                            done[(label, seed)] = _load_member(
                                path, label, seed)

        def finish(label: str, seed: int, st, res) -> None:
            done[(label, seed)] = (st, res)
            if checkpoint_dir is not None:
                _save_member(
                    _member_ckpt_path(checkpoint_dir, arm_of[label], seed),
                    label, seed, st, res)

        built = sims if sims is not None else self.build_sims()
        sims = [(label, spec, built[label]) for label, spec in self.arms]
        if self.target_acc:
            missing = [label for label, _, sim in sims
                       if sim.eval_fn is None and sim.eval_batch_fn is None]
            if missing:
                raise ValueError(
                    f"target_acc needs with_eval=True on every arm; "
                    f"missing eval: {missing}")
        groups: Dict[Any, List[Tuple[str, ExperimentSpec, Simulator]]] = {}
        order: List[Any] = []
        for i, (label, spec, sim) in enumerate(sims):
            if sim.masked_loss_fn is None or sim.backend == "async":
                # No envelope form (hand-built Simulator) or async arm
                # (its own event clock cannot be vmapped against sync
                # round loops): runs solo, sequentially per seed.
                sig: Any = ("__solo__", i)
            else:
                sig = _group_signature(spec, sim.fed)
                if self.grouping == "exact":
                    sig = sig + (sim.fed.batch_size, sim.fed.local_rounds)
            if sig not in groups:
                groups[sig] = []
                order.append(sig)
            groups[sig].append((label, spec, sim))
        if self.bit_check:
            for sig in order:
                self._bit_probe(groups[sig])
        for sig in order:
            if len(sig) == 2 and sig[0] == "__solo__":
                # No envelope form (a hand-built Simulator passed through
                # run(sims=...)): the arm runs sequentially per seed —
                # correct, just not grouped.
                (label, _, sim), = groups[sig]
                for seed in self.seeds:
                    if (label, seed) in done:
                        continue
                    st, res = sim.run(
                        sim.init(seed), max_rounds=self.max_rounds,
                        eval_every=self.eval_every,
                        target_acc=self.target_acc,
                        max_sim_time=self.max_sim_time)
                    finish(label, seed, st, res)
                continue
            members = [
                _Member(arm=a, label=label, sim=sim, seed=seed)
                for a, (label, spec, sim) in enumerate(groups[sig])
                for seed in self.seeds
                if (label, seed) not in done
            ]
            if not members:
                continue  # every member restored from checkpoint
            for m, (st, res) in zip(members, _run_group(
                    members, self.max_rounds, self.eval_every,
                    self.target_acc, self.max_sim_time)):
                finish(m.label, m.seed, st, res)
        results: Dict[str, List[SimResult]] = {
            label: [done[(label, seed)][1] for seed in self.seeds]
            for label, _ in self.arms}
        states: Dict[str, List[SimState]] = {
            label: [done[(label, seed)][0] for seed in self.seeds]
            for label, _ in self.arms}
        return StudyResult(
            labels=tuple(l for l, _ in self.arms), seeds=self.seeds,
            results=results, states=states,
            groups=tuple(tuple(label for label, _, _ in groups[sig])
                         for sig in order),
            target_acc=self.target_acc, max_sim_time=self.max_sim_time,
            cohorts={label: (c.K if (c := spec.cohort_spec()) is not None
                             else None)
                     for label, spec in self.arms},
            async_modes={
                label: (f"{a.mode}/K={a.buffer_size}/{a.staleness}"
                        if (a := spec.async_spec) is not None else None)
                for label, spec in self.arms})

    def _bit_probe(self, group) -> None:
        """One-round native-vs-enveloped bit comparison per arm of a
        group whose envelope actually pads (a trivial envelope IS the
        native graph). Raises on the first mismatch — before the study
        spends its full round budget on a grouping that would not
        reproduce sequential runs."""
        if len(group) < 2:
            return
        V_env = max(sim.fed.local_rounds for _, _, sim in group)
        B_env = max(sim.fed.batch_size for _, _, sim in group)
        seed = self.seeds[0]
        for label, spec, sim in group:
            if (sim.fed.local_rounds, sim.fed.batch_size) == (V_env, B_env):
                continue
            state, native = sim.run_chunk(sim.init(seed), rounds=1)
            p_native = jax.device_get(sim.params(state))
            probe = spec.build()  # fresh sim: run_chunk consumed the state
            m = _Member(arm=0, label=label, sim=probe, seed=seed)
            (st, res), = _run_group([m], 1, 1, None, None,
                                    envelope=(V_env, B_env))
            a, b = native[0].train_loss, res.history[0].train_loss
            loss_ok = np.float32(a).tobytes() == np.float32(b).tobytes()
            params_ok = all(
                np.asarray(x).tobytes() == np.asarray(y).tobytes()
                for x, y in zip(jax.tree.leaves(p_native),
                                jax.tree.leaves(jax.device_get(res.params))))
            if not (loss_ok and params_ok):
                what = "loss" if not loss_ok else "params"
                raise ValueError(
                    f"bit_check: arm {label!r} diverges under the "
                    f"(V={V_env}, b={B_env}) envelope (round-1 {what}; "
                    f"loss {a!r} vs {b!r}); use grouping='exact' for "
                    f"this study or split the arm out")
