"""The Study API: shape-envelope arm grouping, per-member early stop, and
vmapped fleet eval.

  * A mixed-(b, V) study executes its arms in grouped vmapped dispatches,
    bit-identical per arm (train-loss history, Eq. 8 clocks,
    participation, uplink bits, trained params) to sequential
    `Simulator.run()` calls — the padding/masking envelope
    (mesh_rounds.build_round_chunk(envelope=True) + cnn_loss_masked +
    the pad-stable conv backward) must be a bitwise no-op.
  * target_acc / max_sim_time stop members individually inside a fleet:
    a finished member rides along frozen (device-side done-mask) and its
    history/final state match a solo early-stopped run.
  * Chunk-boundary eval is one vmapped dispatch over the stacked member
    axis, exactly agreeing with the per-member host eval.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.base import FedConfig
from repro.federated import experiment
from repro.federated.experiment import ExperimentSpec
from repro.federated.study import Study


def _tiny_spec(b, V, scenario=None, compress=False, lr=0.05,
               with_eval=False):
    return ExperimentSpec(
        fed=FedConfig(n_devices=3, batch_size=b,
                      theta=float(np.exp(-V / 2.0)), nu=2.0, lr=lr,
                      compress_updates=compress),
        model="mnist_cnn_tiny", dataset="mnist", n_train=120, n_test=40,
        seed=0, scenario=scenario, with_eval=with_eval)


def _assert_member_matches(ref, got, params=True):
    assert len(ref.history) == len(got.history)
    for a, b in zip(ref.history, got.history):
        assert a.round == b.round
        assert np.float32(a.train_loss).tobytes() == \
            np.float32(b.train_loss).tobytes()
        assert a.sim_time == b.sim_time
        assert a.T_cm == b.T_cm and a.T_cp == b.T_cp
        assert a.n_participants == b.n_participants
        assert a.uplink_bits == b.uplink_bits
        assert a.test_acc == b.test_acc
    if params:
        for x, y in zip(jax.tree.leaves(ref.params),
                        jax.tree.leaves(got.params)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Envelope grouping: bit-identity with sequential runs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scenario,compress", [
    (None, False), ("dropout", True)])
def test_three_mixed_arms_bit_identical_to_sequential(scenario, compress):
    """The acceptance contract: a 3-arm study with distinct (b, V) —
    grouped into ONE vmapped envelope fleet — reproduces three sequential
    run() calls bit for bit (loss/clock/participation/uplink_bits and the
    trained params), with and without a scenario + int8 compression."""
    study = Study(
        arms=[("A", _tiny_spec(4, 2, scenario, compress)),
              ("B", _tiny_spec(8, 1, scenario, compress)),
              ("C", _tiny_spec(6, 3, scenario, compress))],
        seeds=(0, 1), max_rounds=5, eval_every=2, bit_check=True)
    res = study.run()
    assert res.groups == (("A", "B", "C"),)  # one envelope group
    for label, spec in study.arms:
        for i, seed in enumerate(study.seeds):
            sim = spec.build()
            _, ref = sim.run(sim.init(seed), max_rounds=5, eval_every=2)
            _assert_member_matches(ref, res[label][i])


def test_exact_grouping_splits_and_matches():
    study = Study(
        arms=[("A", _tiny_spec(4, 2)), ("B", _tiny_spec(8, 1))],
        seeds=(0,), max_rounds=3, grouping="exact")
    res = study.run()
    assert res.groups == (("A",), ("B",))
    for label, spec in study.arms:
        sim = spec.build()
        _, ref = sim.run(sim.init(0), max_rounds=3)
        _assert_member_matches(ref, res[label][0])


def test_different_scenarios_group_separately():
    study = Study(
        arms=[("u1", _tiny_spec(4, 2, "uniform")),
              ("u2", _tiny_spec(8, 1, "uniform")),
              ("d1", _tiny_spec(4, 2, "dropout"))],
        seeds=(0,), max_rounds=2)
    res = study.run()
    assert res.groups == (("u1", "u2"), ("d1",))


# ---------------------------------------------------------------------------
# Per-member early stop (done-mask)
# ---------------------------------------------------------------------------


def _smoke_spec(lr=0.2):
    return experiment.get("mnist_smoke").replace(
        n_train=240, n_test=80,
        fed=FedConfig(n_devices=3, batch_size=8, theta=0.62, lr=lr))


def test_fleet_member_freezes_at_target_acc_matching_solo():
    """A fleet member that reaches target_acc mid-study freezes (all-zero
    valid rows; params/opt/PRNG untouched) while the rest continue; its
    history AND final state match a solo early-stopped run."""
    spec = _smoke_spec()
    # Held-out accuracy (80 samples) of seeds 0, 1, 2 first reaches 0.15
    # at rounds 8, 4, 4. Seed 2 stops at 0.2 and seed 0 stays at or below
    # 0.1 until round 8: both 4 samples clear of the target.
    fleet = spec.build().run_fleet(seeds=[0, 1, 2], max_rounds=8,
                                   eval_every=2, target_acc=0.15)
    rounds = [r.rounds for r in fleet.results]
    assert min(rounds) < 8, f"no member early-stopped: {rounds}"
    assert max(rounds) == 8, f"every member stopped: {rounds}"
    for i, seed in enumerate([0, 1, 2]):
        sim = spec.build()
        st, ref = sim.run(sim.init(seed), max_rounds=8, eval_every=2,
                          target_acc=0.15)
        _assert_member_matches(ref, fleet.results[i])
        assert fleet.states[i].round == ref.rounds
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(st.key)),
            np.asarray(jax.device_get(fleet.states[i].key)))


def test_study_members_freeze_at_max_sim_time_matching_solo():
    """max_sim_time stops each study member at its own Eq. 8 clock: arms
    with larger V cross the budget earlier and ride along frozen."""
    arms = [("fast", _tiny_spec(4, 1)), ("slow", _tiny_spec(4, 3))]
    budget = 0.5
    res = Study(arms=arms, seeds=(0,), max_rounds=6, eval_every=2,
                max_sim_time=budget).run()
    assert res["slow"][0].rounds < res["fast"][0].rounds
    for label, spec in arms:
        sim = spec.build()
        _, ref = sim.run(sim.init(0), max_rounds=6, eval_every=2,
                         max_sim_time=budget)
        _assert_member_matches(ref, res[label][0])


def test_run_fleet_target_acc_requires_eval():
    sim = _tiny_spec(4, 1).build()  # with_eval=False
    with pytest.raises(ValueError, match="eval"):
        sim.run_fleet(seeds=[0], max_rounds=2, target_acc=0.5)
    with pytest.raises(ValueError, match="eval"):
        Study(arms=[("A", _tiny_spec(4, 1))], target_acc=0.5,
              max_rounds=2).run()


# ---------------------------------------------------------------------------
# Vmapped fleet eval
# ---------------------------------------------------------------------------


def test_eval_batch_fn_matches_host_eval():
    """The stacked-member eval is ONE vmapped dispatch whose per-member
    accuracies equal the host eval_fn exactly (hit sums are integral, so
    no reduction order can perturb them)."""
    spec = _tiny_spec(4, 1, with_eval=True)
    sim = spec.build()
    assert sim.eval_batch_fn is not None
    keys = [jax.random.PRNGKey(i) for i in range(3)]
    from repro.models import cnn
    cfg = spec.model_config()
    params = [cnn.init_cnn(cfg, k) for k in keys]
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *params)
    batch = sim.eval_batch_fn(stacked)["acc"]
    for i, p in enumerate(params):
        assert float(batch[i]) == sim.eval_fn(p)["acc"]


# ---------------------------------------------------------------------------
# Study construction, plans, result frame
# ---------------------------------------------------------------------------


def test_study_validation():
    spec = _tiny_spec(4, 1)
    with pytest.raises(ValueError, match="at least one arm"):
        Study(arms=[])
    with pytest.raises(ValueError, match="duplicate"):
        Study(arms=[("A", spec), ("A", spec)])
    with pytest.raises(ValueError, match="at least one seed"):
        Study(arms=[("A", spec)], seeds=())
    with pytest.raises(ValueError, match="grouping"):
        Study(arms=[("A", spec)], grouping="nope")
    with pytest.raises(TypeError, match="ExperimentSpec"):
        Study(arms=[("A", object())])
    with pytest.raises(ValueError, match="scan"):
        Study(arms=[("A", spec.replace(backend="batched"))])
    with pytest.raises(ValueError):
        Study(arms=[("A", spec)], max_rounds=0).run()


def test_study_plans_resolve_plan_or_fixed():
    planned = ExperimentSpec(
        fed=FedConfig(n_devices=10, epsilon=0.01, nu=2.0,
                      c=experiment.CALIBRATED_C, lr=0.05),
        model="mnist_cnn", dataset="mnist", plan=True)
    fixed = _tiny_spec(8, 2)
    plans = Study(arms=[("defl", planned), ("base", fixed)]).plans()
    assert plans["defl"].b == planned.resolve_plan().b
    assert plans["base"].b == 8 and plans["base"].V == 2
    assert plans["base"].overall_pred > 0


def test_study_result_frame_and_json():
    study = Study(arms=[("A", _tiny_spec(4, 2, with_eval=True)),
                        ("B", _tiny_spec(8, 1, with_eval=True))],
                  seeds=(0, 1), max_rounds=4, eval_every=2,
                  target_acc=0.999)  # unreachable: every seed misses
    res = study.run()
    assert res.labels == ("A", "B")
    header, rows = res.table()
    assert header.startswith("label,b,V,")
    assert [r[0] for r in rows] == ["A", "B"]
    tta = res.time_to_target("A")
    assert tta.shape == (2,)
    # Missed seeds are NaN (not silently their total time) and the hit
    # rate reports the miss; the _or_total variant keeps the old finite
    # fallback for the headline comparisons.
    assert np.isnan(tta).all()
    assert res.target_hit_rate("A") == 0.0
    s = res.summary("A")
    assert np.isnan(s["time_to_target_mean"]) and s["target_hit_rate"] == 0.0
    np.testing.assert_allclose(
        res.time_to_target_or_total("A"),
        [r.total_time for r in res["A"]])  # never hit -> total time
    assert np.isfinite(res.reduction("A", "B"))
    js = res.to_json()
    assert set(js["arms"]) == {"A", "B"}
    arm = js["arms"]["A"]
    assert arm["b"] == 4 and len(arm["per_seed"]) == 2
    h = arm["per_seed"][0]["history"]
    assert len(h["round"]) == res["A"][0].rounds
    assert js["groups"] and js["seeds"] == [0, 1]


def test_group_graph_cache_shared_across_studies():
    """Two studies over the same arm shapes share one compiled envelope
    graph (the _GROUP_FNS cache keyed on envelope_key + dims)."""
    from repro.federated import study as study_mod
    arms = [("A", _tiny_spec(4, 2)), ("B", _tiny_spec(8, 1))]
    Study(arms=arms, seeds=(0,), max_rounds=2).run()
    n = len(study_mod._GROUP_FNS)
    Study(arms=arms, seeds=(1,), max_rounds=2).run()
    assert len(study_mod._GROUP_FNS) == n  # cache hit, no new graph


def test_solo_fallback_for_sims_without_masked_loss():
    """A hand-built Simulator without the envelope capabilities (passed
    through run(sims=...)) falls back to sequential per-seed run() calls
    — its own group, not an envelope — and matches them exactly."""
    spec_a, spec_b = _tiny_spec(4, 2), _tiny_spec(8, 1)
    sims = {"A": spec_a.build(), "B": spec_b.build()}
    sims["B"].masked_loss_fn = None  # strip the envelope capability
    res = Study(arms=[("A", spec_a), ("B", spec_b)], seeds=(0, 1),
                max_rounds=3).run(sims=sims)
    assert res.groups == (("A",), ("B",))
    for label, spec in (("A", spec_a), ("B", spec_b)):
        for i, seed in enumerate((0, 1)):
            sim = spec.build()
            _, ref = sim.run(sim.init(seed), max_rounds=3)
            _assert_member_matches(ref, res[label][i])


def test_envelope_key_on_spec_sims():
    sim = _tiny_spec(4, 2).build()
    assert sim.masked_loss_fn is not None
    assert sim.envelope_key is not None
    # lr is part of the graph signature (baked into the opt closure).
    other = dataclasses.replace(
        _tiny_spec(4, 2), fed=dataclasses.replace(
            _tiny_spec(4, 2).fed, lr=0.31)).build()
    assert other.envelope_key != sim.envelope_key
