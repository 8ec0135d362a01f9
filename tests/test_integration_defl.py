"""Integration: DEFL (Algorithm 1) end-to-end on the paper's CNN task with
delay accounting; DEFL vs FedAvg predicted-time ordering."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ComputeConfig, FedConfig, WirelessConfig
from repro.core import defl, delay
from repro.data import BatchIterator, make_mnist_like
from repro.federated.partition import partition_dirichlet, partition_sizes
from repro.federated.simulation import Simulator
from repro.models import cnn
from repro.optim import sgd
from repro.utils.tree import tree_bytes

# Calibrated compute model: ~10 ms/sample at b=1 (matches the paper's
# empirically reported theta* ~ 0.15 operating point; see benchmarks).
CAL_CC = ComputeConfig(bits_per_sample=6.8e5)


@pytest.fixture(scope="module")
def mnist_setup():
    data = make_mnist_like(600, seed=0)
    test = make_mnist_like(200, seed=1, task=data)
    cfg = cnn.mnist_cnn()
    params = cnn.init_cnn(cfg, jax.random.PRNGKey(0))
    return data, test, cfg, params


def _make_sim(data, test, cfg, params, fed, pop, label):
    parts = partition_dirichlet(data, fed.n_devices, alpha=1.0, seed=0)
    iters = [BatchIterator(data, p, fed.batch_size, seed=i)
             for i, p in enumerate(parts)]
    xb, yb = jnp.asarray(test.x), jnp.asarray(test.y)

    @jax.jit
    def eval_acc(p):
        logits = cnn.cnn_forward(cfg, p, xb)
        return jnp.mean((jnp.argmax(logits, -1) == yb).astype(jnp.float32))

    return Simulator(
        functools.partial(cnn.cnn_loss, cfg), params, iters,
        partition_sizes(parts), fed, sgd(fed.lr), pop,
        eval_fn=lambda p: {"acc": float(eval_acc(p))}, label=label)


def test_defl_trains_and_tracks_time(mnist_setup):
    data, test, cfg, params = mnist_setup
    fed = FedConfig(n_devices=4, batch_size=16, theta=0.15, nu=2.0, lr=0.05)
    pop = delay.draw_population(4, CAL_CC, WirelessConfig(), 0, 0.2)
    sim = _make_sim(data, test, cfg, params, fed, pop, "defl")
    _, res = sim.run(sim.init(), max_rounds=4, eval_every=2)
    assert res.rounds == 4
    # Simulated clock strictly increases by Eq. 8 per round.
    times = [r.sim_time for r in res.history]
    assert all(t2 > t1 for t1, t2 in zip(times, times[1:]))
    dt = np.diff([0.0] + times)
    T_cm, T_cp = sim.round_times()
    np.testing.assert_allclose(dt, T_cm + fed.local_rounds * T_cp, rtol=1e-6)
    # Training makes progress.
    assert res.history[-1].train_loss < res.history[0].train_loss


def test_defl_plan_reduces_predicted_time_vs_fedavg(mnist_setup):
    """The paper's headline claim, at the model level: DEFL's optimized
    (b*, theta*) yields lower predicted overall time (Eq. 13) than the
    FedAvg reference configuration (b=10, V=20)."""
    data, test, cfg, params = mnist_setup
    fed = FedConfig(n_devices=10, epsilon=0.01, nu=2.0, c=0.4)
    pop = delay.draw_population(10, CAL_CC, WirelessConfig(), 0, 0.0)
    bits = tree_bytes(params) * 8
    plan = defl.make_plan(fed, pop, bits)
    fedavg = defl.fixed_plan(fed, pop, bits, b=10, V=20)
    rand = defl.fixed_plan(fed, pop, bits, b=16, V=15)
    assert plan.overall_pred < fedavg.overall_pred
    assert plan.overall_pred < rand.overall_pred
    assert plan.V >= 1 and plan.b >= 1


def test_compression_shrinks_talk_time(mnist_setup):
    data, test, cfg, params = mnist_setup
    pop = delay.draw_population(4, CAL_CC, WirelessConfig(), 0, 0.0)
    bits = tree_bytes(params) * 8
    fed = FedConfig(n_devices=4)
    plain = defl.make_plan(fed, pop, bits)
    comp = defl.make_plan(
        FedConfig(n_devices=4, compress_updates=True), pop, bits)
    assert comp.T_cm < plain.T_cm / 3.5
    # With cheaper talk, the optimizer shifts toward less local work.
    assert comp.solution.alpha <= plain.solution.alpha + 1e-9
