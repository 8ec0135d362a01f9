"""Client-axis sharding (shard_map over a ("clients",) mesh) must agree
with the unsharded scan backend. XLA's virtual-device flag has to be set
before JAX initializes, so the comparison runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=2 (same pattern as
test_dryrun_subprocess.py) — this process keeps its real single device.

Aggregation order differs between the in-graph allreduce (one jnp.sum
over the stacked client axis) and the psum of per-shard partials, so
params/losses are compared to float tolerance; the host-side clock and
participation accounting is unaffected by sharding and must match
exactly.
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

assert jax.device_count() == 2, jax.devices()

from repro.configs.base import FedConfig
from repro.core import delay
from repro.federated import scenarios
from repro.federated.simulation import Simulator, load_state, save_state
from repro.optim import sgd


def quad_loss(params, batch):
    diff = params["w"] - batch["target"]
    return 0.5 * jnp.sum(diff * diff), {}


class TargetIterator:
    def __init__(self, target, batch_size):
        self.target = np.asarray(target, np.float32)
        self.batch_size = batch_size

    def next_batch(self):
        return {"target": np.tile(self.target, (self.batch_size, 1))}


def make(shard, K=None, M=6):
    d, b = 16, 2
    fed = FedConfig(n_devices=M, batch_size=b, lr=0.05, seed=0)
    scen = scenarios.get("dropout")
    pop = scen.population(M, seed=0)
    iters = [TargetIterator(np.linspace(0.0, m, d) * 0.1, b)
             for m in range(M)]
    return Simulator(
        quad_loss, {"w": jnp.zeros(d)}, iters, 10 * np.arange(1, M + 1),
        fed, sgd(fed.lr), pop, backend="scan", scenario=scen,
        cohort=K, shard_clients=shard)


def run(sim):
    _, res = sim.run(sim.init(), max_rounds=5, eval_every=2)
    # One compiled chunk for the whole run, sharded or not: the chunk's
    # jit places its carry, whatever placement init() gave it.
    assert sim.trace_count == 1, sim.trace_count
    return res


def resumed(sim, path):
    # A checkpoint comes back as numpy leaves; the resumed run still
    # compiles once and continues the uninterrupted one.
    state, first = sim.run(sim.init(), max_rounds=2, eval_every=2)
    save_state(path, state)
    _, rest = sim.run(load_state(path), max_rounds=3, eval_every=2)
    assert sim.trace_count == 1, sim.trace_count
    return dataclasses.replace(rest, history=first.history + rest.history)


for K in (None, 4):
    ref, shd = run(make(False, K)), run(make(True, K))
    with tempfile.TemporaryDirectory() as d:
        res = resumed(make(True, K), os.path.join(d, "state.pkl"))
    for a, b in zip(jax.tree.leaves(shd.params), jax.tree.leaves(res.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [x.train_loss for x in shd.history] == [
        x.train_loss for x in res.history]
    for a, b in zip(jax.tree.leaves(ref.params),
                    jax.tree.leaves(shd.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    for x, y in zip(ref.history, shd.history):
        np.testing.assert_allclose(x.train_loss, y.train_loss,
                                   rtol=1e-5, atol=1e-6)
        assert x.sim_time == y.sim_time
        assert x.n_participants == y.n_participants
        assert x.uplink_bits == y.uplink_bits
    print(f"SHARD_PARITY_OK K={K}")
"""


def test_shardmap_parity_two_virtual_devices():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHARD_PARITY_OK K=None" in out.stdout
    assert "SHARD_PARITY_OK K=4" in out.stdout
