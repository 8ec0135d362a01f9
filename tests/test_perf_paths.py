"""Perf-lever paths: blocked-causal attention and the explicit int8
shard_map sync (EXPERIMENTS.md §Perf)."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.models import attention as attn


@pytest.mark.parametrize("window", [None, 96])
def test_blocked_causal_matches_dense(key, window):
    B, S, H, hd = 2, 300, 4, 16
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    ref = attn._sdpa(q, k, v, attn._causal_mask(S, window))
    out = attn._blocked_causal_sdpa(q, k, v, window, block=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


def test_remat_flag_changes_nothing_numerically(key):
    from repro.configs.registry import get_config
    from repro.models import transformer as tfm

    cfg = get_config("qwen2-0.5b", smoke=True)
    params = tfm.init_params(cfg, key)
    batch = {"tokens": jax.random.randint(key, (2, 32), 0, cfg.vocab_size)}
    l1 = tfm.loss_fn(cfg, params, batch)[0]
    l2 = tfm.loss_fn(cfg.replace(remat=False), params, batch)[0]
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    g1 = jax.grad(lambda p: tfm.loss_fn(cfg, p, batch)[0])(params)
    g2 = jax.grad(lambda p: tfm.loss_fn(cfg.replace(remat=False), p,
                                        batch)[0])(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_int8_shardmap_sync_subprocess():
    """shard_map needs multiple devices -> run in a flagged subprocess."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.federated.mesh_rounds import build_round_step, replicate_clients
from repro.optim import sgd

mesh = jax.make_mesh((4, 2), ("data", "model"))
def loss(params, batch):
    diff = params["w"] - batch["target"]
    return 0.5 * jnp.sum(diff * diff), {}
C, V = 4, 3
stacked = replicate_clients({"w": jnp.ones(8, jnp.float32)}, C)
specs = {"w": P("data", None)}
batches = {"target": jnp.stack(
    [jnp.tile(jnp.full(8, float(t))[None], (V, 1)) for t in range(C)])}
weights = jnp.full((C,), 0.25)
ref = build_round_step(loss, sgd(0.05), V, "allreduce")
sm = build_round_step(loss, sgd(0.05), V, "int8_shardmap", mesh=mesh,
                      param_specs_tree=specs, client_axes=("data",))
with mesh:
    pr, _, _ = jax.jit(ref)(stacked, (), batches, weights)
    ps, _, _ = jax.jit(sm)(stacked, (), batches, weights)
    txt = jax.jit(sm).lower(stacked, (), batches, weights).compile().as_text()
err = float(jnp.max(jnp.abs(pr["w"] - ps["w"])))
assert err < 2.0 / 127 + 1e-6, err
assert any("all-gather" in l and "s8[" in l for l in txt.splitlines()), \\
    "int8 not on the wire"
print("OK")
"""
    out = subprocess.run(
        [sys.executable, "-c", code],
        env=dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
                 JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout
