"""A second model family joins the harness by new files only.

In a copy of the benchmark, a toy token family (a frozen embedding, tied
to the output, and a trained low-rank adapter) is added with its
configuration, traffic and limits files. `cells` loads it, `reference`
runs it with the lanes vmapped and in sequence to equal readings, the
frozen weights come back unchanged, unknown architecture keys are still
refused, and no file that was in the copy changed."""
from __future__ import annotations

import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import cells, reference
from chipbench import run as bench
from chipbench.tests.test_harness import _copy_bench

TOY_FAMILY = '''
"""A toy token model: tokens through a frozen embedding E, plus a
low-rank adapter (h @ A) @ B, scored against E again; rows drawn from
topics, one topic per row."""
import jax
import jax.numpy as jnp
import numpy as np

ARCH_KEYS = {"vocab", "d_model", "rank", "seq_len", "n_topics"}
SEQUENTIAL_LANES = False


def program_architecture(m):
    raise NotImplementedError("the program has no token model")


class Tokens:
    def __init__(self, x, y, topic, n_topics):
        self.x, self.y, self.topic, self.n_topics = x, y, topic, n_topics


def data_maker(config):
    arch = config["architecture"]

    def make(n, seed=0, task=None):
        key = jax.random.fold_in(jax.random.PRNGKey(config["data_seed"]),
                                 0 if task is None else 1)
        k_t, k_p, k_s = jax.random.split(key, 3)
        topic = jax.random.randint(k_t, (n,), 0, arch["n_topics"])
        prefs = jax.random.normal(k_p, (arch["n_topics"], arch["vocab"]))
        seq = jax.random.categorical(
            k_s, 3.0 * prefs[topic][:, None, :],
            shape=(n, arch["seq_len"] + 1)).astype(jnp.int32)
        return Tokens(seq[:, :-1], seq[:, 1:], np.asarray(topic),
                      arch["n_topics"])

    return make


def labels(data):
    return data.topic, data.n_topics


def init(arch, key):
    k_e, k_a = jax.random.split(key)
    d, r = arch["d_model"], arch["rank"]
    frozen = {"embed": jax.random.normal(k_e, (arch["vocab"], d)) / d ** 0.5}
    trained = {"A": jax.random.normal(k_a, (d, r)) / d ** 0.5,
               "B": jnp.zeros((r, d))}
    return trained, frozen


def _logits(trained, frozen, x, precision):
    h = frozen["embed"][x]
    h = h + jnp.dot(jnp.dot(h, trained["A"], precision=precision),
                    trained["B"], precision=precision)
    return jnp.dot(h, frozen["embed"].T, precision=precision)


def loss(trained, frozen, x, y, precision):
    logp = jax.nn.log_softmax(_logits(trained, frozen, x, precision), -1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))


def eval_hits(trained, frozen, x, y, precision):
    pred = jnp.argmax(_logits(trained, frozen, x, precision), -1)
    return jnp.sum(pred == y), y.size


def _macs(arch):
    d, r, v = arch["d_model"], arch["rank"], arch["vocab"]
    return arch["seq_len"] * (2 * d * r), arch["seq_len"] * d * v


def train_flops_per_sample(arch):
    adapter, head = _macs(arch)
    # forward, activations' gradients, and the adapter's weight gradients
    return 2 * (adapter + head) + 2 * (adapter + head) + 2 * adapter


def eval_flops_per_sample(arch):
    return 2 * sum(_macs(arch))
'''

TOY_ARCH = {"vocab": 64, "d_model": 32, "rank": 4, "seq_len": 16,
            "n_topics": 4}
TOY_CONFIG = {
    "name": "toy_lm", "source": "a toy of this test", "family": "toy_lm",
    "model": "toy_lm", "architecture": TOY_ARCH, "dataset": "tokens",
    "n_train": 256, "n_test": 64, "data_seed": 7,
    "fl": {"epsilon": 0.01, "nu": 2.0, "c": 4.0, "lr": 0.5, "plan": False,
           "batch_cap": 8, "alpha": 1.0},
    "dtype": "float32", "matmul_precision": "highest", "reduced": []}
TOY_TRAFFIC = {
    "name": "toy_tokens", "population": {"M": 4, "K": None},
    "scenario": None, "compress_updates": True, "impl": "xla",
    "backend": "scan", "eval_every": 2, "shard_clients": False, "chips": 1,
    "plan_expected": {"b": 8, "V": 2}, "check_steps": 2}
TOY_LIMITS = {"limits": {"first_loss_gap": 0.01}, "readings": {},
              "notes": "a toy of this test"}
NAME = "toy_lm.toy_tokens"


def _hashes(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def _add_files(root: Path, config=TOY_CONFIG, config_name="toy_lm"):
    bench_dir = root / "chipbench"
    new = {bench_dir / "families" / "toy_lm.py": TOY_FAMILY,
           bench_dir / "configs" / f"{config_name}.json": json.dumps(config),
           bench_dir / "traffic" / "toy_tokens.json": json.dumps(TOY_TRAFFIC),
           bench_dir / "limits" / f"{NAME}.json": json.dumps(TOY_LIMITS)}
    for path, text in new.items():
        assert not path.exists()
        path.write_text(text)
    return bench_dir / "configs" / f"{config_name}.json"


def _readings(cell, sequential: bool, monkeypatch):
    monkeypatch.setattr(cell.family, "SEQUENTIAL_LANES", sequential)
    data, test = bench._datasets(cell, 11, bench.data_maker(cell))
    ref = reference.Reference(cell, 11, data, test, 8, 2)
    got = reference.readings(ref, cell.check_steps, cell.eval_every)
    return got, ref


def test_a_token_family_runs_through_cells_and_reference_by_new_files(
        tmp_path, monkeypatch):
    root = _copy_bench(tmp_path)
    before = _hashes(root)
    config_file = _add_files(root)

    cell = cells.from_files(NAME, config_file, "toy_tokens", 1, repo=root)
    assert Path(cell.family.__file__).resolve() == (
        root / "chipbench" / "families" / "toy_lm.py").resolve()
    assert cell.lanes == 4

    vm, vm_ref = _readings(cell, False, monkeypatch)
    sq, sq_ref = _readings(cell, True, monkeypatch)
    assert len(vm["losses"]) == 4 and all(np.isfinite(vm["losses"]))
    np.testing.assert_allclose(sq["losses"], vm["losses"], rtol=1e-6)
    np.testing.assert_allclose(sq["acc"], vm["acc"], atol=0)
    for a, b in zip(jax.tree.leaves(sq["params"]),
                    jax.tree.leaves(vm["params"])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    # Only the adapter is trained, sent and averaged; B moves from zero.
    assert set(vm["params"][0]) == {"A", "B"}
    assert np.abs(vm["params"][-1]["B"]).max() > 0

    _, frozen = cell.family.init(TOY_ARCH, jax.random.PRNGKey(11))
    for ref in (vm_ref, sq_ref):
        np.testing.assert_array_equal(np.asarray(ref.frozen["embed"]),
                                      np.asarray(frozen["embed"]))

    after = _hashes(root)
    assert {p: after[p] for p in before} == before


def test_unknown_keys_of_a_new_family_are_refused(tmp_path):
    root = _copy_bench(tmp_path)
    config = dict(TOY_CONFIG, architecture=dict(TOY_ARCH, n_expers=8))
    config_file = _add_files(root, config, "toy_lm_typo")
    with pytest.raises(cells.CellError, match="unknown keys.*n_expers"):
        cells.from_files(NAME, config_file, "toy_tokens", 1, repo=root)
    missing = root / "chipbench" / "configs" / "no_family.json"
    missing.write_text(json.dumps(dict(TOY_CONFIG, family="nowhere")))
    with pytest.raises(cells.CellError, match="no model family"):
        cells.from_files(NAME, missing, "toy_tokens", 1, repo=root)


@pytest.mark.parametrize("name", ["../families/toy_lm", "families/toy_lm",
                                  "", "toy lm", 3])
def test_a_family_name_that_is_no_identifier_is_refused(tmp_path, name):
    root = _copy_bench(tmp_path)
    _add_files(root)
    config_file = root / "chipbench" / "configs" / "odd_family.json"
    config_file.write_text(json.dumps(dict(TOY_CONFIG, family=name)))
    with pytest.raises(cells.CellError, match="no plain identifier"):
        cells.from_files(NAME, config_file, "toy_tokens", 1, repo=root)
