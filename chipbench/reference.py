"""Plain reference of what a cell's timed path computes.

Federated averaging of the cell's model family (chipbench/families/),
written in straightforward `jax.numpy`: the family's loss, plain SGD, a
participation-weighted mean, float32 throughout, the convolutions and
matmuls at the precision the configuration states (`matmul_precision`:
"default" is the TPU's one bfloat16 pass with float32 accumulation,
"highest" full float32). Only the family's trained weights are
differentiated, sent and averaged; its frozen weights are held once,
shared by every lane, and never change. It imports nothing of the
program. Its host side draws, from the run's seed, the same
per-round inputs the simulator's host draws (the seeded partition of
the training rows by their labels, each client's epoch-shuffled batches,
the cohort and the dropout and link-failure masks), and its int8 uplink
is the stochastic-rounding quantizer (one float32 scale per 1,024-value
row) fed the same per-client key schedule as the simulator. So one
reference round sees what one program round sees, and their results
differ only by the arithmetic.

`Reference(..., dtype=jnp.bfloat16)` is the control: the same rounds
with weights, floating-point data and every operation in bfloat16.
`fault=` plants a fault in the reference, which the tests then put in
the program's place:

    "frozen"       a round that returns its state unchanged
    "half_batch"   each local step drops half its batch, the mean taken
                   over the rest
    "no_exchange"  the cross-chip exchange left out: the aggregate holds
                   only the first chip's share of the lanes
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

ROW = 1024  # values per float32 quantizer scale
HIGHEST = lax.Precision.HIGHEST
PRECISIONS = {"default": lax.Precision.DEFAULT, "highest": HIGHEST}
FAULTS = ("frozen", "half_batch", "no_exchange")


# -- int8 uplink ---------------------------------------------------------------

def quantize_roundtrip(delta: Dict, key) -> Dict:
    """One client's update through the int8 uplink and back: every leaf
    (in sorted-key order) zero-padded to whole 1,024-value rows, one
    float32 scale per row (absmax / 127), stochastic rounding with noise
    (u + 0.5) / 256 from the bytes of `key`'s uint32 draws, clip to
    [-127, 127], dequantize."""
    leaves, treedef = jax.tree_util.tree_flatten(delta)
    segs = []
    for leaf in leaves:
        flat = leaf.reshape(-1).astype(jnp.float32)
        segs.append(jnp.pad(flat, (0, (-flat.size) % ROW)))
    rows = jnp.concatenate(segs).reshape(-1, ROW)
    n = rows.size
    words = jax.random.bits(key, ((n + 3) // 4,), jnp.uint32)
    u8 = lax.bitcast_convert_type(words, jnp.uint8).reshape(-1)[:n]
    noise = ((u8.astype(jnp.float32) + 0.5) / 256.0).reshape(rows.shape)
    absmax = jnp.max(jnp.abs(rows), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.floor(rows / scale + noise), -127, 127)
    flat = (q * scale).reshape(-1)
    out, at = [], 0
    for leaf in leaves:
        out.append(flat[at:at + leaf.size].reshape(leaf.shape)
                   .astype(leaf.dtype))
        at += -(-leaf.size // ROW) * ROW
    return jax.tree_util.tree_unflatten(treedef, out)


def client_keys(key, n: int):
    """n per-client quantizer keys: (key, sub) = split(key), n times."""
    def split(k, _):
        k, sub = jax.random.split(k)
        return k, sub

    return lax.scan(split, key, None, length=n)


# -- one round -----------------------------------------------------------------

def _cast(x, dtype):
    """Floating-point data in `dtype`; token ids and labels as they are."""
    return x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x


@functools.lru_cache(maxsize=None)
def round_fn(family, lr: float, quantize: bool, precision, dtype_name: str,
             fault: Optional[str], shards: int, sequential: bool):
    """The jitted round: every lane runs its V local SGD steps of the
    trained weights from the global model, the lanes vmapped or, where
    `sequential`, one after another; then the participation-weighted
    mean (over the int8 uplink when `quantize`)."""
    dtype = jnp.dtype(dtype_name)

    def lane(p, frozen, xb, yb):
        if fault == "half_batch":
            xb, yb = xb[:, : xb.shape[1] // 2], yb[:, : yb.shape[1] // 2]

        def step(q, batch):
            x, y = batch
            loss, g = jax.value_and_grad(family.loss)(q, frozen, x, y,
                                                      precision)
            return jax.tree.map(lambda a, b: (a - lr * b).astype(dtype),
                                q, g), loss

        q, losses = lax.scan(step, p, (xb, yb))
        return q, jnp.mean(losses)

    def run(p, frozen, xb, yb, weights, mask, key):
        xb = _cast(xb, dtype)
        if sequential:
            new, losses = lax.map(lambda a: lane(p, frozen, *a), (xb, yb))
        else:
            new, losses = jax.vmap(lane, in_axes=(None, None, 0, 0))(
                p, frozen, xb, yb)
        wm = weights.astype(jnp.float32) * mask
        s = jnp.sum(wm)
        wn = wm / jnp.where(s > 0, s, 1.0)
        if fault == "no_exchange":
            # Each chip's share of the sum, never exchanged: the model is
            # what the first chip holds.
            wn = wn * (jnp.arange(wn.shape[0]) < wn.shape[0] // shards)
        m = mask
        # A lane whose update did not arrive counts with its pre-round
        # model at weight 0, so not even a non-finite value can leak in.
        keep = lambda a, b: jnp.where(  # noqa: E731
            m.reshape((-1,) + (1,) * b.ndim) > 0, a, b[None])
        new = jax.tree.map(keep, new, p)
        if quantize:
            key, subs = client_keys(key, wn.shape[0])
            deltas = jax.tree.map(lambda a, b: a - b[None], new, p)
            rec = jax.vmap(quantize_roundtrip)(deltas, subs)
            agg = jax.tree.map(
                lambda b, r: (b.astype(jnp.float32) + jnp.tensordot(
                    wn, r.astype(jnp.float32), axes=(0, 0),
                    precision=HIGHEST)).astype(dtype), p, rec)
        else:
            agg = jax.tree.map(
                lambda a: jnp.tensordot(wn, a.astype(jnp.float32),
                                        axes=(0, 0),
                                        precision=HIGHEST).astype(dtype),
                new)
        agg = jax.tree.map(lambda a, b: jnp.where(s > 0, a, b), agg, p)
        if fault == "frozen":
            agg = p
        n = jnp.sum(m)
        loss = jnp.where(n > 0, jnp.sum(losses.astype(jnp.float32) * m)
                         / jnp.where(n > 0, n, 1.0), jnp.nan)
        return agg, loss, key

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _eval_fn(family, precision):
    @jax.jit
    def hits(p, frozen, x, y):
        return family.eval_hits(p, frozen, x, y, precision)

    return hits


# -- host draws ------------------------------------------------------------------

def dirichlet_parts(y: np.ndarray, n_classes: int, m: int, alpha: float,
                    seed: int) -> List[np.ndarray]:
    """Label-Dirichlet split of the training rows (labels y, 0 to
    n_classes - 1) over m clients, re-drawn until no client is empty."""
    rng = np.random.default_rng(seed)
    for _ in range(100):
        shares = [[] for _ in range(m)]
        for cls in range(n_classes):
            idx = np.flatnonzero(y == cls)
            rng.shuffle(idx)
            p = rng.dirichlet([alpha] * m)
            cuts = (np.cumsum(p)[:-1] * len(idx)).astype(int)
            for dev, part in enumerate(np.split(idx, cuts)):
                shares[dev].append(part)
        parts = [np.sort(np.concatenate(s)) for s in shares]
        if all(len(p) > 0 for p in parts):
            return parts
    raise RuntimeError("no non-empty Dirichlet partition in 100 draws")


def virtual_shard(n: int, client: int, size: int, seed: int) -> np.ndarray:
    """Client `client`'s rows when the population outnumbers the rows:
    `size` sorted rows drawn for that client alone."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5AAD, client]))
    return np.sort(rng.choice(n, size=size, replace=size > n))


class ClientBatches:
    """One client's batches: its rows in a fresh seeded permutation per
    epoch, `b` at a time; a client with fewer than b rows samples b with
    replacement."""

    def __init__(self, rows: np.ndarray, b: int, seed: int):
        self.rows = np.asarray(rows)
        self.b = int(b)
        self.rng = np.random.default_rng(seed)
        self._new_epoch()

    def _new_epoch(self) -> None:
        self.order = self.rng.permutation(self.rows)
        self.at = 0

    def next(self) -> np.ndarray:
        if len(self.order) < self.b:
            return self.rng.choice(self.rows, size=self.b, replace=True)
        if self.at + self.b > len(self.order):
            self._new_epoch()
        idx = self.order[self.at:self.at + self.b]
        self.at += self.b
        return idx


class Population:
    """The host side of the run: which clients train each round, on which
    rows, with which weights, and whose updates arrive."""

    def __init__(self, cell, labels: np.ndarray, n_labels: int, seed: int,
                 b: int, V: int):
        cfg, tr = cell.config, cell.traffic
        pop = tr["population"]
        self.M = int(pop["M"])
        self.K = None if pop.get("K") is None else int(pop["K"])
        self.b, self.V, self.seed = int(b), int(V), int(seed)
        n = len(labels)
        if self.K is not None and self.M > n:
            size = min(64, n)
            self.sizes = np.full(self.M, size, np.float32)
            self._rows = lambda m: virtual_shard(n, m, size, seed)
        else:
            parts = dirichlet_parts(labels, n_labels, self.M,
                                    cfg["fl"]["alpha"], seed)
            self.sizes = np.array([len(p) for p in parts], np.float32)
            self._rows = lambda m: parts[m]
        self._clients: Dict[int, ClientBatches] = {}
        sc = tr.get("scenario")
        self.dropout = 0.0 if sc is None else float(sc["dropout"])
        self.link_failure = 0.0 if sc is None else float(sc["link_failure"])
        self.scenario = sc is not None
        self._rng = np.random.default_rng(np.random.SeedSequence([seed, 0xED6E]))
        self._cohort_rng = np.random.default_rng(
            np.random.SeedSequence([seed, 0xC047]))

    def _client(self, m: int) -> ClientBatches:
        c = self._clients.get(m)
        if c is None:
            c = self._clients[m] = ClientBatches(self._rows(m), self.b,
                                                 self.seed + m)
        return c

    def draw(self):
        """One round: (rows (L, V, b), weights (L,), mask (L,))."""
        if self.K is None or self.K == self.M:
            lanes = np.arange(self.M)
        else:
            keys = self._cohort_rng.random(self.M)
            lanes = np.sort(np.argpartition(keys, self.K)[:self.K])
        if self.scenario:
            present = np.ones(self.M, bool)
            if self.dropout > 0:
                present &= self._rng.random(self.M) >= self.dropout
            failed = np.zeros(self.M, bool)
            if self.link_failure > 0:
                failed |= self._rng.random(self.M) < self.link_failure
            mask = (present & ~failed)[lanes].astype(np.float32)
        else:
            mask = np.ones(len(lanes), np.float32)
        rows = np.stack([np.stack([self._client(int(m)).next()
                                   for _ in range(self.V)]) for m in lanes])
        return rows.astype(np.int32), self.sizes[lanes], mask


# -- the run ---------------------------------------------------------------------

class Reference:
    """A reference run of one cell at one seed, round by round; the lanes
    run in sequence where the family's SEQUENTIAL_LANES says."""

    def __init__(self, cell, seed: int, data, test, b: int, V: int,
                 dtype=jnp.float32, fault: Optional[str] = None,
                 shards: int = 1):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
        self.cell, self.seed = cell, int(seed)
        self.family = family = cell.family
        self.dtype = jnp.dtype(dtype)
        self.precision = (PRECISIONS[cell.config["matmul_precision"]]
                          if self.dtype == jnp.float32 else None)
        trained, frozen = family.init(cell.config["architecture"],
                                      jax.random.PRNGKey(self.seed))
        self.params, self.frozen = jax.tree.map(
            lambda a: a.astype(self.dtype), (trained, frozen))
        self.key = jax.random.PRNGKey(self.seed)
        self.x = _cast(jnp.asarray(data.x), self.dtype)
        self.test_x = _cast(jnp.asarray(test.x), self.dtype)
        self.test_y = jnp.asarray(test.y)
        self.pop = Population(cell, *family.labels(data), self.seed, b, V)
        self.y = jnp.asarray(data.y)
        self._round = round_fn(family, float(cell.config["fl"]["lr"]),
                               bool(cell.traffic["compress_updates"]),
                               self.precision, self.dtype.name, fault,
                               int(shards), bool(family.SEQUENTIAL_LANES))

    def round(self) -> float:
        rows, weights, mask = self.pop.draw()
        rows = jnp.asarray(rows)
        self.params, loss, self.key = self._round(
            self.params, self.frozen, self.x[rows], self.y[rows],
            jnp.asarray(weights), jnp.asarray(mask), self.key)
        return loss

    def rounds(self, n: int) -> List[float]:
        return [float(v) for v in jax.device_get([self.round()
                                                  for _ in range(n)])]

    def accuracy(self, block: int = 1000) -> float:
        hits = _eval_fn(self.family, self.precision)
        n = int(self.test_y.shape[0])
        got = [hits(self.params, self.frozen, self.test_x[i:i + block],
                    self.test_y[i:i + block]) for i in range(0, n, block)]
        return (sum(int(h) for h, _ in got)
                / sum(int(c) for _, c in got))

    def host_params(self) -> Any:
        """The trained weights on the host, in float32."""
        return jax.tree.map(lambda a: np.asarray(a, np.float32),
                            jax.device_get(self.params))


def readings(run: Reference, steps: int, rounds_per_step: int) -> Dict:
    """What the check compares, from `steps` steps of `rounds_per_step`
    rounds each: every round's loss, the model before and after each
    step, and the accuracy after each step."""
    out = {"losses": [], "params": [run.host_params()], "acc": []}
    for _ in range(steps):
        out["losses"] += run.rounds(rounds_per_step)
        out["params"].append(run.host_params())
        out["acc"].append(run.accuracy())
    return out
