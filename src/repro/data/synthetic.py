"""Synthetic datasets (the container is offline — see DESIGN.md §7).

Classification sets mimic MNIST / CIFAR-10 in shape and cardinality: inputs
are drawn from per-class Gaussian blobs pushed through a fixed random
teacher CNN-ish map, giving a learnable but non-trivial task. Token streams
serve the LM architectures.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import numpy as np


@dataclass(frozen=True)
class ClassificationData:
    x: np.ndarray  # (N, H, W, C) float32
    y: np.ndarray  # (N,) int32
    n_classes: int
    # (n_classes, H, W, C) class templates: the task itself. A held-out
    # split passes `task=` to draw new samples of the same templates.
    templates: Optional[np.ndarray] = field(
        default=None, repr=False, compare=False)

    @property
    def n(self) -> int:
        return len(self.y)


def _classification(n, seed, hw, c, n_classes, task):
    """Class-conditional images: smooth class template + structured noise.
    The templates come from `task` when given (a held-out split of that
    task), else from `seed`."""
    rng = np.random.default_rng(seed)
    y = rng.integers(0, n_classes, n).astype(np.int32)
    h, w = hw
    if task is None:
        # Low-frequency class templates upsampled from 7x7 seeds.
        seeds = rng.normal(0.0, 1.0, (n_classes, 7, 7, c)).astype(np.float32)
        reps = (int(np.ceil(h / 7)), int(np.ceil(w / 7)))
        templates = np.kron(seeds, np.ones((1, *reps, 1), np.float32))[
            :, :h, :w, :]
    else:
        templates = task.templates
    x = templates[y]
    x = x + rng.normal(0.0, 0.8, x.shape).astype(np.float32)
    # Mild nonlinearity so linear probes don't trivially solve it.
    return ClassificationData(x=np.tanh(x).astype(np.float32), y=y,
                              n_classes=n_classes, templates=templates)


def make_mnist_like(n: int = 10_000, seed: int = 0,
                    task: Optional[ClassificationData] = None
                    ) -> ClassificationData:
    return _classification(n, seed, (28, 28), 1, 10, task)


def make_cifar_like(n: int = 10_000, seed: int = 0,
                    task: Optional[ClassificationData] = None
                    ) -> ClassificationData:
    return _classification(n, seed, (32, 32), 3, 10, task)


def make_token_stream(
    n_tokens: int, vocab_size: int, seed: int = 0, order: int = 2,
) -> np.ndarray:
    """Markov-ish synthetic token stream (learnable bigram structure)."""
    rng = np.random.default_rng(seed)
    # Sparse bigram transition: each token strongly prefers a few successors.
    fanout = 8
    succ = rng.integers(0, vocab_size, (vocab_size, fanout))
    toks = np.empty(n_tokens, np.int32)
    toks[0] = rng.integers(0, vocab_size)
    noise = rng.random(n_tokens)
    choice = rng.integers(0, fanout, n_tokens)
    rand_tok = rng.integers(0, vocab_size, n_tokens)
    for i in range(1, n_tokens):
        toks[i] = succ[toks[i - 1], choice[i]] if noise[i] < 0.8 else rand_tok[i]
    return toks
