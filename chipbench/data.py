"""The benchmark's own seeded image-classification data.

The same task as the program's synthetic MNIST/CIFAR-like sets
(`repro/data/synthetic.py`): class-conditional images made of a smooth
class template (7x7 normal seeds upsampled to the image size) plus
normal noise of std 0.8, through tanh. It is kept here so that a change
to the program's generator cannot move the yardstick, and it is drawn on
the device in one jitted call per split, so that making 60,000 images is
set-up of milliseconds rather than seconds of numpy.

`DataMaker` is registered in the program's dataset registry under a name
of the benchmark's own; the program then builds the cell's Simulator on
these arrays through its public `ExperimentSpec`, and the reference
reads the same arrays back from the maker.

The data set of a configuration is fixed, as MNIST's is: it is drawn
from the configuration's `data_seed`, whatever seed the program asks
for, and the run's seed varies the partition, the weights, the batch
order and the realizations. The program's eval closes over the test
images, which makes them constants of the compiled eval; a test set that
moved with the seed would be a new program, compiled again, in every run.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NOISE_STD = 0.8
TEMPLATE_SEED_HW = 7


@dataclass(frozen=True)
class Images:
    """What the program's data pipeline reads: x (N, H, W, C) float32 on
    the device, y (N,) int32 on the host, the class count, and the class
    templates that a held-out split shares with its training split."""

    x: Any
    y: np.ndarray
    n_classes: int
    templates: Any = field(default=None, repr=False, compare=False)

    @property
    def n(self) -> int:
        return len(self.y)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, n: int, hw: Tuple[int, int], c: int, n_classes: int,
          templates=None):
    k_y, k_t, k_x = jax.random.split(key, 3)
    y = jax.random.randint(k_y, (n,), 0, n_classes, jnp.int32)
    if templates is None:
        h, w = hw
        seeds = jax.random.normal(
            k_t, (n_classes, TEMPLATE_SEED_HW, TEMPLATE_SEED_HW, c),
            jnp.float32)
        reps = (-(-h // TEMPLATE_SEED_HW), -(-w // TEMPLATE_SEED_HW))
        up = jnp.repeat(jnp.repeat(seeds, reps[0], axis=1), reps[1], axis=2)
        templates = up[:, :h, :w, :]
    noise = jax.random.normal(k_x, (n,) + templates.shape[1:], jnp.float32)
    x = jnp.tanh(templates[y] + NOISE_STD * noise)
    return x, y, templates


class DataMaker:
    """A configuration's image sets. `__call__(n, seed=, task=)` is the
    program's dataset-registry signature; results are kept per split, so
    the reference gets the very arrays the program ran on without drawing
    them twice."""

    def __init__(self, hw: Tuple[int, int], channels: int, n_classes: int,
                 data_seed: int):
        self.data_seed = int(data_seed)
        self.hw = (int(hw[0]), int(hw[1]))
        self.channels = int(channels)
        self.n_classes = int(n_classes)
        self._made: Dict[Tuple[int, int], Images] = {}

    def __call__(self, n: int, seed: int = 0,
                 task: Optional[Images] = None) -> Images:
        """The training split (task None) or a held-out split of `task`;
        `seed` is the program's and is not used (see the module doc)."""
        split = 0 if task is None else 1
        got = self._made.get((int(n), split))
        if got is not None:
            return got
        key = jax.random.fold_in(jax.random.PRNGKey(self.data_seed), split)
        x, y, templates = _draw(
            key, int(n), self.hw, self.channels, self.n_classes,
            None if task is None else task.templates)
        out = Images(x=x, y=np.asarray(jax.device_get(y)),
                     n_classes=self.n_classes, templates=templates)
        self._made[(int(n), split)] = out
        return out
