"""jit'd wrapper for the quantize kernel (row padding + PRNG handling)."""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.quantize.kernel import quantize_kernel
from repro.kernels.quantize.ref import dequantize_ref, stochastic_noise


def quantize(x: jnp.ndarray, key, block_r: int = 256) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (R, D) fp32 -> (q int8 (R, D), scale (R, 1))."""
    R, D = x.shape
    # Same packed-8-bit noise stream as quantize_ref: given the same key the
    # two impls stay bit-identical (tests/test_kernels_quantize.py).
    u = stochastic_noise(key, (R, D))
    pad = (-R) % block_r
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        u = jnp.pad(u, ((0, pad), (0, 0)))
    q, s = quantize_kernel(x, u, block_r=min(block_r, x.shape[0]),
                           interpret=interpret_mode())
    return q[:R], s[:R]


def dequantize(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return dequantize_ref(q, scale)
