"""Pallas TPU kernel: a conv filter gradient summed sample by sample.

dW = sum_b P_b @ dY_b, where P_b (K, N) is sample b's transposed patch
matrix (K = k*k*C filter taps, N pixel positions; `ops.patches_t`) and
dY_b (N, O) its output cotangent. The grid walks the samples in order and adds each one's
(K, O) product into an f32 accumulator that stays resident in VMEM, so the
sum's order is fixed by this code, not by the compiler's tiling of the
surrounding program: a batch padded with zero-cotangent samples adds exact
zeros after the real ones and reproduces the unpadded bits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dw_kernel(p_ref, dy_ref, dw_ref):
    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for s in range(p_ref.shape[0]):  # the block's samples, in order
        dw_ref[...] += jnp.dot(p_ref[s], dy_ref[s],
                               preferred_element_type=jnp.float32)


def conv_dw_kernel(p: jnp.ndarray, dy: jnp.ndarray, *, block_b: int = 1,
                   interpret: bool = True) -> jnp.ndarray:
    """p: (B, K, N), dy: (B, N, O) -> (K, O) f32, summed over b in order,
    `block_b` samples per grid step."""
    B, K, N = p.shape
    O = dy.shape[-1]
    assert B % block_b == 0, (B, block_b)
    return pl.pallas_call(
        _dw_kernel,
        grid=(B // block_b,),
        in_specs=[pl.BlockSpec((block_b, K, N), lambda b: (b, 0, 0)),
                  pl.BlockSpec((block_b, N, O), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((K, O), lambda b: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, O), jnp.float32),
        interpret=interpret,
    )(p, dy)
