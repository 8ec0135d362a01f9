"""Pallas TPU kernel: a conv filter gradient summed sample by sample.

dW = sum_b P_b @ dY_b, where P_b (K, N) is sample b's transposed patch
matrix (K = k*k*C filter taps, N pixel positions over padded rows of
width Wr; `ops.patches_t`) and dY_b (N, O) its output cotangent over the
same rows (`ops.dy_rows`). P_b is never built in HBM: the kernel takes
each sample's row-padded image (`ops.padded_rows`) and assembles P_b in a
VMEM scratch, tap (i, j) being one static lane slice of the image at
offset i*Wr + j. The grid walks the samples in order and adds each one's
(K, O) product into an f32 accumulator that stays resident in VMEM, so
the sum's order is fixed by this code, not by the compiler's tiling of
the surrounding program: a batch padded with zero-cotangent samples adds
exact zeros after the real ones and reproduces the unpadded bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dw_kernel(x_ref, dy_ref, dw_ref, p_ref, *, k):
    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    _, C, L = x_ref.shape
    N = dy_ref.shape[1]
    Wr = (L - N) // k

    for s in range(x_ref.shape[0]):  # the block's samples, in order
        for t in range(k * k):  # rows t*C.. of an HWIO filter, t = i*k + j
            off = (t // k) * Wr + t % k
            p_ref[t * C:(t + 1) * C, :] = x_ref[s, :, off:off + N]
        dw_ref[...] += jnp.dot(p_ref[...], dy_ref[s],
                               preferred_element_type=jnp.float32)


def conv_dw_kernel(xf: jnp.ndarray, dy: jnp.ndarray, *, k: int,
                   block_b: int = 1, interpret: bool = True) -> jnp.ndarray:
    """xf: (B, C, (H+k)*Wr) row-padded images, dy: (B, H*Wr, O) ->
    (k*k*C, O) f32, summed over b in order, `block_b` samples per grid
    step."""
    B, C, L = xf.shape
    N, O = dy.shape[1:]
    assert (L - N) % k == 0 and N % ((L - N) // k) == 0, (xf.shape, dy.shape)
    assert B % block_b == 0, (B, block_b)
    K = k * k * C
    return pl.pallas_call(
        functools.partial(_dw_kernel, k=k),
        grid=(B // block_b,),
        in_specs=[pl.BlockSpec((block_b, C, L), lambda b: (b, 0, 0)),
                  pl.BlockSpec((block_b, N, O), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((K, O), lambda b: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, O), jnp.float32),
        scratch_shapes=[pltpu.VMEM((K, N), xf.dtype)],
        interpret=interpret,
    )(xf, dy)
