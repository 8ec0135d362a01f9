"""Filter gradient of a 'SAME' stride-1 conv through the Pallas kernel:
XLA lays each image and its cotangent out as padded rows (pure data
movement, about the size of the activation), the kernel builds the
patches in VMEM and sums sample by sample. `patches_t` is the patch
tensor the kernel assembles, kept as a helper of its oracle
(`ref.conv_dw_ref`)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.conv_dw.kernel import conv_dw_kernel


def _row_width(W: int, k: int) -> int:
    """Width of a padded image row: W + k - 1, rounded up to 8 so that
    (H, width) folds onto whole sublane tiles."""
    return -(-(W + k - 1) // 8) * 8


def padded_rows(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """x (B, H, W, C) as (B, C, (H+k)*Wr): each channel's image 'SAME'-
    padded, in rows of width Wr = `_row_width(W, k)`, flattened row by
    row, with one spare zero row so that every tap slice of `patches_t`
    stays inside."""
    B, H, W, C = x.shape
    assert k % 2 == 1, f"'SAME' patches need an odd kernel, got {k}"
    p, Wr = k // 2, _row_width(W, k)
    xp = jnp.pad(x.transpose(0, 3, 1, 2),
                 ((0, 0), (0, 0), (p, p + 1), (p, Wr - W - p)))
    return xp.reshape(B, C, -1)


def patches_t(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """'SAME' kxk patches of x (B, H, W, C) as (B, k*k*C, H*Wr), rows
    ordered like an HWIO filter flattened to (k*k*C, O).

    Pixels run along the lanes over padded rows of width Wr =
    `_row_width(W, k)`: with each image flattened row by row
    (`padded_rows`), tap (i, j) of every output pixel is one contiguous
    lane slice at offset i*Wr + j. Columns w >= W of each row hold other
    pixels' values; the cotangent `dy_rows` gives them exact zeros."""
    _, H, W, _ = x.shape
    Wr = _row_width(W, k)
    xf = padded_rows(x, k)
    N = H * Wr
    return jnp.concatenate([xf[:, :, i * Wr + j:i * Wr + j + N]
                            for i in range(k) for j in range(k)], axis=1)


def dy_rows(dy: jnp.ndarray, k: int) -> jnp.ndarray:
    """dy (B, H, W, O) as (B, H*Wr, O) over `patches_t`'s padded rows,
    zero in the columns w >= W."""
    B, H, W, O = dy.shape
    Wr = _row_width(W, k)
    return jnp.pad(dy, ((0, 0), (0, 0), (0, Wr - W), (0, 0))).reshape(
        B, H * Wr, O)


def _filter_grad(x, dy, k, dtype, interpret, max_block_b=8):
    B, H, W, C = x.shape
    O = dy.shape[-1]
    block_b = max(d for d in range(1, min(B, max_block_b) + 1) if B % d == 0)
    dw = conv_dw_kernel(padded_rows(x.astype(dtype), k),
                        dy_rows(dy.astype(dtype), k),
                        k=k, block_b=block_b, interpret=interpret)
    return dw.reshape(k, k, C, O)


def conv_filter_grad(x: jnp.ndarray, dy: jnp.ndarray, k: int) -> jnp.ndarray:
    """dW (k, k, C, O) of a 'SAME' stride-1 conv of x (B, H, W, C) with
    output cotangent dy (B, H, W, O), summed over the batch in sample
    order. On the TPU the kernel runs compiled and its products take
    bf16 operands with f32 sums, as XLA's default precision does for an
    f32 conv there; elsewhere it runs interpreted in f32."""
    return jax.lax.platform_dependent(
        x, dy,
        tpu=functools.partial(_filter_grad, k=k, dtype=jnp.bfloat16,
                              interpret=False),
        default=functools.partial(_filter_grad, k=k, dtype=jnp.float32,
                                  interpret=True))
