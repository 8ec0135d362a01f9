"""Conv filter-gradient kernel (`kernels/conv_dw`), interpreted on the CPU:
against its jnp oracle and against XLA's own filter gradient, and the
sample-ordered sum that makes it pad-stable."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.conv_dw import ops
from repro.kernels.conv_dw.kernel import conv_dw_kernel
from repro.kernels.conv_dw.ref import conv_dw_ref
from repro.models import cnn


@pytest.mark.parametrize("B,K,N,O,block_b", [(4, 25, 896, 32, 1),
                                             (6, 75, 320, 64, 3),
                                             (8, 1, 40, 2, 8)])
def test_kernel_matches_ref(key, B, K, N, O, block_b):
    kp, kd = jax.random.split(key)
    p = jax.random.normal(kp, (B, K, N))
    dy = jax.random.normal(kd, (B, N, O))
    np.testing.assert_allclose(
        np.asarray(conv_dw_kernel(p, dy, block_b=block_b)),
        np.asarray(conv_dw_ref(p, dy)), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("k,C,O,H", [(5, 1, 32, 28), (5, 32, 64, 14),
                                     (5, 3, 32, 32), (1, 1, 2, 28)])
def test_filter_grad_matches_xla(key, k, C, O, H):
    """dW over the padded-row patches equals the `jax.vjp` of the conv in
    the filter, at the CNN's layer shapes (1x1 for mnist_cnn_tiny)."""
    kx, kw, kd = jax.random.split(key, 3)
    x = jax.random.normal(kx, (3, H, H, C))
    w = jax.random.normal(kw, (k, k, C, O))
    dy = jax.random.normal(kd, (3, H, H, O))
    want, = jax.vjp(lambda w: cnn._conv_fwd(x, w), w)[1](dy)
    got = ops.conv_filter_grad(x, dy, k)
    assert got.shape == w.shape
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5 * scale)


@pytest.mark.parametrize("max_block_b", [1, 8])
def test_filter_grad_pad_stable(key, max_block_b):
    """Five samples, and the same five followed by three with zero
    cotangents (any input): bit-identical dW, whatever the block."""
    kx, kd = jax.random.split(key)
    x = jax.random.normal(kx, (8, 14, 14, 4))
    dy = jax.random.normal(kd, (8, 14, 14, 8)).at[5:].set(0.0)
    dw = lambda x, dy: ops._filter_grad(  # noqa: E731
        x, dy, 5, jnp.float32, interpret=True, max_block_b=max_block_b)
    np.testing.assert_array_equal(np.asarray(dw(x[:5], dy[:5])),
                                  np.asarray(dw(x, dy)))


def test_patches_t_is_im2col_transposed(key):
    """Row t*C + c of `patches_t` at pixel (h, w) is `cnn._patches`' column
    t*C + c there; the padded columns w >= W are dropped."""
    x = jax.random.normal(key, (2, 6, 6, 3))
    k, H, W = 5, 6, 6
    Wr = ops._row_width(W, k)
    pt = ops.patches_t(x, k).reshape(2, k * k * 3, H, Wr)[..., :W]
    ref = cnn._patches(x, k).transpose(0, 3, 1, 2)
    np.testing.assert_array_equal(np.asarray(pt), np.asarray(ref))
