"""Conv filter-gradient kernel (`kernels/conv_dw`), interpreted on the CPU:
against its jnp oracle and against XLA's own filter gradient, and the
sample-ordered sum that makes it pad-stable."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.kernels.conv_dw import ops
from repro.kernels.conv_dw.kernel import conv_dw_kernel
from repro.kernels.conv_dw.ref import conv_dw_ref
from repro.models import cnn


# (B, H, W, C, O, k, block_b): the first three are the (B, K, N, O) cases
# the kernel was first tested at on patch tensors; then every CNN layer
# (mnist_cnn, cifar_cnn, mnist_cnn_small, mnist_cnn_tiny's 1x1).
LAYERS = [(4, 28, 28, 1, 32, 5, 1), (6, 20, 12, 3, 64, 5, 3),
          (8, 5, 8, 1, 2, 1, 8),
          (4, 28, 28, 1, 32, 5, 4), (4, 14, 14, 32, 64, 5, 2),
          (2, 32, 32, 3, 32, 5, 2), (2, 16, 16, 32, 64, 5, 1),
          (4, 28, 28, 1, 8, 5, 4), (4, 14, 14, 8, 16, 5, 4),
          (4, 28, 28, 1, 1, 1, 4), (4, 14, 14, 1, 2, 1, 2)]


def _inputs(key, B, H, W, C, O):
    kx, kd = jax.random.split(key)
    return (jax.random.normal(kx, (B, H, W, C)),
            jax.random.normal(kd, (B, H, W, O)))


def _patch_tensor_dw(p, dy, block_b, interpret=True):
    """The kernel's former form: the same sample-ordered sum, over the
    patch tensor `ops.patches_t` and the padded cotangent `ops.dy_rows`
    read from HBM (also a form of scripts/conv_dw_forms.py)."""
    def body(p_ref, dy_ref, dw_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            dw_ref[...] = jnp.zeros_like(dw_ref)

        for s in range(p_ref.shape[0]):
            dw_ref[...] += jnp.dot(p_ref[s], dy_ref[s],
                                   preferred_element_type=jnp.float32)

    B, K, N = p.shape
    O = dy.shape[-1]
    return pl.pallas_call(
        body, grid=(B // block_b,),
        in_specs=[pl.BlockSpec((block_b, K, N), lambda b: (b, 0, 0)),
                  pl.BlockSpec((block_b, N, O), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((K, O), lambda b: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, O), jnp.float32),
        interpret=interpret)(p, dy)


@pytest.mark.parametrize("B,H,W,C,O,k,block_b", LAYERS)
def test_kernel_matches_ref(key, B, H, W, C, O, k, block_b):
    x, dy = _inputs(key, B, H, W, C, O)
    got = conv_dw_kernel(ops.padded_rows(x, k), ops.dy_rows(dy, k), k=k,
                         block_b=block_b)
    want = conv_dw_ref(ops.patches_t(x, k), ops.dy_rows(dy, k))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("B,H,W,C,O,k,block_b", LAYERS)
def test_kernel_bits_match_patch_tensor_form(key, B, H, W, C, O, k, block_b):
    """Patches assembled in VMEM give the bits of the patch tensor read
    from HBM: the same per-sample products, summed in the same order."""
    x, dy = _inputs(key, B, H, W, C, O)
    got = conv_dw_kernel(ops.padded_rows(x, k), ops.dy_rows(dy, k), k=k,
                         block_b=block_b)
    want = _patch_tensor_dw(ops.patches_t(x, k), ops.dy_rows(dy, k), block_b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


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
