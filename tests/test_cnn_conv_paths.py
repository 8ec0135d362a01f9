"""The CNN's two conv lowerings, both run here on the CPU.

`cnn._conv` takes `_conv_xla` (XLA's own convolution with the pad-stable
`_ps_conv` backward) when the code is compiled for a TPU, and
`_conv_im2col` (patches + `_ps_matmul`) everywhere else. These tests pin
`_conv` to each path in turn:

  * the TPU path's forward and gradients match the im2col path within an f32
    tolerance, at the shapes of `mnist_cnn`, `cifar_cnn` and
    `mnist_cnn_tiny` (1x1 kernels);
  * on both paths, the loss and parameter gradients are bit-identical
    between a batch and the same batch zero-padded with `sample_mask`
    zeros, the contract the Study's (V, b)-envelope relies on
    (tests/test_study.py asserts it through whole runs on the CPU);
  * `_conv` picks the im2col path when lowered for the CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import cnn

CONFIGS = {"mnist_cnn": cnn.mnist_cnn, "cifar_cnn": cnn.cifar_cnn,
           "mnist_cnn_tiny": cnn.mnist_cnn_tiny}


def _batch(cfg, B, seed=1):
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (B, *cfg.input_hw, cfg.in_channels))
    y = jax.random.randint(ky, (B,), 0, cfg.n_classes)
    return x, y


def _loss_and_grads(cfg, params, x, y, mask, n):
    """`cnn_loss_masked`'s loss and its gradients in params and images,
    traced afresh with whatever `cnn._conv` is now."""
    f = jax.jit(jax.value_and_grad(
        lambda p, x: cnn.cnn_loss_masked(cfg, p, {"x": x, "y": y}, mask,
                                         n)[0], argnums=(0, 1)))
    return f(params, x)


def _leaves_close(a, b, rtol):
    for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        u, v = np.asarray(u), np.asarray(v)
        scale = float(np.max(np.abs(v)))
        np.testing.assert_allclose(u, v, rtol=0, atol=rtol * scale)


@pytest.mark.parametrize("name", list(CONFIGS))
def test_xla_conv_matches_im2col(name, monkeypatch):
    cfg = CONFIGS[name]()
    params = cnn.init_cnn(cfg, jax.random.PRNGKey(0))
    x, y = _batch(cfg, 4)
    mask, n = jnp.ones((4,)), jnp.float32(4)
    got = {}
    for path in ("xla", "im2col"):
        monkeypatch.setattr(cnn, "_conv", getattr(cnn, f"_conv_{path}"))
        logits = jax.jit(lambda p, x: cnn.cnn_forward(cfg, p, x))(params, x)
        got[path] = logits, _loss_and_grads(cfg, params, x, y, mask, n)
    (logits_xla, (l_xla, g_xla)), (logits_ref, (l_ref, g_ref)) = (
        got["xla"], got["im2col"])
    _leaves_close(logits_xla, logits_ref, 1e-5)
    np.testing.assert_allclose(float(l_xla), float(l_ref), rtol=1e-5)
    _leaves_close(g_xla, g_ref, 1e-4)


@pytest.mark.parametrize("path", ["xla", "im2col"])
@pytest.mark.parametrize("name", list(CONFIGS))
def test_conv_grads_pad_stable(name, path, monkeypatch):
    """Loss and parameter gradients of each path at b=3 against the same
    three samples padded to b=8 with zero-masked samples."""
    monkeypatch.setattr(cnn, "_conv", getattr(cnn, f"_conv_{path}"))
    cfg = CONFIGS[name]()
    params = cnn.init_cnn(cfg, jax.random.PRNGKey(0))
    b, B = 3, 8
    x, y = _batch(cfg, B)
    x_pad = x.at[b:].set(0.0)  # padded rows, as the envelope zeroes them
    n = jnp.float32(b)
    l_b, (g_b, _) = _loss_and_grads(cfg, params, x[:b], y[:b],
                                    jnp.ones((b,)), n)
    l_B, (g_B, _) = _loss_and_grads(cfg, params, x_pad, y,
                                    (jnp.arange(B) < b).astype(jnp.float32),
                                    n)
    assert np.float32(l_b).tobytes() == np.float32(l_B).tobytes()
    for u, v in zip(jax.tree.leaves(g_b), jax.tree.leaves(g_B)):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


def test_conv_lowers_im2col_on_cpu():
    """On the CPU `_conv` is the im2col path: bit-identical to it, and
    its compiled module holds no convolution."""
    cfg = cnn.mnist_cnn_small()
    params = cnn.init_cnn(cfg, jax.random.PRNGKey(0))
    x, _ = _batch(cfg, 2)
    conv = jax.jit(cnn._conv)
    np.testing.assert_array_equal(
        np.asarray(conv(x, params["conv1"])),
        np.asarray(jax.jit(cnn._conv_im2col)(x, params["conv1"])))
    hlo = conv.lower(x, params["conv1"]).compile().as_text()
    assert "convolution(" not in hlo
