"""The paper's evaluation model: the FedAvg CNN (McMahan et al. [2]) for
MNIST / CIFAR-10 image classification — two 5x5 conv + pool stages, one
512-unit FC layer, softmax head."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.conv_dw.ops import conv_filter_grad


@dataclass(frozen=True)
class CNNConfig:
    name: str
    input_hw: Tuple[int, int]
    in_channels: int
    n_classes: int = 10
    conv_channels: Tuple[int, int] = (32, 64)
    kernel: int = 5
    fc_dim: int = 512

    @property
    def flat_dim(self) -> int:
        h, w = self.input_hw
        return (h // 4) * (w // 4) * self.conv_channels[1]


def mnist_cnn() -> CNNConfig:
    return CNNConfig(name="cnn-mnist", input_hw=(28, 28), in_channels=1)


def mnist_cnn_small() -> CNNConfig:
    """Smoke-scale variant (same topology, ~30x fewer params). The round-
    step bench runs on it so simulator overhead (per-client dispatch, host
    compression roundtrips, device->host syncs) dominates over GEMM time —
    the regime the batched backend exists for."""
    return CNNConfig(name="cnn-mnist-small", input_hw=(28, 28), in_channels=1,
                     conv_channels=(8, 16), fc_dim=64)


def mnist_cnn_tiny() -> CNNConfig:
    """Overhead-scale variant: 1x1 kernels (the im2col path degenerates to
    pointwise GEMMs) and minimal widths, so one round's fwd/bwd compute
    sits at dispatch-overhead scale (~sub-ms). The fleet rows of the
    round-step bench run on it: what `run_fleet` amortizes is per-run
    driver/dispatch cost, which GEMM time would otherwise mask entirely
    (see EXPERIMENTS.md §Driver overhead)."""
    return CNNConfig(name="cnn-mnist-tiny", input_hw=(28, 28), in_channels=1,
                     conv_channels=(1, 2), kernel=1, fc_dim=8)


def cifar_cnn() -> CNNConfig:
    return CNNConfig(name="cnn-cifar", input_hw=(32, 32), in_channels=3)


def init_cnn(cfg: CNNConfig, key) -> Dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    c1, c2 = cfg.conv_channels
    k = cfg.kernel

    def conv_init(key, shape):
        fan_in = shape[0] * shape[1] * shape[2]
        return jax.random.normal(key, shape, jnp.float32) * (2.0 / fan_in) ** 0.5

    def fc_init(key, shape):
        return jax.random.normal(key, shape, jnp.float32) * (2.0 / shape[0]) ** 0.5

    return {
        "conv1": {"w": conv_init(k1, (k, k, cfg.in_channels, c1)),
                  "b": jnp.zeros((c1,), jnp.float32)},
        "conv2": {"w": conv_init(k2, (k, k, c1, c2)),
                  "b": jnp.zeros((c2,), jnp.float32)},
        "fc1": {"w": fc_init(k3, (cfg.flat_dim, cfg.fc_dim)),
                "b": jnp.zeros((cfg.fc_dim,), jnp.float32)},
        "fc2": {"w": fc_init(k4, (cfg.fc_dim, cfg.n_classes)),
                "b": jnp.zeros((cfg.n_classes,), jnp.float32)},
    }


@jax.custom_vjp
def _ps_matmul(a, w):
    """`a @ w` with a *pad-stable* backward.

    Forward is exactly the plain matmul (bit-identical to `a @ w`). The
    backward restructures the filter gradient: XLA's autodiff dW is one
    dot_general contracting over (batch x spatial), whose fp32
    accumulation XLA re-associates when the contraction LENGTH changes —
    so a batch padded with zero-cotangent rows (the Study API's
    (V, b)-envelope, study.py) would not reproduce the unpadded bits.
    Here dW is computed per sample (contraction over the sample's own
    fixed-size spatial dims only) and then reduced over the leading batch
    axis, where appended exact-zero per-sample grads cannot perturb the
    accumulation. Verified bit-identical under zero-masked batch padding
    and under client/fleet vmap in tests/test_study.py.
    """
    return a @ w


def _ps_matmul_fwd(a, w):
    return a @ w, (a, w)


def _ps_matmul_bwd(res, dy):
    a, w = res
    K, O = w.shape
    da = dy @ w.T
    dw_b = jnp.einsum(
        "bnk,bno->bko", a.reshape(a.shape[0], -1, K),
        dy.reshape(dy.shape[0], -1, O))
    return da, jnp.sum(dw_b, axis=0)


_ps_matmul.defvjp(_ps_matmul_fwd, _ps_matmul_bwd)


def _patches(x, k):
    """'SAME' kxk patches of x (B, H, W, C) -> (B, H, W, k*k*C), ordered to
    match an HWIO filter flattened as (k*k*C, O)."""
    B, H, W, C = x.shape
    # Symmetric k//2 padding only equals XLA SAME for odd windows.
    assert k % 2 == 1, f"im2col path requires odd kernel, got {k}"
    p = k // 2
    xp = jnp.pad(x, ((0, 0), (p, p), (p, p), (0, 0)))
    cols = [xp[:, i : i + H, j : j + W, :] for i in range(k) for j in range(k)]
    return jnp.concatenate(cols, axis=-1)


def _conv_fwd(x, w):
    """'SAME' stride-1 conv of x (B, H, W, C) with an HWIO filter, at the
    default matmul precision."""
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


@jax.custom_vjp
def _ps_conv(x, w):
    """`_conv_fwd` with a *pad-stable* backward, as `_ps_matmul` is for the
    im2col path. dx is XLA's own transposed conv. dW is summed sample by
    sample, in sample order, by the Pallas kernel of
    `kernels.conv_dw`, which builds each sample's patches in VMEM, so a batch
    padded with zero-cotangent samples reproduces the unpadded filter
    gradient, and the sum's order does not depend on how the compiler
    tiles the program around it (PERF.md §6 compares the dW forms)."""
    return _conv_fwd(x, w)


def _ps_conv_fwd(x, w):
    return _conv_fwd(x, w), (x, w)


def _ps_conv_bwd(res, dy):
    x, w = res
    dx, = jax.vjp(lambda x: _conv_fwd(x, w), x)[1](dy)
    return dx, conv_filter_grad(x, dy, w.shape[0])


_ps_conv.defvjp(_ps_conv_fwd, _ps_conv_bwd)


def _conv_im2col(x, p):
    k = p["w"].shape[0]
    w = p["w"].reshape(-1, p["w"].shape[-1])  # (k*k*C, O)
    return _ps_matmul(_patches(x, k), w) + p["b"]


def _conv_xla(x, p):
    return _ps_conv(x, p["w"]) + p["b"]


def _conv(x, p):
    # The lowering is chosen by the platform the code is compiled for.
    # TPU: XLA's own convolution (`_conv_xla`). im2col writes k*k shifted
    # copies of every activation to HBM (and pads MNIST's one input channel
    # 128x on the minor dimension), which took most of the local steps' and
    # the eval's time there; the forward and dx convs read the activations
    # in place, and dW's patches are built only in VMEM, inside the Pallas
    # kernel that sums it sample by sample (`_ps_conv`).
    # Every other platform (the CPU): im2col + matmul (`_conv_im2col`).
    # XLA:CPU lowers the filter/input gradients of a direct conv to
    # transposed convolutions that run ~10-25x slower than the forward
    # pass; the patches+dot form keeps both directions on the GEMM path.
    # Both paths keep the parameter gradients pad-stable (`_ps_matmul`,
    # `_ps_conv`), which the Study's (V, b)-envelope relies on.
    return jax.lax.platform_dependent(x, p, tpu=_conv_xla,
                                      default=_conv_im2col)


def _maxpool(x):
    # Non-overlapping 2x2 window == reshape + max; reduce_window's gradient
    # (select-and-scatter) is a scalar loop on XLA:CPU. Tie-breaking in the
    # VJP differs (split vs first-hit) but the forward is exact.
    B, H, W, C = x.shape
    assert H % 2 == 0 and W % 2 == 0, f"2x2 pool needs even dims, got {H}x{W}"
    return jnp.max(x.reshape(B, H // 2, 2, W // 2, 2, C), axis=(2, 4))


def cnn_forward(cfg: CNNConfig, params: Dict, images: jnp.ndarray) -> jnp.ndarray:
    """images: (B, H, W, C) -> logits (B, n_classes)."""
    x = _maxpool(jax.nn.relu(_conv(images, params["conv1"])))
    x = _maxpool(jax.nn.relu(_conv(x, params["conv2"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
    return x @ params["fc2"]["w"] + params["fc2"]["b"]


def _seq_mean(v: jnp.ndarray, n) -> jnp.ndarray:
    """Mean over a 1-D array via a sequential left-fold (lax.scan).

    XLA's reduce re-associates its fp32 accumulation when the reduction
    LENGTH changes, so `jnp.mean(nll[:b])` and a zero-masked mean over a
    padded (b_env,) array can differ in the last ulp. A left-fold's
    partial sums are prefix-stable: appending exact-zero terms (masked
    padded samples) leaves every partial — and the total — bit-identical.
    Both `cnn_loss` and `cnn_loss_masked` reduce through this, which is
    what makes the Study envelope's train-loss HISTORY (not just the
    trained params) bit-identical to unpadded runs. The gradient is
    unchanged from jnp.mean (each element's cotangent is exactly 1/n)."""
    total, _ = jax.lax.scan(
        lambda acc, x: (acc + x, None), jnp.zeros((), v.dtype), v)
    return total / n


def cnn_loss(cfg: CNNConfig, params: Dict, batch: Dict) -> Tuple[jnp.ndarray, Dict]:
    logits = cnn_forward(cfg, params, batch["x"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1)[:, 0]
    loss = _seq_mean(nll, nll.shape[0])
    acc = jnp.mean((jnp.argmax(logits, -1) == batch["y"]).astype(jnp.float32))
    return loss, {"ce_loss": loss, "accuracy": acc}


def cnn_loss_masked(
    cfg: CNNConfig, params: Dict, batch: Dict, sample_mask: jnp.ndarray,
    n: jnp.ndarray,
) -> Tuple[jnp.ndarray, Dict]:
    """`cnn_loss` over the first `n` samples of a padded batch.

    sample_mask is a traced (B_env,) 0/1 float (the leading int(n) entries
    are 1) and n the valid-sample count as f32. Padded rows contribute an
    exact 0 to the nll sum (x * 0.0) and exact-zero logits cotangents, so
    at any padding — including none — the loss and its params gradient are
    bit-identical to `cnn_loss` on the unpadded batch (the `_ps_matmul`
    backward keeps the conv filter gradients pad-stable). This is the
    loss form the Study API's (V, b)-envelope round step runs."""
    logits = cnn_forward(cfg, params, batch["x"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1)[:, 0]
    loss = _seq_mean(nll * sample_mask, n)
    hit = (jnp.argmax(logits, -1) == batch["y"]).astype(jnp.float32)
    acc = jnp.sum(hit * sample_mask) / n
    return loss, {"ce_loss": loss, "accuracy": acc}
