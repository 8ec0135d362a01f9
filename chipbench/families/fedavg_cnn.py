"""The FedAvg CNN (McMahan et al., AISTATS 2017) as a model family of the
benchmark: 5x5 conv 32, 2x2 max-pool, 5x5 conv 64, 2x2 max-pool, FC 512,
FC to the classes, ReLU between, written from the description in
straightforward `jax.numpy` (`lax.conv_general_dilated` and `jnp.dot`)
at the precision the caller gives. It imports nothing of the program.

Every weight is trained, so the frozen part is empty. A row's partition
label is its class.

Operations per sample, in multiply-accumulates: conv1 H*W*k*k*C_in*c1,
conv2 (H/2)*(W/2)*k*k*c1*c2 (after one 2x2 pool), fc1 flat*fc, fc2
fc*classes, with flat = (H/4)*(W/4)*c2. A MAC is 2 FLOPs; a training
sample costs its forward pass and a backward pass of twice that
(gradients of activations and of weights), 6 FLOPs a MAC. Bias adds,
ReLU, pooling and the softmax are left out.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.data import DataMaker

ARCH_KEYS = {"input_hw", "in_channels", "n_classes", "conv_channels",
             "kernel", "fc_dim"}
SEQUENTIAL_LANES = False


def program_architecture(m) -> Dict:
    """The program's model (`ExperimentSpec.model_config()`, a
    `CNNConfig`) in the configuration file's keys."""
    return {"input_hw": list(m.input_hw), "in_channels": m.in_channels,
            "n_classes": m.n_classes, "conv_channels": list(m.conv_channels),
            "kernel": m.kernel, "fc_dim": m.fc_dim}


def data_maker(config: Dict) -> DataMaker:
    arch = config["architecture"]
    return DataMaker(arch["input_hw"], arch["in_channels"], arch["n_classes"],
                     config["data_seed"])


def labels(data) -> Tuple[np.ndarray, int]:
    """Each training row's partition label, and how many labels there are."""
    return np.asarray(data.y), int(data.n_classes)


# -- model -------------------------------------------------------------------

def init(arch: Dict, key) -> Tuple[Dict, Dict]:
    """(trained, frozen): He-normal weights and zero biases, drawn in the
    order conv1, conv2, fc1, fc2 from one split of `key`; nothing frozen."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    c1, c2 = arch["conv_channels"]
    k, cin = arch["kernel"], arch["in_channels"]
    h, w = arch["input_hw"]
    flat = (h // 4) * (w // 4) * c2

    def he(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) * (2.0 / fan_in) ** 0.5

    return {
        "conv1": {"w": he(k1, (k, k, cin, c1), k * k * cin),
                  "b": jnp.zeros((c1,), jnp.float32)},
        "conv2": {"w": he(k2, (k, k, c1, c2), k * k * c1),
                  "b": jnp.zeros((c2,), jnp.float32)},
        "fc1": {"w": he(k3, (flat, arch["fc_dim"]), flat),
                "b": jnp.zeros((arch["fc_dim"],), jnp.float32)},
        "fc2": {"w": he(k4, (arch["fc_dim"], arch["n_classes"]),
                        arch["fc_dim"]),
                "b": jnp.zeros((arch["n_classes"],), jnp.float32)},
    }, {}


def forward(p: Dict, x, precision):
    """Logits of images x (N, H, W, C)."""
    dn = ("NHWC", "HWIO", "NHWC")

    def conv(x, q):
        y = lax.conv_general_dilated(x, q["w"], (1, 1), "SAME",
                                     dimension_numbers=dn,
                                     precision=precision)
        return y + q["b"]

    def pool(x):
        return lax.reduce_window(x, -jnp.inf, lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    x = pool(jax.nn.relu(conv(x, p["conv1"])))
    x = pool(jax.nn.relu(conv(x, p["conv2"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, p["fc1"]["w"], precision=precision)
                    + p["fc1"]["b"])
    return jnp.dot(x, p["fc2"]["w"], precision=precision) + p["fc2"]["b"]


def loss(trained, frozen, x, y, precision):
    """Mean cross-entropy of labels y (N,)."""
    logp = jax.nn.log_softmax(forward(trained, x, precision), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def eval_hits(trained, frozen, x, y, precision):
    """(correct predictions, predictions) over test rows x."""
    return (jnp.sum(jnp.argmax(forward(trained, x, precision), -1) == y),
            y.shape[0])


# -- operations --------------------------------------------------------------

def forward_macs(arch: Dict) -> int:
    h, w = arch["input_hw"]
    k, cin = arch["kernel"], arch["in_channels"]
    c1, c2 = arch["conv_channels"]
    flat = (h // 4) * (w // 4) * c2
    return (h * w * k * k * cin * c1
            + (h // 2) * (w // 2) * k * k * c1 * c2
            + flat * arch["fc_dim"]
            + arch["fc_dim"] * arch["n_classes"])


def train_flops_per_sample(arch: Dict) -> int:
    return 6 * forward_macs(arch)


def eval_flops_per_sample(arch: Dict) -> int:
    return 2 * forward_macs(arch)
