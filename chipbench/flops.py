"""Operations and bytes of the cells' work, counted from shapes.

The FedAvg CNN's forward pass per sample, in multiply-accumulates:
conv1 H*W*k*k*C_in*c1, conv2 (H/2)*(W/2)*k*k*c1*c2 (after one 2x2 pool),
fc1 flat*fc, fc2 fc*classes, with flat = (H/4)*(W/4)*c2. A MAC is 2
FLOPs; a training sample costs its forward pass and a backward pass of
twice that (gradients of activations and of weights), 6 FLOPs a MAC.
Bias adds, ReLU, pooling and the softmax are left out.

A kernel's bytes are its arguments' and outputs' bytes, from the shapes
and dtypes written in the operation's own HLO text in the trace. On the
chip the compiler may stage an argument in another memory space before
the kernel runs (its layout then carries `S(n)`, n >= 1): those bytes do
not cross HBM while the kernel runs, so `hbm_bytes` counts only what
lives in HBM.
"""
from __future__ import annotations

import json
import math
import re
from pathlib import Path
from typing import Dict

PEAKS = Path(__file__).resolve().parent / "peaks.json"


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


DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
               "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\](\{[^}]*\})?")


def hbm_bytes(hlo: str) -> int:
    """Bytes of an HLO instruction's outputs and operands that live in
    HBM, from the shapes in its text ("%op = (s8[2,8]{...}, ...)
    custom-call(f32[2,8]{...S(1)} %a, ...), ..."): a shape whose layout
    names a memory space S(n), n >= 1, is left out."""
    head, _, rest = hlo.partition(" = ")
    body = rest.split("), ", 1)[0] if "), " in rest else rest
    total = 0
    for dtype, dims, layout in _SHAPE.findall(body):
        if dtype not in DTYPE_BYTES:
            continue
        if layout and re.search(r"S\([1-9]\d*\)", layout):
            continue
        n = math.prod(int(d) for d in dims.split(",") if d) if dims else 1
        total += n * DTYPE_BYTES[dtype]
    return total


def peaks(device_kind: str, path: Path = PEAKS) -> Dict[str, float]:
    """The published peaks of `device_kind`; an unknown kind is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    try:
        return table[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}") from None
