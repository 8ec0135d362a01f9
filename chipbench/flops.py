"""Bytes of the cells' kernels, counted from shapes, and the chips'
published peaks. A model family's operations are counted in its own
module (chipbench/families/<family>.py).

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
