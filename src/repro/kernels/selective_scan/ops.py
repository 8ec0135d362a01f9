"""jit'd public wrapper for the selective-scan kernel: block-size choice,
d_inner padding, h0 fast-path, and interpret-mode selection on CPU."""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.selective_scan.kernel import selective_scan_kernel
from repro.kernels.selective_scan.ref import selective_scan_ref


def _pick_block_d(Dm: int) -> int:
    for bd in (512, 256, 128):
        if Dm % bd == 0:
            return bd
    return Dm


def selective_scan(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A: jnp.ndarray,
    B: jnp.ndarray,
    C: jnp.ndarray,
    D: jnp.ndarray,
    chunk: int = 128,
    h0: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Same contract as ref.selective_scan_ref."""
    if h0 is not None:
        # Kernel carries state from zeros; nonzero h0 (rare: chunked prefill
        # resume) falls back to the reference path.
        return selective_scan_ref(x, dt, A, B, C, D, chunk=chunk, h0=h0)
    Bsz, S, Dm = x.shape
    L = min(chunk, S)
    pad_s = (-S) % L
    if pad_s:
        x, dt = (jnp.pad(t, ((0, 0), (0, pad_s), (0, 0))) for t in (x, dt))
        B, C = (jnp.pad(t, ((0, 0), (0, pad_s), (0, 0))) for t in (B, C))
    y, h = selective_scan_kernel(
        x, dt, A, B, C, D, chunk=L, block_d=_pick_block_d(Dm),
        interpret=interpret_mode())
    return y[:, :S], h
