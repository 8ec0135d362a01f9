"""jit'd public wrapper for the flash-attention kernel.

Handles GQA head grouping, (B, S, H, hd) <-> (BH, S, hd) reshapes, block
padding, and backend selection (interpret mode on CPU; compiled Pallas on
TPU). The backward pass falls back to the reference implementation via
custom_vjp (forward speed is what the serving/prefill path needs; training
uses the XLA path by default).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_bh(q, k, v, causal, window, block):
    block_q, block_k = block
    Sq = q.shape[1]
    pad_q = (-Sq) % block_q
    pad_k = (-k.shape[1]) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    out = flash_attention_kernel(
        qp, kp, vp, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret_mode())
    return out[:, :Sq]


def _flash_bh_fwd(q, k, v, causal, window, block):
    return _flash_bh(q, k, v, causal, window, block), (q, k, v)


def _flash_bh_bwd(causal, window, block, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_ref(q_, k_, v_, causal, window), q, k, v)
    return vjp(g)


_flash_bh.defvjp(_flash_bh_fwd, _flash_bh_bwd)


def flash_attention(
    q: jnp.ndarray,  # (B, Sq, H, hd)
    k: jnp.ndarray,  # (B, Sk, KV, hd)
    v: jnp.ndarray,  # (B, Sk, KV, hd)
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
) -> jnp.ndarray:
    """GQA flash attention. Returns (B, Sq, H, hd)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    rep = H // KV
    # (B, S, H, hd) -> (B*H, S, hd) with KV heads repeated per group.
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kt = jnp.repeat(k.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, -1, hd)
    vt = jnp.repeat(v.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, -1, hd)
    out = _flash_bh(qt, kt, vt, causal, window, (block_q, block_k))
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
