"""Blockwise (flash) attention kernel for TPU (Pallas).

Online-softmax attention with causal and sliding-window masking and GQA/MQA
head sharing. This is the compute hot-spot of every attention architecture in
the assigned pool; on TPU the kernel holds a (block_q x head_dim) accumulator
plus running max/denominator in VMEM scratch while streaming (block_k x
head_dim) K/V tiles from HBM, so the S x S score matrix is never materialized.

Tiling: grid = (batch*q_heads, S_q/block_q, S_kv/block_k) with the k-block
axis innermost (TPU grids execute sequentially in row-major order, which is
what makes the scratch carry correct). Blocks outside the causal/window band
are skipped via pl.when (a production variant would shrink the grid; masking
keeps the kernel simple and the skipped-block cost is loads only).

MXU alignment: block_q/block_k default to 128 and head_dim is padded to a
multiple of 128 by the wrapper.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.interpret import resolve_interpret

__all__ = ["flash_attention_kernel_call"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int | None,
            kv_len: int, block_q: int, block_k: int, num_kb: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    # Band check: is any (q, k) pair in this block-pair visible?
    needed = k_start < kv_len
    if causal:
        needed &= k_start <= q_start + block_q - 1
    if window is not None:
        needed &= k_start + block_k - 1 >= q_start - window + 1

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale              # (bq, dh)
        k = k_ref[0].astype(jnp.float32)                      # (bk, dh)
        v = v_ref[0].astype(jnp.float32)                      # (bk, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

        q_idx = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_idx = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_idx < kv_len
        if causal:
            mask &= k_idx <= q_idx
        if window is not None:
            mask &= k_idx > q_idx - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]                                   # (bq, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot(p, v)
        m_ref[...] = m_cur

    @pl.when(ik == num_kb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_kernel_call(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True,
    window: int | None = None,
    kv_len: int,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """q: (B, Hq, Sq, Dh); k, v: (B, Hkv, Skv, Dh), Sq/Skv multiples of blocks.

    Returns (B, Hq, Sq, Dh).  ``kv_len`` is the un-padded KV length (padding
    columns are masked inside the kernel).
    """
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    num_qb, num_kb = sq // block_q, skv // block_k

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        kv_len=kv_len, block_q=block_q, block_k=block_k, num_kb=num_kb)

    grid = (b * hq, num_qb, num_kb)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, dh), lambda bh, iq, ik, g=group, h=hq: (bh // h * (h // g) + (bh % h) // g, ik, 0)),
            pl.BlockSpec((1, block_k, dh), lambda bh, iq, ik, g=group, h=hq: (bh // h * (h // g) + (bh % h) // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q.reshape(b * hq, sq, dh), k.reshape(b * hkv, skv, dh), v.reshape(b * hkv, skv, dh))
    return out.reshape(b, hq, sq, dh)
