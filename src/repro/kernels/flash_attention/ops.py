"""Jitted public wrapper for the flash attention kernel (pads to tile grid)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_kernel_call

__all__ = ["flash_attention"]


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Blockwise attention; q (B, Hq, Sq, Dh), k/v (B, Hkv, Skv, Dh)."""
    sq, skv, dh = q.shape[2], k.shape[2], q.shape[3]
    bq = min(block_q, max(8, sq))
    bk = min(block_k, max(8, skv))
    qp = _pad_to(q, 2, bq)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    out = flash_attention_kernel_call(
        qp, kp, vp, causal=causal, window=window, kv_len=skv,
        block_q=bq, block_k=bk, interpret=interpret)
    return out[:, :, :sq, :]
