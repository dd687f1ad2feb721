"""Jitted public wrapper for the SSD scan kernel (pads S to the chunk grid)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_scan_kernel_call

__all__ = ["ssd_scan"]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, bmat, cmat, *, chunk: int = 128,
             interpret: bool | None = None):
    """Chunked SSD scan; pads the sequence with dt=0 steps (exact no-ops)."""
    s = x.shape[1]
    c = min(chunk, max(8, s))
    pad = (-s) % c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))  # dt=0 -> decay 1, no inject
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0)))
    y = ssd_scan_kernel_call(x, dt, a, bmat, cmat, chunk=c, interpret=interpret)
    return y[:, :s]
