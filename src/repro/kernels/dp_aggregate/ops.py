"""Jitted public wrapper for the fused DP aggregation kernel.

Pads (M, d) to the kernel's tiling contract, invokes the Pallas kernel (or the
jnp oracle on request) and converts raw sums into the ``RoundStats`` consumed
by the step-size rules.  The clip threshold, noise sigma, and noise seed are
traced operands (scalar-prefetched by the kernel), so per-round values — e.g.
the adaptive-clip threshold — do not trigger recompilation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.aggregation import RoundStats
from repro.kernels.dp_aggregate.kernel import (
    dp_aggregate_kernel_call,
    ldp_noise_kernel_call,
)
from repro.kernels.dp_aggregate.ref import dp_aggregate_ref
from repro.kernels.interpret import resolve_interpret

__all__ = ["dp_aggregate", "dp_aggregate_sums", "dp_aggregate_sums_chunked",
           "generate_ldp_noise", "pick_block_m"]

# VMEM budget per input tile on TPU (bytes); conservative vs the ~16 MB arena
# since the kernel holds the tile plus a handful of same-shape temporaries.
_TPU_TILE_BYTES = 2 * 1024 * 1024
_INTERPRET_MAX_BLOCK_M = 2048


def pick_block_m(m: int, d_padded: int, interpret: bool) -> int:
    """Shape-based row-block heuristic (replaces the old hardcoded 8).

    Interpreter mode: one grid step when feasible — each extra step is an
    extra python-traced block copy, and there is no VMEM to respect.
    Compiled TPU: the largest multiple of 8 whose f32 tile fits the VMEM
    budget, clamped to [8, 1024].
    """
    m8 = -(-m // 8) * 8
    if interpret:
        if m8 <= _INTERPRET_MAX_BLOCK_M:
            return m8
        # split into the fewest blocks under the cap and size them evenly, so
        # row padding stays < 8 * nblocks (a naive cap of 2048 would pad
        # M=2100 all the way to 4096)
        nblocks = -(-m8 // _INTERPRET_MAX_BLOCK_M)
        per_block = -(-m8 // nblocks)
        return -(-per_block // 8) * 8
    rows = _TPU_TILE_BYTES // (4 * d_padded)
    return max(8, min(1024, (rows // 8) * 8, m8))


def _resolve_defaults(m: int, d: int, interpret: bool | None,
                      block_m: int | None) -> tuple[bool, int]:
    """One home for the backend/tiling defaults every entry point shares."""
    interpret = resolve_interpret(interpret)
    if block_m is None:
        block_m = pick_block_m(m, -(-d // 128) * 128, interpret)
    return interpret, block_m


def _pad_axis(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _key_to_seed(key: jax.Array) -> jax.Array:
    """Fold a JAX PRNG key (typed or raw uint32 pair) to one int32 scalar."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    mixed = key.reshape(-1)[0] ^ key.reshape(-1)[-1]
    return jax.lax.bitcast_convert_type(mixed.astype(jnp.uint32), jnp.int32)


@functools.partial(jax.jit, static_argnames=("use_ref", "interpret", "block_m", "fused"))
def _impl(updates, noise, clip_norm, sigma, seed, use_ref, interpret, block_m, fused):
    m, d = updates.shape
    if use_ref:
        s, sq_rel, sq_clip = dp_aggregate_ref(updates, noise, clip_norm)
    else:
        u = _pad_axis(_pad_axis(updates, 1, 128), 0, block_m)
        n = None if noise is None else _pad_axis(_pad_axis(noise, 1, 128), 0, block_m)
        s, sq_rel, sq_clip = dp_aggregate_kernel_call(
            u, n, clip_norm,
            noise_sigma=sigma if fused else None,
            noise_seed=seed if fused else None,
            m_true=m, d_true=d,
            block_m=block_m, interpret=interpret)
        s = s[:d]
    # raw SUMS, not means: the client-sharded engine psums these across the
    # `clients` mesh axis before normalizing (dp_aggregate divides below)
    return s, sq_rel, sq_clip


def dp_aggregate(
    updates: jax.Array,
    clip_norm,
    noise: jax.Array | None = None,
    *,
    noise_key: jax.Array | None = None,
    noise_sigma=None,
    use_ref: bool = False,
    interpret: bool | None = None,
    block_m: int | None = None,
) -> RoundStats:
    """Fused clip(+noise)+aggregate returning FedEXP round statistics.

    Pass a materialized ``noise`` matrix OR (``noise_key``, ``noise_sigma``)
    to draw the Gaussian noise inside the kernel (fused-noise path).
    """
    interpret, block_m = _resolve_defaults(*updates.shape, interpret, block_m)
    fused = noise_key is not None
    if fused and noise_sigma is None:
        raise ValueError("`noise_key` requires `noise_sigma` (sigma=0 would "
                         "silently release un-noised updates)")
    if fused and use_ref:
        raise ValueError("in-kernel noise has no jnp reference path; "
                         "materialize the noise for use_ref=True")
    seed = _key_to_seed(noise_key) if fused else jnp.int32(0)
    sigma = jnp.asarray(noise_sigma if noise_sigma is not None else 0.0, jnp.float32)
    s, sq_rel, sq_clip = _impl(
        updates, noise, jnp.asarray(clip_norm, jnp.float32), sigma, seed,
        use_ref, interpret, block_m, fused)
    m = updates.shape[0]
    cbar = s / m
    return RoundStats(
        cbar=cbar,
        mean_sq=sq_rel / m,
        agg_sq=jnp.sum(jnp.square(cbar)),
        mean_sq_clipped=sq_clip / m,
    )


def dp_aggregate_sums(
    updates: jax.Array,
    clip_norm,
    noise: jax.Array | None = None,
    *,
    use_ref: bool = False,
    interpret: bool | None = None,
    block_m: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Partial-sum entry point: ``(sum_c, sum_sq_released, sum_sq_clipped)``.

    The same fused clip(+noise)+reduce kernel as ``dp_aggregate``, but the raw
    per-shard SUMS are returned un-normalized so the client-sharded engine can
    ``psum`` them across the ``clients`` mesh axis and divide once globally
    (DESIGN.md §9).  In-kernel noise generation is not offered here: the
    kernel's seed derivation has no notion of a shard offset, so every shard
    would draw identical noise — materialize per-client rows instead
    (``repro.core.aggregation.materialize_ldp_noise``).
    """
    interpret, block_m = _resolve_defaults(*updates.shape, interpret, block_m)
    return _impl(updates, noise, jnp.asarray(clip_norm, jnp.float32),
                 jnp.float32(0.0), jnp.int32(0), use_ref, interpret,
                 block_m, False)


def dp_aggregate_sums_chunked(
    updates: jax.Array,
    clip_norm,
    noise: jax.Array | None = None,
    *,
    chunk_m: int,
    slots: jax.Array | None = None,
    slot_mask: jax.Array | None = None,
    use_ref: bool = False,
    interpret: bool | None = None,
    block_m: int | None = None,
    compress_fn=None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``dp_aggregate_sums`` accumulated over row chunks (DESIGN.md §12/§14).

    Reduces the (M, d) update matrix ``chunk_m`` rows at a time — one kernel
    launch per chunk inside a ``lax.scan`` — and adds the three partial sums
    into an O(d) carry.  The kernel's working set (its padded input copy and
    VMEM tiles) is bounded by ``chunk_m * d`` instead of ``M * d``, which is
    what the streaming cohort engine needs from the kernel layer when a
    round's cohort is too large to stage densely.  In-kernel noise
    generation is excluded exactly as in ``dp_aggregate_sums``: the kernel
    seed derivation is chunk-oblivious, so every chunk would repeat the same
    noise block — materialize per-client rows keyed by global index instead
    (``repro.core.aggregation.materialize_ldp_noise``).

    ``slots`` is the §14 sparse-cohort entry: a (cap,) slot table (as packed
    by ``fedsim.local.gather_slots``) restricts the reduction to the sampled
    rows, gathered from ``updates`` one chunk at a time right before its
    kernel launch — never a dense (cap, d) staging block — so a q-sampled
    round's kernel work is O(cap·d).  Padding slots hold index 0 (client 0's
    real row), so the accompanying ``slot_mask`` where-zeroes each gathered
    chunk before the kernel sees it — the engines' ``mask_rows`` discipline,
    applied here because only this layer ever materializes the gathered rows.
    With ``slots``, ``noise`` must already be slot-aligned ((cap, d),
    materialized for the GATHERED global indices, zero rows on padding
    slots).

    Args:
      updates: (M, d) raw client updates; M (or ``cap`` when ``slots`` is
        given) must be a multiple of ``chunk_m`` (the engine's chunk/slot
        grids guarantee this — pad with zero-weight rows otherwise).
      clip_norm: clip threshold C (python float or traced scalar).
      noise: optional pre-materialized per-client noise — (M, d), or (cap, d)
        slot-aligned when ``slots`` is given.
      chunk_m: rows per kernel launch (>= 1).
      slots: optional (cap,) int32 slot table of sampled-row indices.
      slot_mask: (cap,) float {0., 1.} validity of each slot; required with
        ``slots`` (without it a padding slot would double-count client 0).
      use_ref / interpret / block_m: forwarded to each chunk's reduction.
      compress_fn: optional linear per-row map ``(chunk_m, d) -> (chunk_m,
        kc)`` (DESIGN.md §16).  Each chunk's rows are clipped then compressed
        before summation, so the carry holds a (kc,) vector instead of (d,);
        linearity of the map makes the chunked sum equal the dense compressed
        sum.  Incompatible with per-row ``noise`` — LDP noise lives in R^d and
        compressing a noised row breaks its privacy accounting.

    Returns:
      ``(sum_c, sum_sq_released, sum_sq_clipped)`` raw SUMS over the reduced
      rows — the dense entry's values re-associated at chunk boundaries only.
      With ``compress_fn``, ``sum_c`` is the (kc,) compressed-domain sum and
      released == clipped (no per-row noise enters the compressed path).
    """
    if compress_fn is not None and noise is not None:
        raise ValueError(
            "compress_fn cannot combine with per-row noise: LDP noise is a "
            "full R^d vector per client, drawn BEFORE aggregation — "
            "compressing it afterwards breaks the privacy accounting "
            "(DESIGN.md §16)")
    m, d = updates.shape
    rows = m if slots is None else slots.shape[0]
    if chunk_m < 1:
        raise ValueError(f"chunk_m must be >= 1, got {chunk_m}")
    chunk_m = min(chunk_m, rows)
    if rows % chunk_m:
        what = "M" if slots is None else "cap"
        raise ValueError(
            f"{what}={rows} is not a multiple of chunk_m={chunk_m}; pad the "
            "cohort to the chunk grid first (zero-weight rows contribute "
            "nothing)")
    n_chunks = rows // chunk_m
    interpret, block_m = _resolve_defaults(chunk_m, d, interpret, block_m)
    clip = jnp.asarray(clip_norm, jnp.float32)

    if slots is None:
        xs = {"u": updates.reshape(n_chunks, chunk_m, d)}
        if noise is not None:
            xs["noise"] = noise.reshape(n_chunks, chunk_m, d)
    else:
        if slot_mask is None:
            raise ValueError(
                "slots requires slot_mask (padding slots hold index 0; an "
                "unmasked gather would double-count client 0's update)")
        xs = {"slots": slots.reshape(n_chunks, chunk_m),
              "mask": slot_mask.reshape(n_chunks, chunk_m)}
        if noise is not None:
            if noise.shape[0] != rows:
                raise ValueError(
                    f"with slots, noise must be slot-aligned: expected "
                    f"({rows}, {d}), got {noise.shape} — materialize it for "
                    "the gathered global indices, not the full cohort")
            xs["noise"] = noise.reshape(n_chunks, chunk_m, d)

    def body(acc, chunk):
        if slots is None:
            u = chunk["u"]
        else:
            u = jnp.take(updates, chunk["slots"], axis=0)
            u = jnp.where(chunk["mask"][:, None] > 0, u, 0.0)
        if compress_fn is not None:
            # clip scale commutes with the linear map, so the compressed sum
            # never materializes the clipped (chunk_m, d) block
            sq = jnp.sum(jnp.square(u), axis=-1)
            scale = jnp.minimum(1.0, clip / jnp.maximum(jnp.sqrt(sq), 1e-12))
            s = jnp.sum(compress_fn(u) * scale[:, None], axis=0)
            sq_clip = jnp.sum(sq * jnp.square(scale))
            sq_rel = sq_clip
        else:
            s, sq_rel, sq_clip = _impl(
                u, chunk.get("noise"), clip, jnp.float32(0.0),
                jnp.int32(0), use_ref, interpret, block_m, False)
        a_s, a_rel, a_clip = acc
        return (a_s + s, a_rel + sq_rel, a_clip + sq_clip), None

    if compress_fn is None:
        sum_c_zero = jnp.zeros((d,), jnp.float32)
    else:
        kc = jax.eval_shape(
            compress_fn,
            jax.ShapeDtypeStruct((chunk_m, d), jnp.float32)).shape[-1]
        sum_c_zero = jnp.zeros((kc,), jnp.float32)
    zero = (sum_c_zero, jnp.float32(0.0), jnp.float32(0.0))
    (s, sq_rel, sq_clip), _ = jax.lax.scan(body, zero, xs)
    return s, sq_rel, sq_clip


def generate_ldp_noise(
    m: int,
    d: int,
    noise_key: jax.Array,
    noise_sigma,
    *,
    interpret: bool | None = None,
    block_m: int | None = None,
) -> jax.Array:
    """Materialize the (m, d) Gaussian noise the fused kernel draws in-kernel
    for ``noise_key`` — the test oracle for the in-kernel PRNG statistics."""
    interpret, block_m = _resolve_defaults(m, d, interpret, block_m)
    d_padded = -(-d // 128) * 128
    m_padded = -(-m // block_m) * block_m
    full = ldp_noise_kernel_call(
        m_padded, d_padded, _key_to_seed(noise_key), noise_sigma,
        block_m=block_m, interpret=interpret)
    return full[:m, :d]
