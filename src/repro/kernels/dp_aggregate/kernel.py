"""Fused DP aggregation kernel (Pallas TPU) with optional in-kernel noise.

Server hot loop of Algorithms 1/2: given the raw (M, d) client-update matrix
(and optionally an (M, d) LDP noise matrix), produce in ONE pass over HBM:

    sum_released     (d,)  = sum_i clip(u_i) + n_i
    sum_sq_released  (1,1) = sum_i ||clip(u_i) + n_i||^2     (FedEXP numerator)
    sum_sq_clipped   (1,1) = sum_i ||clip(u_i)||^2           (CDP numerator)

The naive composition (norms pass, scale pass, reduce pass) reads the update
matrix three times; at fedsim scale (M=1000, d up to ~1e5) the op is purely
memory-bound, so the fusion is a ~3x bandwidth win on TPU.

Noise modes (DESIGN.md §8):
    "none"      CDP — no per-client noise.
    "operand"   LDP with a pre-materialized (M, d) noise matrix streamed in.
    "fused"     LDP with the Gaussian noise drawn INSIDE the kernel from a
                scalar-prefetched seed: on compiled TPU via the hardware PRNG
                (``pltpu.prng_seed`` + ``prng_random_bits``), in interpreter
                mode via an in-kernel Threefry-2x32 counter PRF (the same PRF
                family JAX's host RNG uses); both feed a Box-Muller transform.
                This removes the (M, d) noise write+read from HBM entirely —
                a further ~3x traffic cut over "operand" for the LDP round.

Scalars (clip threshold, noise sigma, seed, true M/d before padding) arrive
via scalar prefetch so traced values — e.g. the adaptive-clip threshold that
changes every round — do not force recompilation.

Tiling: grid over row blocks; each program holds a (block_m, d) tile in VMEM
(d padded to the 128-lane boundary by the wrapper, M padded to the row-block).
TPU grid execution is sequential, so outputs are accumulated across grid steps
with a first-step initialization guard — the standard Pallas reduction
pattern.  The column sum is computed as ``ones @ tile`` (MXU on TPU, BLAS in
interpreter mode) because plain axis-0 reduces are far off bandwidth on both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.interpret import resolve_interpret

__all__ = ["dp_aggregate_kernel_call", "ldp_noise_kernel_call"]

# stable names of the two kernels: the HLO instruction of each Mosaic call
# (``%dp_aggregate.N``) and so its device ops in a profiler trace
KERNEL_NAME = "dp_aggregate"
NOISE_KERNEL_NAME = "ldp_noise"

_EPS = 1e-12
_THREEFRY_C = 0x1BD11BDA     # Threefry key-schedule constant
_GOLDEN = 0x9E3779B9         # second key word for the in-kernel PRF


def _rotl(x, r: int):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def _threefry2x32(k0, k1, x0, x1):
    """Vectorized 20-round Threefry-2x32 block cipher (counter-mode PRF)."""
    rot = ((13, 15, 26, 6), (17, 29, 16, 24))
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(_THREEFRY_C))
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for j in range(1, 6):
        for r in rot[(j - 1) % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + ks[j % 3]
        x1 = x1 + ks[(j + 1) % 3] + jnp.uint32(j)
    return x0, x1


def _bits_to_unit(bits):
    """uint32 -> float32 uniform in the OPEN interval (0, 1) (top 24 bits).

    The shifted value is below 2**24, so the int32 bitcast is exact; Mosaic
    has no uint32 -> float32 conversion, only int32 -> float32.
    """
    top = jax.lax.bitcast_convert_type(bits >> jnp.uint32(8), jnp.int32)
    return (top.astype(jnp.float32) + 0.5) * jnp.float32(2.0**-24)


def _noise_block(seed, step, shape, *, tpu_prng: bool):
    """One (block_m, d) tile of standard Gaussian noise.

    ``seed`` is an int32 scalar; ``step`` the row-block index, mixed into the
    stream so every block draws independent noise.  Returns float32 N(0, 1).
    """
    if tpu_prng:
        pltpu.prng_seed(seed, step)
        b0 = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
        b1 = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    else:
        bm, d = shape
        lane = (jax.lax.broadcasted_iota(jnp.uint32, shape, 0) * jnp.uint32(d)
                + jax.lax.broadcasted_iota(jnp.uint32, shape, 1))
        k0 = jax.lax.bitcast_convert_type(seed, jnp.uint32)
        b0, b1 = _threefry2x32(k0, jnp.uint32(_GOLDEN), lane,
                               jnp.full(shape, step, jnp.uint32))
    # Box-Muller: two uniform streams -> one standard-normal tile.
    r = jnp.sqrt(-2.0 * jnp.log(_bits_to_unit(b0)))
    return r * jnp.cos(jnp.float32(2.0 * jnp.pi) * _bits_to_unit(b1))


def _kernel(meta_i_ref, meta_f_ref, u_ref, *refs,
            noise_mode: str, tpu_prng: bool):
    if noise_mode == "operand":
        n_ref, sum_ref, sq_rel_ref, sq_clip_ref = refs
    else:
        sum_ref, sq_rel_ref, sq_clip_ref = refs
    step = pl.program_id(0)
    clip_norm = meta_f_ref[0]
    sigma = meta_f_ref[1]
    seed = meta_i_ref[0]
    m_true = meta_i_ref[1]
    d_true = meta_i_ref[2]

    u = u_ref[...].astype(jnp.float32)                      # (bm, d)
    bm, d = u.shape
    sq_norms = jnp.sum(u * u, axis=1, keepdims=True)        # (bm, 1)
    scale = jnp.minimum(1.0, clip_norm / jnp.sqrt(jnp.maximum(sq_norms, _EPS)))
    clipped = u * scale
    # per-row quantities stay (bm, 1): Mosaic cannot relayout 1-D row vectors
    sq_clipped = sq_norms * scale ** 2                      # (bm, 1)

    if noise_mode == "operand":
        released = clipped + n_ref[...].astype(jnp.float32)
        sq_released = jnp.sum(released * released, axis=1, keepdims=True)
    elif noise_mode == "fused":
        # Padded rows/cols must draw ZERO noise: the wrapper pads u with
        # zeros, which clip to zero, but generated noise would otherwise
        # leak into the sums.
        rows = jax.lax.broadcasted_iota(jnp.int32, (bm, d), 0) + step * bm
        cols = jax.lax.broadcasted_iota(jnp.int32, (bm, d), 1)
        valid = (rows < m_true) & (cols < d_true)
        noise = jnp.where(valid, sigma * _noise_block(seed, step, (bm, d),
                                                      tpu_prng=tpu_prng), 0.0)
        released = clipped + noise
        sq_released = jnp.sum(released * released, axis=1, keepdims=True)
    else:
        released = clipped
        sq_released = sq_clipped

    ones = jnp.ones((1, bm), jnp.float32)
    # HIGHEST: at default precision the TPU MXU multiplies f32 in bf16
    part_sum = jax.lax.dot_general(                         # (1, d) column sum
        ones, released, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    part_sq_rel = jnp.sum(sq_released, keepdims=True)       # (1, 1)
    part_sq_clip = jnp.sum(sq_clipped, keepdims=True)

    @pl.when(step == 0)
    def _init():
        sum_ref[...] = part_sum
        sq_rel_ref[...] = part_sq_rel
        sq_clip_ref[...] = part_sq_clip

    @pl.when(step != 0)
    def _accum():
        sum_ref[...] += part_sum
        sq_rel_ref[...] += part_sq_rel
        sq_clip_ref[...] += part_sq_clip


def dp_aggregate_kernel_call(
    updates: jax.Array,
    noise: jax.Array | None,
    clip_norm,
    *,
    noise_sigma=None,
    noise_seed=None,
    m_true: int | None = None,
    d_true: int | None = None,
    block_m: int = 8,
    interpret: bool | None = None,
):
    """Invoke the fused kernel.  Expects M % block_m == 0 and d % 128 == 0
    (the ops.py wrapper pads).  ``noise_seed`` (int32 scalar) switches on
    in-kernel noise generation of std ``noise_sigma``; a materialized
    ``noise`` operand is streamed instead when given.  Returns
    (sum_released, sum_sq_released, sum_sq_clipped)."""
    m, d = updates.shape
    assert m % block_m == 0, (m, block_m)
    interpret = resolve_interpret(interpret)
    if noise is not None and noise_seed is not None:
        raise ValueError("materialized noise and in-kernel noise are exclusive")
    noise_mode = "operand" if noise is not None else (
        "fused" if noise_seed is not None else "none")

    meta_i = jnp.stack([
        jnp.asarray(noise_seed if noise_seed is not None else 0, jnp.int32),
        jnp.asarray(m_true if m_true is not None else m, jnp.int32),
        jnp.asarray(d_true if d_true is not None else d, jnp.int32),
    ])
    meta_f = jnp.stack([
        jnp.asarray(clip_norm, jnp.float32),
        jnp.asarray(noise_sigma if noise_sigma is not None else 0.0, jnp.float32),
    ])

    in_specs = [pl.BlockSpec((block_m, d), lambda i, *_: (i, 0))]
    operands = [updates]
    if noise_mode == "operand":
        in_specs.append(pl.BlockSpec((block_m, d), lambda i, *_: (i, 0)))
        operands.append(noise)

    kernel = functools.partial(_kernel, noise_mode=noise_mode,
                               tpu_prng=not interpret)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m // block_m,),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, d), lambda i, *_: (0, 0)),
                pl.BlockSpec((1, 1), lambda i, *_: (0, 0)),
                pl.BlockSpec((1, 1), lambda i, *_: (0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_NAME,
    )(meta_i, meta_f, *operands)
    sum_released, sq_rel, sq_clip = out
    return sum_released[0], sq_rel[0, 0], sq_clip[0, 0]


def _noise_only_kernel(meta_i_ref, meta_f_ref, out_ref, *, tpu_prng: bool):
    step = pl.program_id(0)
    bm, d = out_ref.shape
    sigma = meta_f_ref[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, d), 0) + step * bm
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, d), 1)
    valid = (rows < meta_i_ref[1]) & (cols < meta_i_ref[2])
    z = _noise_block(meta_i_ref[0], step, (bm, d), tpu_prng=tpu_prng)
    out_ref[...] = jnp.where(valid, sigma * z, 0.0)


def ldp_noise_kernel_call(
    m: int,
    d: int,
    noise_seed,
    noise_sigma,
    *,
    block_m: int = 8,
    interpret: bool | None = None,
):
    """Materialize the exact noise the fused kernel would draw (test oracle;
    shapes must already satisfy the kernel tiling contract)."""
    assert m % block_m == 0, (m, block_m)
    interpret = resolve_interpret(interpret)
    meta_i = jnp.stack([jnp.asarray(noise_seed, jnp.int32),
                        jnp.asarray(m, jnp.int32), jnp.asarray(d, jnp.int32)])
    meta_f = jnp.asarray(noise_sigma, jnp.float32)[None]
    kernel = functools.partial(_noise_only_kernel, tpu_prng=not interpret)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m // block_m,),
            in_specs=[],
            out_specs=pl.BlockSpec((block_m, d), lambda i, *_: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        interpret=interpret,
        name=NOISE_KERNEL_NAME,
    )(meta_i, meta_f)
