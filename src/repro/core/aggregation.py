"""Server-side aggregation of client updates + the FedEXP round statistics.

The server consumes the (possibly randomized) client updates ``c_i`` and needs
exactly three reductions per round (Algorithms 1 & 2):

    cbar      = (1/M) sum_i c_i                  -- the pseudo-gradient
    mean_sq   = (1/M) sum_i ||c_i||^2            -- FedEXP numerator statistic
    agg_sq    = ||cbar||^2                       -- FedEXP denominator

``aggregate_stats`` is the jnp reference; ``fused_clip_aggregate`` performs
clip -> (optional noise) -> the three reductions and routes between backends
(see DESIGN.md §5 and §8):

    "jnp"          one elementwise pass + BLAS reductions.  The column sum is
                   expressed as ``ones @ u`` because XLA:CPU's strided
                   axis-0 reduce runs ~15x below memcpy bandwidth while the
                   BLAS matvec saturates it; the per-row square norms use the
                   contiguous axis-1 reduce.  This is the cross-backend
                   fallback and the oracle for the kernel tests.
    "kernel"       the fused Pallas ``dp_aggregate`` kernel (one pass over
                   HBM; compiled on TPU, interpret elsewhere), with the
                   LDP noise matrix materialized by the caller or from
                   ``noise_key``.
    "kernel-fused" the same kernel drawing the Gaussian noise *inside* the
                   kernel (per-block PRNG, DESIGN.md §8), eliminating the
                   (M, d) noise write+read from HBM entirely.
    "auto"         kernel-fused (when noise is requested) or kernel on TPU;
                   the tuned jnp path on CPU/GPU, where interpret-mode Pallas
                   cannot beat BLAS.

Moment-based API (DESIGN.md §9).  The three reductions above are exact sums
over clients, so they decompose over any partition of the cohort:
``partial_clip_moments`` computes one shard's *partial sums* (Σ c_i,
Σ ||c_i||^2, Σ ||Delta_i||^2, Σ mask_i), which the client-sharded engine
``psum``s across the ``clients`` mesh axis before ``RoundMoments.stats``
normalizes them into the same ``RoundStats`` the step-size rules consume.
A ``weight_mask`` row weight (0.0 for padding clients when M % n_shards != 0)
keeps padded rows out of every sum, including the client count.

Streaming (DESIGN.md §12).  The same additivity lets the reductions run in
ROW CHUNKS: ``streamed_clip_moments`` accumulates per-chunk
``partial_clip_moments`` in a ``lax.scan`` carry, bounding the working set
by the chunk size — the in-core form of the decomposition the streaming
cohort engine applies one level higher (per-chunk local training, so the
full (M, d) matrix never materializes at all).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

__all__ = [
    "RoundStats",
    "RoundMoments",
    "aggregate_stats",
    "sum_dot",
    "fused_clip_aggregate",
    "partial_clip_moments",
    "streamed_clip_moments",
    "raw_moments",
    "global_client_indices",
    "materialize_ldp_noise",
    "resolve_backend",
]

_EPS = 1e-12


def sum_dot(v: jax.Array, x: jax.Array) -> jax.Array:
    """``v @ x`` at full f32 precision: a weighted sum over rows.

    Exact sums over clients are written as matvecs (``ones @ u``, ``mask @
    x``) because XLA:CPU's BLAS matvec is fast and matches the reference
    reduction order bit-for-bit.  At default precision the TPU's MXU
    multiplies f32 in bf16, a 2**-9 relative error that swamps Eq. 6's
    ``mean_sq - d * sigma**2``; ``HIGHEST`` keeps f32, and is a no-op on CPU.
    """
    return jnp.matmul(v, x, precision=jax.lax.Precision.HIGHEST)


def global_client_indices(start, m: int) -> jax.Array:
    """(m,) GLOBAL client indices for a block of m cohort rows.

    Every per-client randomness derivation (LDP noise rows, randomizer keys,
    local-training shuffles) keys by global client index so that any
    partition of the cohort — shards, stream chunks, or a sparse gathered
    block — reproduces the dense single-device draw bit-for-bit.  ``start``
    is either the scalar global index of row 0 (contiguous shard/chunk
    slices: indices are ``start + arange(m)``) or already a (m,) vector of
    global indices (the §14 sparse-gather path, where row j holds client
    ``slots[j]``), which passes through unchanged.
    """
    if getattr(start, "ndim", 0) == 1:
        return start
    return start + jnp.arange(m)


@dataclasses.dataclass
class RoundStats:
    """Aggregate statistics of one federated round (all scalars but cbar)."""

    cbar: jax.Array           # (d,) mean of released updates
    mean_sq: jax.Array        # scalar, mean_i ||c_i||^2
    agg_sq: jax.Array         # scalar, ||cbar||^2
    mean_sq_clipped: jax.Array | None = None  # mean_i ||Delta_i||^2 (pre-noise; CDP only)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RoundMoments:
    """Per-shard partial sums of one round's release — a psum-able pytree.

    Every field is a SUM over the shard's (mask-weighted) clients, never a
    mean, so moments from different shards combine by addition alone:
    ``psum(local_moments, 'clients')`` is the global moments.
    """

    sum_c: jax.Array           # (d,) sum of released updates
    sum_sq: jax.Array          # scalar, sum_i ||c_i||^2 (post-noise)
    sum_sq_clipped: jax.Array  # scalar, sum_i ||clip(Delta_i)||^2 (pre-noise)
    count: jax.Array           # scalar, sum of row weights (true client count)

    def stats(self) -> RoundStats:
        """Normalize global sums into the RoundStats the stepsize rules eat."""
        return RoundStats(
            cbar=self.sum_c / self.count,
            mean_sq=self.sum_sq / self.count,
            agg_sq=jnp.sum(jnp.square(self.sum_c / self.count)),
            mean_sq_clipped=self.sum_sq_clipped / self.count,
        )


def materialize_ldp_noise(noise_key: jax.Array, m: int, d: int, sigma,
                          dtype=jnp.float32, *, start: int | jax.Array = 0) -> jax.Array:
    """(m, d) per-client LDP Gaussian noise, row i drawn from
    ``fold_in(noise_key, start + i)``.

    Keying rows by GLOBAL client index (not by one (M, d) tensor draw) is what
    lets a client shard materialize exactly its own rows of the cohort noise:
    shard s passes ``start = s * m_local`` and reproduces rows [start, start+m)
    of the single-device matrix bit-for-bit.  Mathematically this is clients
    randomizing locally with independent keys — the form in which the LDP
    guarantee is stated.  ``start`` may also be a (m,) vector of global
    indices (the sparse-gather path, DESIGN.md §14): row j then draws client
    ``start[j]``'s noise.
    """
    idx = global_client_indices(start, m)
    keys = jax.vmap(lambda i: jax.random.fold_in(noise_key, i))(idx)
    rows = jax.vmap(lambda k: jax.random.normal(k, (d,), dtype))(keys)
    return (sigma * rows).astype(dtype)


def _colmean(updates: jax.Array) -> jax.Array:
    """Column mean via matvec: XLA:CPU's axis-0 reduce is ~15x slower."""
    m = updates.shape[0]
    ones = jnp.ones((m,), jnp.float32)
    return sum_dot(ones, updates) / m


def aggregate_stats(updates: jax.Array) -> RoundStats:
    """Reference reductions over an ``(M, d)`` matrix of released updates.

    Means are written ``sum / m`` (NOT ``jnp.mean``, which lowers to a
    reciprocal-multiply one ULP away) so they are bit-identical to the
    moment path's psummed-sums-then-divide normalization.
    """
    m = updates.shape[0]
    cbar = _colmean(updates)
    mean_sq = jnp.sum(jnp.sum(jnp.square(updates), axis=-1)) / m
    agg_sq = jnp.sum(jnp.square(cbar))
    return RoundStats(cbar=cbar, mean_sq=mean_sq, agg_sq=agg_sq)


def resolve_backend(backend: str | None, *, wants_noise_gen: bool = False) -> str:
    """Map "auto"/None to a concrete backend for the current JAX platform."""
    if backend in (None, "auto"):
        if jax.default_backend() == "tpu":
            return "kernel-fused" if wants_noise_gen else "kernel"
        return "jnp"
    return backend


def fused_clip_aggregate(
    raw_updates: jax.Array,
    clip_norm,
    noise: jax.Array | None = None,
    *,
    noise_key: jax.Array | None = None,
    noise_sigma=None,
    backend: str = "auto",
    use_kernel: bool = False,
    interpret: bool | None = None,
    block_m: int | None = None,
) -> RoundStats:
    """Clip rows to L2 <= C, optionally add per-client noise, and reduce.

    Args:
      raw_updates: (M, d) raw client updates.
      clip_norm: clipping threshold C (python float or traced scalar).
      noise: optional pre-materialized (M, d) noise matrix (LDP Gaussian);
        None for CDP (noise is added to the *mean* by the caller, which needs
        ``mean_sq_clipped``).
      noise_key: PRNG key for LDP Gaussian noise of std ``noise_sigma``;
        the backend decides whether to materialize it (jnp / kernel) or draw
        it inside the kernel (kernel-fused).  Mutually exclusive with
        ``noise``.
      noise_sigma: noise std (python float or traced scalar), with noise_key.
      backend: "auto" | "jnp" | "kernel" | "kernel-fused" (see module doc).
      use_kernel: legacy alias for backend="kernel".
      interpret: run the Pallas kernel in interpreter mode; None = auto
        (interpret everywhere but TPU).
      block_m: kernel row-block size; None = shape-based heuristic.

    Returns RoundStats where ``mean_sq`` is computed on the *released* c_i
    (post-noise if noise given) and ``mean_sq_clipped`` on the clipped
    deltas (pre-noise).
    """
    if noise is not None and noise_key is not None:
        raise ValueError("pass either a materialized `noise` or `noise_key`, not both")
    if noise_key is not None and noise_sigma is None:
        # without this, the kernel-fused path would default sigma to 0 and
        # silently release UN-noised updates — a privacy-guarantee violation
        raise ValueError("`noise_key` requires `noise_sigma`")
    wants_noise_gen = noise_key is not None
    if use_kernel and backend == "auto":
        backend = "kernel"
    backend = resolve_backend(backend, wants_noise_gen=wants_noise_gen)

    if backend in ("kernel", "kernel-fused"):
        from repro.kernels.dp_aggregate import ops as _ops

        if backend == "kernel" and wants_noise_gen:
            noise = materialize_ldp_noise(noise_key, *raw_updates.shape,
                                          noise_sigma, raw_updates.dtype)
            noise_key = None
        return _ops.dp_aggregate(
            raw_updates, clip_norm, noise,
            noise_key=noise_key if backend == "kernel-fused" else None,
            noise_sigma=noise_sigma if backend == "kernel-fused" else None,
            interpret=interpret, block_m=block_m)

    if backend != "jnp":
        raise ValueError(f"unknown aggregation backend {backend!r}")

    if wants_noise_gen:
        noise = materialize_ldp_noise(noise_key, *raw_updates.shape,
                                      noise_sigma, raw_updates.dtype)
    m = raw_updates.shape[0]
    sq_norms = jnp.sum(jnp.square(raw_updates), axis=-1)      # contiguous reduce
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(jnp.sqrt(sq_norms), _EPS))
    clipped = raw_updates * scale[:, None]
    # sum/m (not jnp.mean) to stay bit-identical to the sharded moment path
    mean_sq_clipped = jnp.sum(sq_norms * jnp.square(scale)) / m
    if noise is None:
        released = clipped
        mean_sq = mean_sq_clipped
    else:
        released = clipped + noise
        mean_sq = jnp.sum(jnp.sum(jnp.square(released), axis=-1)) / m
    cbar = _colmean(released)
    return RoundStats(
        cbar=cbar,
        mean_sq=mean_sq,
        agg_sq=jnp.sum(jnp.square(cbar)),
        mean_sq_clipped=mean_sq_clipped,
    )


def partial_clip_moments(
    raw_updates: jax.Array,
    clip_norm,
    noise: jax.Array | None = None,
    *,
    weight_mask: jax.Array | None = None,
    row_weights: jax.Array | None = None,
    backend: str = "auto",
    interpret: bool | None = None,
    block_m: int | None = None,
    compress_fn=None,
    compress_row_bound=None,
) -> RoundMoments:
    """Shard-local clip -> (optional noise) -> PARTIAL SUMS over the rows.

    The moment-producing half of ``fused_clip_aggregate``: identical
    clip/noise math, but the reductions stay un-normalized sums so shards
    combine by ``psum`` (DESIGN.md §9).  ``noise`` must be materialized by the
    caller (per-client rows via ``materialize_ldp_noise`` with the shard's
    global ``start``) — the in-kernel PRNG path is deliberately excluded here
    because its seed derivation is shard-oblivious: every shard would draw the
    SAME noise block, silently correlating "independent" client randomizers.

    ``weight_mask`` (float (M,) of {0., 1.}) GATES each row's contribution
    to all four sums; padding rows (mask 0) are zeroed BEFORE the clip so a
    NaN from local training on dummy data cannot poison the reduction.
    KNOWN LIMITATION: a with-replacement multiplicity mask (values > 1,
    ``CohortSpec(replace=True)``) only inflates ``count`` here — repeated
    clients are gated in once, not multiplicity-weighted as
    ``raw_moments``/the PrivUnit moments do (weighting the gated sums is not
    bit-compatible with the plain sums the dense reference lowers to, and
    the kernel's fixed sums cannot row-weight).  Exact multiplicity
    weighting is available through ``row_weights``.

    ``row_weights`` (float (M,), optional) additionally weights each RELEASED
    row multiplicatively — the weighted-aggregation layer (DESIGN.md §11):
    ``sum_c = Σ v_i c_i``, the scalar sums weight per-row, and ``count``
    becomes ``Σ gate_i v_i`` so ``sum_c / count`` is the weighted mean.
    Weighting happens AFTER clip+noise, so each client's DP release is
    untouched; ``None`` is bit-identical to the historical unweighted path.
    Weighted reductions always use the jnp path (the kernel's fixed sums
    don't take per-row weights).

    ``compress_fn`` (optional, DESIGN.md §16) is a LINEAR per-row map
    (..., d) -> (..., kc) — rand-k selection or count-sketch — applied to the
    released rows so ``sum_c`` becomes the (kc,) compressed partial sum while
    the three SCALAR sums stay the dense values (FedEXP's step-size inputs
    are exact under compression).  Linearity lets the clip scales commute:
    the raw rows are compressed once and the per-row scale multiplies the
    (m, kc) compressed block, so the clipped (M, d) matrix never
    materializes — one O(M·d) pass (the row norms) instead of the dense
    path's three.  Per-row ``noise`` is rejected (an LDP release is a full
    R^d vector; compression composes with CENTRAL noise added after the
    reduction) and the kernel backend is bypassed (its fixed sums are
    dense).  ``compress_row_bound`` re-clips each COMPRESSED row to that L2
    bound — the count-sketch sensitivity enforcement (worst-case row growth
    sqrt(depth); the bound is a no-op for rows the sketch didn't inflate).
    """
    m = raw_updates.shape[0]
    backend = resolve_backend(backend)
    if backend == "kernel-fused":   # no key routed here; see docstring
        backend = "kernel"
    if compress_fn is not None:
        if noise is not None:
            raise ValueError(
                "compress_fn cannot combine with per-row (LDP) noise: each "
                "client's release is a full R^d vector, so there is nothing "
                "sound to compress.  Use central noise (added to the "
                "compressed aggregate) or drop the compression layer.")
        backend = "jnp"   # the kernel's fixed dense sums cannot compress
    if row_weights is not None:
        backend = "jnp"
    if weight_mask is not None:
        keep = weight_mask[:, None] > 0
        raw_updates = jnp.where(keep, raw_updates, 0.0)
        if noise is not None:
            noise = jnp.where(keep, noise, 0.0)
        gate = weight_mask
    else:
        gate = jnp.ones((m,), jnp.float32)
    count = (jnp.sum(gate) if row_weights is None
             else jnp.sum(gate * row_weights))
    if weight_mask is None and row_weights is None:
        count = jnp.float32(m)  # static-shape constant, as historically

    if backend == "kernel":
        from repro.kernels.dp_aggregate import ops as _ops

        sum_c, sum_sq, sum_sq_clipped = _ops.dp_aggregate_sums(
            raw_updates, clip_norm, noise, interpret=interpret, block_m=block_m)
        return RoundMoments(sum_c=sum_c, sum_sq=sum_sq,
                            sum_sq_clipped=sum_sq_clipped, count=count)
    if backend != "jnp":
        raise ValueError(f"unknown aggregation backend {backend!r}")

    sq_norms = jnp.sum(jnp.square(raw_updates), axis=-1)
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(jnp.sqrt(sq_norms), _EPS))
    if compress_fn is not None:
        # clip commutes with the linear compressor: compress the raw rows,
        # then scale the (m, kc) block — never the (m, d) clipped matrix
        comp = compress_fn(raw_updates) * scale[:, None]
        if compress_row_bound is not None:
            comp_sq = jnp.sum(jnp.square(comp), axis=-1)
            comp = comp * jnp.minimum(
                1.0, compress_row_bound / jnp.maximum(jnp.sqrt(comp_sq),
                                                      _EPS))[:, None]
        # scalar sums are the DENSE clipped values (exact step-size inputs)
        if row_weights is not None:
            v = gate * row_weights
            sum_sq_clipped = sum_dot(v, sq_norms * jnp.square(scale))
            return RoundMoments(sum_c=sum_dot(v, comp), sum_sq=sum_sq_clipped,
                                sum_sq_clipped=sum_sq_clipped, count=count)
        sum_sq_clipped = jnp.sum(sq_norms * jnp.square(scale))
        ones = jnp.ones((m,), jnp.float32)
        return RoundMoments(sum_c=sum_dot(ones, comp), sum_sq=sum_sq_clipped,
                            sum_sq_clipped=sum_sq_clipped, count=count)
    clipped = raw_updates * scale[:, None]
    released = clipped if noise is None else clipped + noise
    if row_weights is not None:
        v = gate * row_weights
        sum_sq_clipped = sum_dot(v, sq_norms * jnp.square(scale))
        sum_sq = (sum_sq_clipped if noise is None
                  else sum_dot(v, jnp.sum(jnp.square(released), axis=-1)))
        return RoundMoments(sum_c=sum_dot(v, released), sum_sq=sum_sq,
                            sum_sq_clipped=sum_sq_clipped, count=count)
    sum_sq_clipped = jnp.sum(sq_norms * jnp.square(scale))
    sum_sq = (sum_sq_clipped if noise is None
              else jnp.sum(jnp.sum(jnp.square(released), axis=-1)))
    ones = jnp.ones((released.shape[0],), jnp.float32)
    return RoundMoments(sum_c=sum_dot(ones, released), sum_sq=sum_sq,
                        sum_sq_clipped=sum_sq_clipped, count=count)


def streamed_clip_moments(
    raw_updates: jax.Array,
    clip_norm,
    noise: jax.Array | None = None,
    *,
    chunk_clients: int,
    weight_mask: jax.Array | None = None,
    row_weights: jax.Array | None = None,
    backend: str = "auto",
    interpret: bool | None = None,
    block_m: int | None = None,
    compress_fn=None,
    compress_row_bound=None,
) -> RoundMoments:
    """``partial_clip_moments`` streamed over row chunks (DESIGN.md §12).

    Splits the (M, d) update matrix into ceil(M / chunk_clients) row chunks,
    reduces each chunk with the identical clip/noise math, and accumulates
    the additive ``RoundMoments`` in a ``lax.scan`` carry — the reference
    formulation of the streaming engine's inner loop for callers that hold a
    dense matrix but want the chunk-grid numerics (testing, or bounding a
    kernel launch's working set).  The engine itself streams one level
    higher (per-chunk LOCAL TRAINING, so the (M, d) matrix never exists);
    this entry point only re-associates the reductions at chunk boundaries
    — all values, including the materialized noise rows, are the dense
    path's (rtol ~1e-6; exact when ``chunk_clients >= M``).

    Args:
      raw_updates: (M, d) raw client updates.
      clip_norm: clip threshold C (python float or traced scalar).
      noise: optional (M, d) pre-materialized per-client noise.
      chunk_clients: rows reduced per scan step (>= 1).
      weight_mask: optional (M,) float {0., 1.} row gate (padding/sampling).
      row_weights: optional (M,) per-client aggregation weights (§11).
      backend: per-chunk reduction backend, as ``partial_clip_moments``.
      interpret / block_m: kernel knobs, forwarded per chunk.
      compress_fn / compress_row_bound: optional §16 per-row compressor,
        forwarded per chunk; the scan carry's ``sum_c`` takes the COMPRESSED
        width (from ``jax.eval_shape``), so chunk partial sums stay additive
        in the compressed domain — the stream form of the §16 invariant.

    Returns:
      The cohort's ``RoundMoments`` partial SUMS, count included —
      ``sum(weight_mask)`` (or the weight sum) exactly as the un-streamed
      entry computes it.
    """
    if chunk_clients < 1:
        raise ValueError(f"chunk_clients must be >= 1, got {chunk_clients}")
    m = raw_updates.shape[0]
    c = min(chunk_clients, m)
    pad = (-m) % c
    n_chunks = (m + pad) // c

    mask = (jnp.ones((m,), jnp.float32) if weight_mask is None
            else weight_mask.astype(jnp.float32))
    had_mask = weight_mask is not None

    def grid(x, fill=0.0):
        """Pad the trailing rows and lay a leaf on the (n_chunks, c, ...) grid."""
        if pad:
            widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
            x = jnp.pad(x, widths, constant_values=fill)
        return x.reshape((n_chunks, c) + x.shape[1:])

    xs = {"u": grid(raw_updates), "mask": grid(mask)}
    if noise is not None:
        xs["noise"] = grid(noise)
    if row_weights is not None:
        xs["w"] = grid(row_weights.astype(jnp.float32))

    def body(acc, chunk):
        """Scan body: accumulate one chunk's additive moments into the carry."""
        mom = partial_clip_moments(
            chunk["u"], clip_norm, chunk.get("noise"),
            weight_mask=chunk["mask"], row_weights=chunk.get("w"),
            backend=backend, interpret=interpret, block_m=block_m,
            compress_fn=compress_fn, compress_row_bound=compress_row_bound)
        return jax.tree_util.tree_map(jnp.add, acc, mom), None

    if compress_fn is None:
        sum_c_zero = jnp.zeros(raw_updates.shape[1:], jnp.float32)
    else:   # the carry accumulates COMPRESSED partial sums
        kc = jax.eval_shape(
            compress_fn, jax.ShapeDtypeStruct((1,) + raw_updates.shape[1:],
                                              jnp.float32)).shape[-1]
        sum_c_zero = jnp.zeros((kc,), jnp.float32)
    zero = RoundMoments(sum_c=sum_c_zero,
                        sum_sq=jnp.float32(0.0),
                        sum_sq_clipped=jnp.float32(0.0),
                        count=jnp.float32(0.0))
    moments, _ = jax.lax.scan(body, zero, xs)
    if not had_mask and row_weights is None and pad == 0:
        # mirror the un-streamed entry's static-count constant when no mask
        # gates rows (each chunk's count is the static chunk size anyway)
        moments = dataclasses.replace(moments, count=jnp.float32(m))
    return moments


def raw_moments(deltas: jax.Array, mask: jax.Array | None,
                row_weights: jax.Array | None = None, *,
                compress_fn=None) -> RoundMoments:
    """Unclipped per-shard sums (non-private algorithms); mask-weighted.

    ``compress_fn`` (optional, DESIGN.md §16): a linear per-row compressor
    applied to the rows feeding ``sum_c`` only — the scalar sums stay the
    dense values, exactly as in ``partial_clip_moments``.  Where-zeroed
    masked rows compress to zero rows (linearity), so padding clients
    contribute nothing to the compressed sum either.

    Every masked scalar sum is a dot with the mask: on XLA:CPU a fused
    ``sum(mask * x)`` accumulates in a different order than the plain
    ``sum(x)`` the unsharded reference lowers to, while ``mask @ x`` matches
    it bit-for-bit (and the column sum already rides the same matvec idiom as
    ``aggregate_stats``).  ``row_weights`` folds per-client aggregation
    weights into the same dot (weighted mean via ``sum_c / count``).

    Masked rows are where-zeroed first: the engine already zeroes them at
    the source (so this is a numeric no-op on that path), but a direct
    caller's garbage row must not leak as ``0 * inf = NaN`` through the
    mask dot — masked clients contribute exactly zero, always.

    ``mask=None`` means full participation with no gate at all: the where
    pass and the traced count are skipped (an all-ones dot is kept so the
    reduction order — hence bitwise value — matches the masked path).
    """
    if mask is None:
        v = (jnp.ones((deltas.shape[0],), jnp.float32) if row_weights is None
             else row_weights)
        count = (jnp.float32(deltas.shape[0]) if row_weights is None
                 else jnp.sum(row_weights))
    else:
        deltas = jnp.where(mask[:, None] > 0, deltas, 0.0)
        v = mask if row_weights is None else mask * row_weights
        count = jnp.sum(v)
    sum_sq = sum_dot(v, jnp.sum(jnp.square(deltas), axis=-1))
    rows = deltas if compress_fn is None else compress_fn(deltas)
    return RoundMoments(sum_c=sum_dot(v, rows), sum_sq=sum_sq,
                        sum_sq_clipped=sum_sq, count=count)
