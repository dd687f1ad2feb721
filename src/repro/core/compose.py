"""Composable algorithm stack: privacy mechanism x aggregation x global step.

The paper's core claim is that DP-FedEXP is a *composition*: any client
randomizer (Gaussian LDP, PrivUnit, central Gaussian) under any clipping
regime can feed the adaptive extrapolated step size.  This module makes that
literal (DESIGN.md §11).  A server algorithm is

    ComposedAlgorithm(mechanism, step, aggregator, name)

built from three orthogonal frozen-dataclass layers (the fourth layer of the
stack — ``LocalTrainer`` / ``LocalSpec`` — lives in ``repro.fedsim`` because
it runs client-side, before the server ever sees an update):

    PrivacyMechanism   owns clipping + noise + the step-size bias correction
                       + the privacy-accounting hook for ITS release:
                       ``NoPrivacy``, ``GaussianLDP``, ``PrivUnitLDP``,
                       ``CentralGaussian`` (fixed sigma or the adaptive-clip
                       noise multiplier ``z_mult``).
    Aggregation        how released updates combine: ``MeanAggregation``
                       (the paper) or ``WeightedAggregation`` (per-client
                       priority/size weights, Talaei et al. 2024), both
                       riding the masked-moment machinery of DESIGN.md §9
                       (``partial_clip_moments`` / the ``dp_aggregate``
                       kernel path, unchanged).
    GlobalStep         what the server does with the released mean:
                       ``FixedEta`` (DP-FedAvg), ``FedEXPStep`` (the paper's
                       adaptive extrapolation, Eqs. 2/6/7/8 — it asks the
                       MECHANISM for its debiased numerator, so one step
                       class serves every randomizer), ``ServerOpt`` (FedOpt
                       family: server Adam / momentum), ``AdaptiveClipStep``
                       (Andrew et al. 2021 quantile clip tracking — owns the
                       clip state and overrides every mechanism's threshold).

Layer contract (who may touch what — DESIGN.md §11):

* The MECHANISM is stateless.  It reads the round key and the clip threshold
  (its own static ``clip_norm`` unless the step overrides it), draws ALL
  randomness of the release (per-client LDP noise keyed by global client
  index; central noise from the replicated post-psum key), and is the only
  layer that sees per-client rows.
* The AGGREGATION layer only reweights rows AFTER the per-client release
  (weights are public), so the DP guarantee is untouched.
* The STEP owns the carry state (optimizer moments, clip threshold) and the
  extra PRNG streams (xi, clip-bit noise) — split off the round key exactly
  as the monolithic classes did, so compositions are bit-identical to them.
* Accounting: ``ComposedAlgorithm.budget`` delegates to
  ``mechanism.budget(...)`` with ``with_numerator`` set when the step
  releases the FedEXP numerator; the session's ``privacy_report`` calls it.

Every legacy registry name (``repro.core.fedexp.make_algorithm``) is now one
of these compositions, pinned bit-for-bit against the monolithic classes by
``tests/test_compose.py``.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core import accounting, compression, stepsize
from repro.core import mechanisms as mech
from repro.core.aggregation import (
    RoundMoments,
    RoundStats,
    aggregate_stats,
    fused_clip_aggregate,
    materialize_ldp_noise,
    partial_clip_moments,
    raw_moments,
    sum_dot,
)
from repro.core.algorithm import (
    RoundAux,
    ServerAlgorithm,
    client_keys,
    set_moment_count,
)
from repro.telemetry import spans

__all__ = [
    "PrivacyMechanism",
    "NoPrivacy",
    "GaussianLDP",
    "PerClientGaussian",
    "PrivUnitLDP",
    "CentralGaussian",
    "NoiseSchedule",
    "Aggregation",
    "MeanAggregation",
    "WeightedAggregation",
    "RandKAggregation",
    "CountSketchAggregation",
    "CompressionCarry",
    "with_compression",
    "GlobalStep",
    "FixedEta",
    "FedEXPStep",
    "ServerOpt",
    "AdaptiveClipStep",
    "ComposedAlgorithm",
    "compose_algorithm",
]


# ---------------------------------------------------------------------------
# Privacy mechanisms
# ---------------------------------------------------------------------------

class PrivacyMechanism:
    """One client randomizer + its clipping regime + its accounting.

    ``clip`` arguments below are ``None`` (use the mechanism's own static
    ``clip_norm`` — the historical, bit-pinned path) or a traced per-round
    threshold injected by ``AdaptiveClipStep``.

    Methods (all pure; ``key`` is the round key, NEVER pre-split — the step
    layer owns key splitting so compositions match the monolithic classes):

        release(key, deltas, clip, m)           dense (M, d) -> (RoundStats, extras)
        moments(key, deltas, mask, start, clip, row_weights)
                                                -> (RoundMoments, extras) partial SUMS
        finalize(key, mom, extras, clip, m_eff) psummed moments -> (RoundStats, extras')
        extrapolation(k_xi, stats, extras, dim, clip, m_eff)
                                                -> (eta, eta_naive, eta_target)
        budget(delta, rounds, dim, sampling_q, with_numerator) -> PrivacyReport
    """

    is_private = True
    needs_xi_key = False            # CDP-style post-aggregation numerator noise
    # compression (DESIGN.md §16): only mechanisms whose release randomness is
    # drawn AFTER the aggregation (central noise) — or not at all — can ride a
    # compressed sum.  An LDP release is a full R^d vector per client; there
    # is no sound way to compress it server-side, so LDP mechanisms leave
    # this False and ComposedAlgorithm rejects the composition at build time.
    supports_compression = False
    # scalar extras psummed alongside the moments (PrivUnit's sum_s_hat);
    # counted by the §16 communication model
    n_scalar_extras = 0
    # round-indexed mechanisms (NoiseSchedule) resolve to a per-round release
    # via ``at_round(t)``; engines thread t only when this is True, so every
    # fixed-noise composition keeps its exact pre-§17 trace
    is_round_indexed = False

    def at_round(self, t):
        """The mechanism governing round ``t`` (self unless round-indexed)."""
        return self

    @property
    def clip_independent_budget(self) -> bool:
        """True when this mechanism's guarantee does not depend on the clip
        threshold (so an AdaptiveClipStep override keeps the budget sound):
        PrivUnit (pure-DP in eps0/eps1/eps2) and the z-tracking
        CentralGaussian (noise std scales with C).  Fixed-sigma Gaussian
        mechanisms are NOT — their sensitivity/noise ratio moves with C."""
        return False

    def _clip(self, clip):
        # subclasses with a clipping regime define a clip_norm field
        return getattr(self, "clip_norm", None) if clip is None else clip

    def release(self, key, deltas, clip, m):
        """Dense release: clip + randomize + reduce M rows to ``(RoundStats, extras)``."""
        raise NotImplementedError

    def moments(self, key, deltas, mask, start, clip, row_weights=None):
        """Shard-local partial SUMS of the release over masked rows at global ``start``."""
        raise NotImplementedError

    def finalize(self, key, mom, extras, clip, m_eff):
        """Globally reduced moments -> the ``RoundStats`` the step layer consumes."""
        return mom.stats(), {}

    def compressed_noise(self, key, shape, clip, m_eff, sens_factor):
        """Release noise for a COMPRESSED aggregate mean of the given shape
        (DESIGN.md §16), or None when this release adds no central noise.
        ``sens_factor`` is the compressor's worst-case row-norm growth
        (enforced pre-aggregation by the moment path's row re-clip), so the
        per-cell noise std scales by it and the C/sigma ratio — all the
        accounting sees — is unchanged from the dense release."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support compressed aggregation")

    def extrapolation(self, k_xi, stats, extras, dim, clip, m_eff):
        """This mechanism's debiased step size: ``(eta_g, eta_naive, eta_target)``."""
        raise NotImplementedError

    def budget(self, delta, *, rounds, dim, sampling_q, with_numerator):
        """Privacy budget of a ``rounds``-round run of this release (``PrivacyReport``)."""
        raise ValueError(f"{type(self).__name__} is not a private mechanism")


@dataclasses.dataclass(frozen=True)
class NoPrivacy(PrivacyMechanism):
    """No clipping, no noise: the FedAvg/FedEXP reference release."""

    is_private = False
    supports_compression = True     # nothing to privatize; compression is free

    def release(self, key, deltas, clip, m):
        """Dense release: clip + randomize + reduce M rows to ``(RoundStats, extras)``."""
        return aggregate_stats(deltas), {}

    def moments(self, key, deltas, mask, start, clip, row_weights=None,
                compress_fn=None, compress_row_bound=None):
        """Shard-local partial SUMS of the release over masked rows at global ``start``."""
        return raw_moments(deltas, mask, row_weights,
                           compress_fn=compress_fn), {}

    def compressed_noise(self, key, shape, clip, m_eff, sens_factor):
        """No release noise; the compressed aggregate passes through."""
        return None

    def extrapolation(self, k_xi, stats, extras, dim, clip, m_eff):
        """This mechanism's debiased step size: ``(eta_g, eta_naive, eta_target)``."""
        return stepsize.fedexp(stats.mean_sq, stats.agg_sq), None, None


@dataclasses.dataclass(frozen=True)
class GaussianLDP(PrivacyMechanism):
    """Per-client clip + Gaussian noise (the paper's LDP setting).

    Noise rows are keyed by GLOBAL client index (``materialize_ldp_noise``)
    so shards reproduce the single-device randomization bit-for-bit; the
    dense release routes through ``fused_clip_aggregate`` (kernel-fused noise
    on TPU, tuned jnp elsewhere — DESIGN.md §5/§8).
    """

    clip_norm: float
    sigma: float
    backend: str = "auto"

    def release(self, key, deltas, clip, m):
        """Dense release: clip + randomize + reduce M rows to ``(RoundStats, extras)``."""
        return fused_clip_aggregate(deltas, self._clip(clip), noise_key=key,
                                    noise_sigma=self.sigma,
                                    backend=self.backend), {}

    def moments(self, key, deltas, mask, start, clip, row_weights=None):
        """Shard-local partial SUMS of the release over masked rows at global ``start``."""
        noise = materialize_ldp_noise(key, *deltas.shape, self.sigma,
                                      deltas.dtype, start=start)
        return partial_clip_moments(deltas, self._clip(clip), noise,
                                    weight_mask=mask, row_weights=row_weights,
                                    backend=self.backend), {}

    def extrapolation(self, k_xi, stats, extras, dim, clip, m_eff):
        """This mechanism's debiased step size: ``(eta_g, eta_naive, eta_target)``."""
        eta = stepsize.ldp_gaussian(stats.mean_sq, stats.agg_sq, dim, self.sigma)
        return (eta,
                stepsize.naive_noisy(stats.mean_sq, stats.agg_sq),
                stepsize.target(stats.mean_sq_clipped, stats.agg_sq))

    def budget(self, delta, *, rounds, dim, sampling_q, with_numerator):
        # per-release local guarantee (Prop. 4.1): identical for FedAvg /
        # FedEXP / FedOpt steps — the step size is computed server-side from
        # already-released updates — and unamplified by central subsampling
        """Privacy budget of a ``rounds``-round run of this release (``PrivacyReport``)."""
        return accounting.ldp_gaussian_budget(self.clip_norm, self.sigma, delta)


@dataclasses.dataclass(frozen=True)
class PerClientGaussian(PrivacyMechanism):
    """Heterogeneous-privacy Gaussian LDP: client i carries its OWN epsilon.

    Each client's sigma_i is derived at build time from its (eps_i, delta)
    budget by inverting the GDP single-release curve (``sigma_for_epsilon``
    with sensitivity 2C), so the per-client guarantee is exact, not a shared
    worst case.  sigma_i is indexed by GLOBAL client index — the same
    contract as ``WeightedAggregation.weights`` — and the noise rows reuse
    the globally-keyed ``materialize_ldp_noise`` stream scaled per row, so
    shards/chunks reproduce the single-device randomization bit-for-bit.

    The FedEXP bias correction under mixed noise subtracts
    ``d * mean(sigma_i^2)`` over the realized cohort (``ldp_gaussian_mixed``);
    the cohort's sum of sigma_i^2 rides the psum as a scalar extra, exactly
    like PrivUnit's sum_s_hat.  When every epsilon is equal the whole path
    short-circuits to ``GaussianLDP``'s expressions with the common sigma —
    the degenerate composition is bit-identical, by construction.

    ``inverse_variance_weights()`` exposes the public 1/sigma_i^2 weights the
    registry pairs with ``WeightedAggregation`` (noisier clients count less;
    the weights depend only on the PUBLIC epsilons, not the data).
    """

    clip_norm: float
    epsilons: tuple[float, ...]
    delta: float
    backend: str = "auto"

    def __post_init__(self):
        eps = tuple(float(e) for e in self.epsilons)
        if not eps:
            raise ValueError("PerClientGaussian requires per-client epsilons")
        object.__setattr__(self, "epsilons", eps)
        sigmas = mech.per_client_sigmas(eps, self.delta, self.clip_norm)
        object.__setattr__(self, "sigmas", sigmas)
        object.__setattr__(self, "_uniform", len(set(sigmas)) == 1)

    @property
    def n_scalar_extras(self):
        """sum_sigma_sq rides the psum only when sigmas actually differ."""
        return 0 if self._uniform else 1

    def inverse_variance_weights(self) -> tuple[float, ...]:
        """Public 1/sigma_i^2 aggregation weights (for WeightedAggregation)."""
        return tuple(1.0 / (s * s) for s in self.sigmas)

    def _sigma_rows(self, start, m_local):
        """(m_local,) per-row sigmas at global ``start`` — the exact slicing
        contract of ``WeightedAggregation.row_weights`` (scalar start or a
        gather-index vector; padding rows past M pick up sigma 0 => no noise,
        and they are masked out of every reduction anyway)."""
        s = jnp.asarray(self.sigmas, jnp.float32)
        if getattr(start, "ndim", 0) == 1:
            padded = jnp.concatenate([s, jnp.zeros((m_local,), jnp.float32)])
            return jnp.take(padded, jnp.minimum(start, len(self.sigmas)),
                            axis=0)
        if isinstance(start, int) and start == 0 and m_local == len(self.sigmas):
            return s
        padded = jnp.concatenate([s, jnp.zeros((m_local,), jnp.float32)])
        return jax.lax.dynamic_slice(padded, (start,), (m_local,))

    def _noise(self, key, shape, dtype, start):
        """Per-row noise: the unit-sigma globally-keyed stream scaled by
        sigma_i — same draws as GaussianLDP, heterogeneous scale."""
        rows = materialize_ldp_noise(key, *shape, 1.0, dtype, start=start)
        return rows * self._sigma_rows(start, shape[0])[:, None]

    def release(self, key, deltas, clip, m):
        """Dense release: clip + randomize + reduce M rows to ``(RoundStats, extras)``."""
        if self._uniform:
            return fused_clip_aggregate(deltas, self._clip(clip), noise_key=key,
                                        noise_sigma=self.sigmas[0],
                                        backend=self.backend), {}
        noise = self._noise(key, deltas.shape, deltas.dtype, 0)
        stats = fused_clip_aggregate(deltas, self._clip(clip), noise,
                                     backend=self.backend)
        sig_sq = jnp.square(self._sigma_rows(0, deltas.shape[0]))
        return stats, {"mean_sigma_sq": jnp.sum(sig_sq) / m}

    def moments(self, key, deltas, mask, start, clip, row_weights=None):
        """Shard-local partial SUMS of the release over masked rows at global ``start``."""
        if self._uniform:
            noise = materialize_ldp_noise(key, *deltas.shape, self.sigmas[0],
                                          deltas.dtype, start=start)
            return partial_clip_moments(deltas, self._clip(clip), noise,
                                        weight_mask=mask,
                                        row_weights=row_weights,
                                        backend=self.backend), {}
        noise = self._noise(key, deltas.shape, deltas.dtype, start)
        mom = partial_clip_moments(deltas, self._clip(clip), noise,
                                   weight_mask=mask, row_weights=row_weights,
                                   backend=self.backend)
        v = mask if row_weights is None else mask * row_weights
        sig_sq = jnp.square(self._sigma_rows(start, deltas.shape[0]))
        return mom, {"sum_sigma_sq": sum_dot(v, sig_sq)}

    def finalize(self, key, mom, extras, clip, m_eff):
        """Globally reduced moments -> the ``RoundStats`` the step layer consumes."""
        if self._uniform:
            return mom.stats(), {}
        return mom.stats(), {"mean_sigma_sq": extras["sum_sigma_sq"] / mom.count}

    def extrapolation(self, k_xi, stats, extras, dim, clip, m_eff):
        """This mechanism's debiased step size: ``(eta_g, eta_naive, eta_target)``."""
        if self._uniform:
            eta = stepsize.ldp_gaussian(stats.mean_sq, stats.agg_sq, dim,
                                        self.sigmas[0])
        else:
            eta = stepsize.ldp_gaussian_mixed(stats.mean_sq, stats.agg_sq, dim,
                                              extras["mean_sigma_sq"])
        return (eta,
                stepsize.naive_noisy(stats.mean_sq, stats.agg_sq),
                stepsize.target(stats.mean_sq_clipped, stats.agg_sq))

    def budget(self, delta, *, rounds, dim, sampling_q, with_numerator):
        """Worst-client budget: the report is the LDP guarantee of the
        smallest-sigma (largest-epsilon) client; every other client's release
        is strictly more private (its eps_i at the same delta is smaller)."""
        rep = accounting.ldp_gaussian_budget(self.clip_norm, min(self.sigmas),
                                             delta)
        return dataclasses.replace(
            rep, setting=f"LDP (Gaussian, per-client worst of "
                         f"{len(self.epsilons)})")


@dataclasses.dataclass(frozen=True)
class PrivUnitLDP(PrivacyMechanism):
    """Per-client clip + PrivUnit direction x ScalarDP magnitude (pure LDP).

    With a traced clip override (adaptive clipping) the static ScalarDP
    lattice built at ``clip_norm`` is reused through exact public rescaling:
    magnitudes are released on the reference scale and multiplied back by
    ``clip / clip_norm`` (ScalarDP's debias transform is linear in ``r_max``,
    so this is the r_max=clip release, not an approximation).
    """

    clip_norm: float
    eps0: float
    eps1: float
    eps2: float
    dim: int

    n_scalar_extras = 1      # sum_s_hat rides the psum next to the moments

    def __post_init__(self):
        object.__setattr__(self, "pu", mech.make_privunit_params(self.dim, self.eps0, self.eps1))
        object.__setattr__(self, "sc", mech.make_scalardp_params(self.eps2, self.clip_norm))

    @property
    def clip_independent_budget(self) -> bool:
        """True when the guarantee does not move with the clip threshold."""
        return True  # pure (eps0+eps1+eps2)-LDP at ANY clip threshold

    def _randomize(self, key, deltas, start, clip):
        """Per-client clip + PrivUnit release, keys by GLOBAL client index."""
        m, _ = deltas.shape
        keys = client_keys(key, m, start)
        c = self._clip(clip)
        norms = jnp.linalg.norm(deltas, axis=-1)
        scale = jnp.minimum(1.0, c / jnp.maximum(norms, 1e-12))
        clipped = deltas * scale[:, None]
        if clip is None:
            released = jax.vmap(
                lambda k, dlt: mech.privunit_randomize(k, dlt, self.pu, self.sc))(keys, clipped)
        else:  # traced clip: release on the reference scale, rescale publicly
            to_ref = self.clip_norm / c
            released = jax.vmap(
                lambda k, dlt: mech.privunit_randomize(k, dlt, self.pu, self.sc))(
                keys, clipped * to_ref) / to_ref
        return released, clipped

    def _s_hat(self, released, clip):
        est = jax.vmap(lambda v: mech.estimate_norm_sq(v, self.pu, self.sc))
        if clip is None:
            return est(released)
        to_ref = self.clip_norm / self._clip(clip)
        return est(released * to_ref) / jnp.square(to_ref)

    def release(self, key, deltas, clip, m):
        """Dense release: clip + randomize + reduce M rows to ``(RoundStats, extras)``."""
        released, clipped = self._randomize(key, deltas, 0, clip)
        stats = aggregate_stats(released)
        stats.mean_sq_clipped = (
            jnp.sum(jnp.sum(jnp.square(clipped), axis=-1)) / m)
        return stats, {"mean_s_hat": jnp.sum(self._s_hat(released, clip)) / m}

    def moments(self, key, deltas, mask, start, clip, row_weights=None):
        """Shard-local partial SUMS of the release over masked rows at global ``start``."""
        released, clipped = self._randomize(key, deltas, start, clip)
        # where-zero BOTH row sets (released and pre-noise clipped): the
        # engine zeroes masked deltas at the source, but a garbage row must
        # not leak as 0 * inf = NaN through the mask dots below
        keep = mask[:, None] > 0
        released = jnp.where(keep, released, 0.0)
        clipped = jnp.where(keep, clipped, 0.0)
        # dots with the mask, not sum(mask * x): bit-parity with the
        # unsharded reference reductions (see ``raw_moments``)
        v = mask if row_weights is None else mask * row_weights
        mom = RoundMoments(
            sum_c=sum_dot(v, released),
            sum_sq=sum_dot(v, jnp.sum(jnp.square(released), axis=-1)),
            sum_sq_clipped=sum_dot(v, jnp.sum(jnp.square(clipped), axis=-1)),
            count=jnp.sum(v))
        return mom, {"sum_s_hat": sum_dot(v, self._s_hat(released, clip))}

    def finalize(self, key, mom, extras, clip, m_eff):
        """Globally reduced moments -> the ``RoundStats`` the step layer consumes."""
        return mom.stats(), {"mean_s_hat": extras["sum_s_hat"] / mom.count}

    def extrapolation(self, k_xi, stats, extras, dim, clip, m_eff):
        """This mechanism's debiased step size: ``(eta_g, eta_naive, eta_target)``."""
        eta = stepsize.ldp_privunit(extras["mean_s_hat"], stats.agg_sq)
        return (eta,
                stepsize.naive_noisy(stats.mean_sq, stats.agg_sq),
                stepsize.target(stats.mean_sq_clipped, stats.agg_sq))

    def budget(self, delta, *, rounds, dim, sampling_q, with_numerator):
        """Privacy budget of a ``rounds``-round run of this release (``PrivacyReport``)."""
        return accounting.privunit_budget(self.eps0, self.eps1, self.eps2)


@dataclasses.dataclass(frozen=True)
class CentralGaussian(PrivacyMechanism):
    """Clip-only clients + server-side Gaussian noise on the mean (CDP).

    Two noise modes:
      * fixed ``sigma`` (the paper): server noise std ``sigma / sqrt(M)``
        with the STATIC configured client count — the release the
        Proposition 4.2 accounting is stated for;
      * ``z_mult`` (adaptive clipping, Andrew et al.): std ``z*C / sqrt(m)``
        tracking the CURRENT clip threshold and the REALIZED cohort size, so
        the guarantee is C-independent.
    Noise is drawn from the replicated round key AFTER the psum, so sharded
    and single-device releases add the identical (d,) draw (DESIGN.md §9).
    """

    clip_norm: float | None = None
    sigma: float | None = None
    num_clients: int = 0
    sigma_xi: float | None = None     # numerator noise; None = d sigma^2 / M
    z_mult: float | None = None       # adaptive mode: sigma = z * C
    backend: str = "auto"

    needs_xi_key = True
    supports_compression = True     # noise is drawn POST-aggregation (§16)

    def __post_init__(self):
        if (self.sigma is None) == (self.z_mult is None):
            raise ValueError("set exactly one of sigma (fixed) / z_mult (adaptive)")
        if self.sigma is not None and self.clip_norm is None:
            raise ValueError("fixed-sigma CentralGaussian requires clip_norm")
        if self.num_clients < 1:
            raise ValueError("CentralGaussian requires num_clients >= 1")

    @property
    def clip_independent_budget(self) -> bool:
        """True when the guarantee does not move with the clip threshold."""
        return self.z_mult is not None  # noise tracks z*C => C cancels

    def _sigma(self, clip):
        return self.sigma if self.z_mult is None else self.z_mult * self._clip(clip)

    def _m_noise(self, m_eff):
        """Divisor of the server-noise std: the static configured M for the
        fixed-sigma release, the realized cohort for the z-tracking one.
        A traced realized count is floored at 1 (a weight-sum count < 1 must
        not inflate the noise; the static dense count is left untouched —
        the monolithic classes' exact expression)."""
        if self.z_mult is None:
            return float(self.num_clients)
        return m_eff if isinstance(m_eff, float) else jnp.maximum(m_eff, 1.0)

    def _noised(self, key, cbar, clip, m_eff):
        d = cbar.shape[-1]
        noise = (self._sigma(clip) / jnp.sqrt(self._m_noise(m_eff))) \
            * jax.random.normal(key, (d,))
        return cbar + noise

    def release(self, key, deltas, clip, m):
        """Dense release: clip + randomize + reduce M rows to ``(RoundStats, extras)``."""
        stats = fused_clip_aggregate(deltas, self._clip(clip), None,
                                     backend=self.backend)
        cbar = self._noised(key, stats.cbar, clip, m)
        return RoundStats(cbar=cbar, mean_sq=stats.mean_sq,
                          agg_sq=jnp.sum(jnp.square(cbar)),
                          mean_sq_clipped=stats.mean_sq_clipped), {}

    def moments(self, key, deltas, mask, start, clip, row_weights=None,
                compress_fn=None, compress_row_bound=None):
        """Shard-local partial SUMS of the release over masked rows at global ``start``."""
        return partial_clip_moments(deltas, self._clip(clip), None,
                                    weight_mask=mask, row_weights=row_weights,
                                    backend=self.backend,
                                    compress_fn=compress_fn,
                                    compress_row_bound=compress_row_bound), {}

    def finalize(self, key, mom, extras, clip, m_eff):
        """Globally reduced moments -> the ``RoundStats`` the step layer consumes."""
        cbar = self._noised(key, mom.sum_c / mom.count, clip, m_eff)
        return RoundStats(cbar=cbar, mean_sq=mom.sum_sq / mom.count,
                          agg_sq=jnp.sum(jnp.square(cbar)),
                          mean_sq_clipped=mom.sum_sq_clipped / mom.count), {}

    def compressed_noise(self, key, shape, clip, m_eff, sens_factor):
        """Gaussian noise on the compressed aggregate mean (DESIGN.md §16).

        Per-client sensitivity of the compressed SUM is ``sens_factor * C``
        (rand-k is a contraction, sens_factor 1; count-sketch rows are
        re-clipped to ``sqrt(depth) * C`` by the moment path), so the mean's
        noise std is the dense release's ``sigma(C) / sqrt(m)`` scaled by the
        same factor — the C/sigma ratio, hence ``budget()``, is unchanged."""
        return (sens_factor * self._sigma(clip)
                / jnp.sqrt(self._m_noise(m_eff))) \
            * jax.random.normal(key, shape)

    def extrapolation(self, k_xi, stats, extras, dim, clip, m_eff):
        """This mechanism's debiased step size: ``(eta_g, eta_naive, eta_target)``."""
        sigma = self._sigma(clip)
        sigma_xi = (self.sigma_xi if self.sigma_xi is not None
                    else dim * sigma**2 / self._m_noise(m_eff))
        xi = sigma_xi * jax.random.normal(k_xi, ())
        eta = stepsize.cdp(stats.mean_sq_clipped, xi, stats.agg_sq)
        return eta, None, stepsize.target(stats.mean_sq_clipped, stats.agg_sq)

    def budget(self, delta, *, rounds, dim, sampling_q, with_numerator):
        """Privacy budget of a ``rounds``-round run of this release (``PrivacyReport``)."""
        q = sampling_q
        if self.z_mult is not None:
            # noise std tracks z*C, so the C/sigma ratio — all the budget
            # sees — is the constant 1/z; stated in C=1 units.  The noise
            # scales with the REALIZED cohort (sigma/sqrt(|S_t|)), so the
            # conditional per-round mu inflates by 1/sqrt(q) only; feeding
            # cdp_budget the effective count M/q composes exactly that.  The
            # clip-bit release adds adaptive_clip_rho, negligible by
            # construction (sigma_b ~ 10).
            return accounting.cdp_budget(
                1.0, self.z_mult, self.num_clients / q, rounds, delta,
                sigma_xi=(dim * self.z_mult**2 / self.num_clients
                          if with_numerator else None),
                sampling_q=q)
        sigma_xi = None
        if with_numerator:
            sigma_xi = (self.sigma_xi if self.sigma_xi is not None
                        else dim * self.sigma**2 / self.num_clients)
        return accounting.cdp_budget(self.clip_norm, self.sigma,
                                     self.num_clients, rounds, delta,
                                     sigma_xi=sigma_xi, sampling_q=q)


@dataclasses.dataclass(frozen=True)
class NoiseSchedule(PrivacyMechanism):
    """Round-indexed noise schedule sigma(t) over a fixed-sigma mechanism.

    A pure CONFIG wrapper (DESIGN.md §17): it never executes a release
    itself.  Engines that see ``is_round_indexed`` thread the round index t
    into the composition, and ``at_round(t)`` resolves the wrapper to its
    inner mechanism with ``sigma = sigma(t)`` — a traced scalar riding the
    existing clip/sigma plumbing, so no engine grows a schedule branch.

        sigma(t) = sigma0 * decay**t * step_factor(t)

    where ``step_factor`` is 1 before the first boundary and ``scales[i]``
    from ``boundaries[i]`` on (Adap-DP-FL-style decay, plus step drops).
    A CONSTANT schedule (decay 1, no boundaries) resolves to the inner
    mechanism UNCHANGED — same object, same trace, bit-for-bit the fixed-
    sigma run — which is the degenerate case the parity suite pins.

    ``budget()`` composes the non-uniform sequence honestly: per-round
    mu_t summed in GDP (``composed_gdp_mu``) with the RDP upper bound kept
    (``schedule_ldp_budget`` / ``schedule_cdp_budget``); a constant schedule
    delegates to the inner mechanism's own accounting for an exactly equal
    report.
    """

    inner: PrivacyMechanism = None
    decay: float = 1.0
    boundaries: tuple[int, ...] = ()
    scales: tuple[float, ...] = ()

    def __post_init__(self):
        if not isinstance(self.inner, (GaussianLDP, CentralGaussian)):
            raise ValueError(
                "NoiseSchedule wraps a fixed-sigma Gaussian mechanism "
                "(GaussianLDP or CentralGaussian); got "
                f"{type(self.inner).__name__}")
        if isinstance(self.inner, CentralGaussian) and self.inner.sigma is None:
            raise ValueError(
                "NoiseSchedule needs a fixed-sigma CentralGaussian; the "
                "z_mult (adaptive-clip) mode already rescales its noise per "
                "round and has no static sigma to schedule")
        if not (isinstance(self.decay, (int, float)) and self.decay > 0):
            raise ValueError(f"decay must be positive, got {self.decay!r}")
        bounds = tuple(int(b) for b in self.boundaries)
        if any(b < 0 for b in bounds) or list(bounds) != sorted(set(bounds)):
            raise ValueError(
                "boundaries must be strictly increasing nonnegative rounds")
        scales = tuple(float(s) for s in self.scales)
        if len(scales) != len(bounds):
            raise ValueError("scales must match boundaries one-to-one")
        if any(s <= 0 for s in scales):
            raise ValueError("scales must be positive")
        object.__setattr__(self, "boundaries", bounds)
        object.__setattr__(self, "scales", scales)

    # -- schedule ----------------------------------------------------------

    @property
    def is_constant(self) -> bool:
        """True when sigma(t) == sigma0 for every t (degenerate schedule)."""
        return self.decay == 1.0 and not self.boundaries

    @property
    def is_round_indexed(self):
        """Engines thread t only for genuinely varying schedules."""
        return not self.is_constant

    def at_round(self, t):
        """The inner mechanism at round ``t`` (traced-sigma replace); the
        inner object ITSELF for a constant schedule — same trace, bit-for-bit
        the fixed-sigma composition."""
        if self.is_constant:
            return self.inner
        return dataclasses.replace(self.inner, sigma=self._sigma_at(t))

    def _sigma_at(self, t):
        """sigma(t) as a traced f32 scalar (t is the traced round index)."""
        tf = jnp.asarray(t, jnp.float32)
        s = jnp.float32(self.inner.sigma) \
            * jnp.power(jnp.float32(self.decay), tf)
        if self.boundaries:
            factors = jnp.asarray((1.0,) + self.scales, jnp.float32)
            idx = jnp.sum((jnp.asarray(self.boundaries) <= t).astype(jnp.int32))
            s = s * factors[idx]
        return s

    def sigma_value(self, t: int) -> float:
        """sigma(t) as a Python float (accounting / telemetry validation).

        f64 mirror of ``_sigma_at``; the traced release uses the f32 value,
        so cross-checks against emitted telemetry compare at f32 rtol.
        """
        factor = 1.0
        for b, sc in zip(self.boundaries, self.scales):
            if t >= b:
                factor = sc
        return float(self.inner.sigma) * float(self.decay) ** int(t) * factor

    # -- delegation to the inner mechanism ---------------------------------

    @property
    def needs_xi_key(self):
        """The wrapper splits keys exactly as its inner mechanism would."""
        return self.inner.needs_xi_key

    @property
    def supports_compression(self):
        """Compression composes iff the inner release does (§16)."""
        return self.inner.supports_compression

    @property
    def n_scalar_extras(self):
        """The inner release's psummed scalar extras (none for Gaussians)."""
        return self.inner.n_scalar_extras

    def __getattr__(self, item):
        if item.startswith("__") or item == "inner":
            raise AttributeError(item)
        d = object.__getattribute__(self, "__dict__")
        inner = d.get("inner")
        if inner is None:
            raise AttributeError(item)
        return getattr(inner, item)

    # -- accounting --------------------------------------------------------

    def budget(self, delta, *, rounds, dim, sampling_q, with_numerator):
        """GDP composition of the non-uniform sigma sequence (DESIGN.md §17);
        constant schedules delegate to the inner mechanism's own accounting
        so the degenerate report is exactly the fixed-sigma one."""
        if self.is_constant:
            return self.inner.budget(delta, rounds=rounds, dim=dim,
                                     sampling_q=sampling_q,
                                     with_numerator=with_numerator)
        sigmas = [self.sigma_value(t) for t in range(rounds)]
        if isinstance(self.inner, GaussianLDP):
            # local guarantee: unamplified by sampling, xi is server-side
            return accounting.schedule_ldp_budget(self.inner.clip_norm,
                                                  sigmas, delta)
        sigma_xis = None
        if with_numerator:
            # mirror CentralGaussian.extrapolation: the hyperparameter-free
            # numerator noise tracks the CURRENT sigma(t) unless pinned
            sigma_xis = [self.inner.sigma_xi if self.inner.sigma_xi is not None
                         else dim * s ** 2 / self.inner.num_clients
                         for s in sigmas]
        return accounting.schedule_cdp_budget(self.inner.clip_norm, sigmas,
                                              self.inner.num_clients, delta,
                                              sigma_xis=sigma_xis,
                                              sampling_q=sampling_q)


# ---------------------------------------------------------------------------
# Aggregation layer
# ---------------------------------------------------------------------------

class Aggregation:
    """How released client updates combine into the round's moments.

    Two orthogonal capabilities ride this layer: per-client WEIGHTS
    (``is_weighted``; public reweighting after each DP release) and
    per-round COMPRESSION (``is_compressed``, DESIGN.md §16; a linear
    per-row map shrinking the O(d) round collective to the compressed
    width).  A compressed layer's plan is re-derived each round from
    ``fold_in(round_key, COMPRESS_TAG)`` — replicated, so every shard and
    stream chunk compresses with the identical plan and the compressed
    partial sums stay additive (§12).
    """

    is_weighted: bool = False
    is_compressed = False
    # worst-case L2 growth of a compressed row vs its dense norm; the moment
    # path re-clips compressed rows to sens_factor * C so central noise can
    # scale by exactly this factor (§16)
    sens_factor = 1.0
    uses_error_feedback = False

    def row_weights(self, start, m_local):
        """Per-client aggregation weights for the rows [start, start + m_local)."""
        return None

    def comm_floats(self, d: int) -> int:
        """Floats in one client's released update / the round's vector sum."""
        return d

    # -- compression API (no-ops for dense layers) --------------------------

    def plan(self, plan_key, d):
        """Per-round shared-randomness tables (indices / hashes); None = dense."""
        return None

    def compress_fn(self, plan):
        """The linear per-row compressor ``(..., d) -> (..., kc)`` for this plan."""
        return None

    def decompress(self, comp, plan, d):
        """(kc,) compressed aggregate -> (d,) estimate (identity when dense)."""
        return comp

    def select(self, g):
        """Post-decompression support selection (top-k); identity by default."""
        return g


def _as_int(name: str, v) -> int:
    if not isinstance(v, int) or isinstance(v, bool) or v < 1:
        raise ValueError(f"{name} must be a positive int, got {v!r}")
    return v


@dataclasses.dataclass(frozen=True)
class MeanAggregation(Aggregation):
    """Uniform mean over the (masked) cohort — the paper's aggregation.
    ``sum / count`` through the masked-moment machinery, bit-identical to
    the monolithic classes."""


@dataclasses.dataclass(frozen=True)
class WeightedAggregation(Aggregation):
    """Per-client aggregation weights (priority / dataset-size weighting,
    Talaei et al. 2024): the round releases ``Σ v_i c_i / Σ v_i``.

    Weights are PUBLIC and applied AFTER each client's DP release, so the
    per-client guarantee is unchanged (the central sensitivity of the
    weighted mean is ``2C·max_i v_i / Σv`` — budget reporting stays the
    mechanism's; see DESIGN.md §11).  ``weights`` is a static per-client
    tuple indexed by GLOBAL client index; shards slice their own rows.
    Weighted counts are real-valued, so the engine's static-count
    substitution is disabled for these compositions.
    """

    weights: tuple[float, ...] = ()

    is_weighted = True

    def __post_init__(self):
        if not self.weights:
            raise ValueError("WeightedAggregation requires per-client weights")
        if any(w < 0 for w in self.weights) or sum(self.weights) <= 0:
            raise ValueError("weights must be nonnegative with positive sum")

    def row_weights(self, start, m_local):
        """Per-client aggregation weights for the rows [start, start + m_local).

        ``start`` is the scalar global index of row 0 (contiguous shard/chunk
        slices) or a (m_local,) vector of global indices (the sparse-gather
        path, DESIGN.md §14) — padding rows index past M and pick up zeros.
        """
        w = jnp.asarray(self.weights, jnp.float32)
        if getattr(start, "ndim", 0) == 1:
            padded = jnp.concatenate([w, jnp.zeros((m_local,), jnp.float32)])
            return jnp.take(padded, jnp.minimum(start, len(self.weights)),
                            axis=0)
        if isinstance(start, int) and start == 0 and m_local == len(self.weights):
            return w
        # shard slice by (possibly traced) global start; zero-pad so padding
        # clients past M slice zeros
        padded = jnp.concatenate([w, jnp.zeros((m_local,), jnp.float32)])
        return jax.lax.dynamic_slice(padded, (start,), (m_local,))


@dataclasses.dataclass(frozen=True)
class RandKAggregation(Aggregation):
    """Unbiased random-k coordinate aggregation (DESIGN.md §16).

    Each round draws k distinct coordinates (shared plan from the round
    key — no per-client state, so it composes with §14 sampling); clients'
    clipped updates are projected onto them, the round reduces a (k,) sum,
    and the server's d/k-scaled scatter is an UNBIASED estimate of the dense
    mean: ``E[decompress(compress(x))] = x`` over the index draw.  A
    coordinate projection is an L2 contraction, so the compressed release
    keeps sensitivity C exactly (sens_factor 1) and central noise is the
    dense std per compressed coordinate.  Unbiased => no error feedback.
    """

    k: int

    is_compressed = True

    def __post_init__(self):
        _as_int("k", self.k)

    def comm_floats(self, d: int) -> int:
        """Floats in one client's released update / the round's vector sum."""
        return min(self.k, d)

    def plan(self, plan_key, d):
        """(k,) distinct coordinate indices drawn for this round."""
        return compression.randk_plan(plan_key, d, min(self.k, d))

    def compress_fn(self, plan):
        """The linear per-row compressor ``(..., d) -> (..., k)``."""
        return lambda u: compression.randk_compress(u, plan)

    def decompress(self, comp, plan, d):
        """Unbiased (d,) estimate: scatter the (k,) sum back, scaled by d/k."""
        return compression.randk_decompress(comp, plan, d)


@dataclasses.dataclass(frozen=True)
class CountSketchAggregation(Aggregation):
    """Count-sketch aggregation with heavy-hitter recovery (DESIGN.md §16).

    Clients sketch their clipped update into a (depth, width) bucket table
    (shared per-round hashes from the round key), the round reduces the
    (depth * width,) flattened sketch, and the server unsketches by
    median-of-depth, optionally keeping only the ``top_k`` largest-|.|
    coordinates (heavy hitters).  The sketch is BIASED once ``top_k``
    truncates the support, so ``error_feedback=True`` carries the
    truncation residual server-side (in the scan state) and re-injects it
    next round — the EF accumulator restores convergence for the biased
    variant.  Worst-case row growth: a (depth, d) sign-hash sketch of a
    C-clipped row has L2 at most ``sqrt(depth) * C`` in expectation-exact
    cases and up to ``sqrt(depth) * ||u||_1`` adversarially, so the moment
    path RE-CLIPS each compressed row to ``sqrt(depth) * C`` (sens_factor)
    before summing — sensitivity is enforced, not assumed, and central
    noise scales by the same factor (the C/sigma accounting is unchanged).
    """

    width: int
    depth: int = 3
    top_k: int | None = None
    error_feedback: bool = False

    is_compressed = True

    def __post_init__(self):
        _as_int("width", self.width)
        _as_int("depth", self.depth)
        if self.top_k is not None:
            _as_int("top_k", self.top_k)
        if self.error_feedback and self.top_k is None:
            raise ValueError(
                "error_feedback without top_k has nothing to feed back: the "
                "un-truncated median unsketch is already the best estimate "
                "this sketch offers.  Set top_k=<support size> (the biased "
                "variant EF exists to correct) or drop error_feedback.")

    @property
    def sens_factor(self):
        """Worst-case compressed-row L2 growth: sqrt(depth) sign-hash tables."""
        return math.sqrt(self.depth)

    @property
    def uses_error_feedback(self):
        """Whether the carry grows a server-side EF residual (§16)."""
        return self.error_feedback

    def comm_floats(self, d: int) -> int:
        """Floats in one client's released update / the round's vector sum."""
        return self.width * self.depth

    def plan(self, plan_key, d):
        """This round's (depth, d) bucket ids + Rademacher signs."""
        return compression.sketch_plan(plan_key, d, self.width, self.depth)

    def compress_fn(self, plan):
        """The linear per-row sketcher ``(..., d) -> (..., depth * width)``."""
        return lambda u: compression.sketch_compress(u, plan, self.width)

    def decompress(self, comp, plan, d):
        """Median-of-depth unsketch to a dense (d,) estimate (no truncation
        here — ``select`` applies top-k AFTER error feedback so the EF
        residual sees the full estimate)."""
        return compression.sketch_decompress(comp, plan, d)

    def select(self, g):
        """Keep the top_k largest-|.| coordinates (identity when top_k unset)."""
        return g if self.top_k is None else compression.topk_select(g, self.top_k)


# ---------------------------------------------------------------------------
# Global step layer
# ---------------------------------------------------------------------------

class GlobalStep:
    """Server-side update policy + owner of the carry state and extra keys.

    ``n_extra_keys`` declares how many PRNG streams beyond the mechanism's
    must be split off the round key (xi for CDP extrapolation, the clip-bit
    stream) — EXACTLY the splits the monolithic classes performed, which is
    what keeps compositions bit-identical.
    """

    stateful: bool = False
    needs_clip_bits: bool = False
    uses_extrapolation: bool = False

    def n_extra_keys(self, mechanism) -> int:
        """PRNG streams beyond the mechanism's to split off the round key."""
        return 0

    def clip_override(self, state):
        """Traced per-round clip threshold from the carry; None = mechanism's static."""
        return None

    def init(self, w):
        """Initial step-owned carry state (optimizer moments / clip threshold)."""
        return ()

    def apply(self, extra_keys, w, stats, extras, mechanism, clip, m_eff, state):
        """Apply this server-update policy to the released round statistics."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class FixedEta(GlobalStep):
    """w <- w + eta_g * cbar with a constant eta_g (DP-FedAvg: eta_g = 1)."""

    eta: float = 1.0

    def apply(self, extra_keys, w, stats, extras, mechanism, clip, m_eff, state):
        """Apply this server-update policy to the released round statistics."""
        w_next = w + stats.cbar if self.eta == 1.0 else w + self.eta * stats.cbar
        return w_next, RoundAux(eta_g=jnp.float32(self.eta)), state


@dataclasses.dataclass(frozen=True)
class FedEXPStep(GlobalStep):
    """The paper's adaptive extrapolation (Eqs. 2/6/7/8).

    The mechanism supplies its own debiased numerator (it owns the noise it
    must correct for); this step owns the policy — extrapolate by the ratio,
    floored at 1 — and the xi key when the mechanism privatizes the
    numerator post-aggregation.
    """

    uses_extrapolation = True

    def n_extra_keys(self, mechanism):
        """PRNG streams beyond the mechanism's to split off the round key."""
        return 1 if mechanism.needs_xi_key else 0

    def apply(self, extra_keys, w, stats, extras, mechanism, clip, m_eff, state):
        """Apply this server-update policy to the released round statistics."""
        k_xi = extra_keys[0] if extra_keys else None
        eta, naive, target = mechanism.extrapolation(
            k_xi, stats, extras, w.shape[-1], clip,
            extras.get("n_clients", m_eff))
        aux = RoundAux(eta_g=eta, eta_naive=naive, eta_target=target,
                       update_norm=eta * jnp.linalg.norm(stats.cbar))
        return w + eta * stats.cbar, aux, state


@dataclasses.dataclass(frozen=True)
class ServerOpt(GlobalStep):
    """FedOpt servers (Reddi et al. 2021): Adam / momentum over the released
    pseudo-gradient — the extra-hyperparameter family the paper argues
    against, kept for the E6 ablation and now composable with ANY mechanism
    (e.g. LDP-Gaussian + server Adam)."""

    kind: str = "adam"
    lr: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8

    stateful = True

    def __post_init__(self):
        from repro import optim
        if self.kind == "adam":
            opt = optim.adam(lr=self.lr, b1=self.beta1, b2=self.beta2, eps=self.eps)
        elif self.kind == "momentum":
            opt = optim.momentum(lr=self.lr, beta=self.beta1)
        else:
            raise ValueError(f"unknown ServerOpt kind {self.kind!r}")
        object.__setattr__(self, "_opt", opt)

    def init(self, w):
        """Initial step-owned carry state (optimizer moments / clip threshold)."""
        return self._opt.init(w)

    def apply(self, extra_keys, w, stats, extras, mechanism, clip, m_eff, state):
        """Apply this server-update policy to the released round statistics."""
        step, state = self._opt.update(stats.cbar, state)
        return w + step, RoundAux(eta_g=jnp.float32(self.lr)), state


@dataclasses.dataclass(frozen=True)
class AdaptiveClipStep(GlobalStep):
    """Quantile-tracked clipping (Andrew et al. 2021) composed over any
    mechanism: the clip threshold C lives in the carry, overrides the
    mechanism's static threshold each round (a TRACED scalar — the kernel
    backend prefetches it, no recompiles), and updates from the privatized
    below-threshold bit sum.  The step size is the mechanism's extrapolation
    rule read at the CURRENT C (for CentralGaussian(z_mult=z) that is the
    hyperparameter-free sigma_xi = d (zC)^2 / m of §3.2)."""

    c0: float = 1.0
    gamma: float = 0.5
    clip_lr: float = 0.2
    sigma_b: float = 10.0

    stateful = True
    needs_clip_bits = True
    uses_extrapolation = True

    def n_extra_keys(self, mechanism):
        """PRNG streams beyond the mechanism's to split off the round key."""
        return (1 if mechanism.needs_xi_key else 0) + 1

    def clip_override(self, state):
        """Traced per-round clip threshold from the carry; None = mechanism's static."""
        return state.clip

    def init(self, w):
        """Initial step-owned carry state (optimizer moments / clip threshold)."""
        from repro.core import adaptive_clip as ac
        return ac.init_state(self.c0)

    def apply(self, extra_keys, w, stats, extras, mechanism, clip, m_eff, state):
        """Apply this server-update policy to the released round statistics."""
        from repro.core import adaptive_clip as ac
        if len(extra_keys) == 2:
            k_xi, k_bit = extra_keys
        else:
            k_xi, (k_bit,) = None, extra_keys
        c = state.clip
        # quantile tracking and realized-cohort noise run on the CLIENT
        # count; weighted compositions deliver it separately because their
        # moment count is a weight sum (extras["n_clients"]); everywhere
        # else m_eff IS the client count — the monolithic classes' exact arg
        m_clients = extras.get("n_clients", m_eff)
        eta, _, _ = mechanism.extrapolation(
            k_xi, stats, extras, w.shape[-1], clip, m_clients)
        cfg = ac.AdaptiveClipConfig(gamma=self.gamma, lr=self.clip_lr,
                                    sigma_b=self.sigma_b)
        state, _ = ac.update_clip_from_stats(k_bit, state,
                                             extras["count_below"],
                                             m_clients, cfg)
        aux = RoundAux(eta_g=eta, update_norm=c)   # report the clip used
        return w + eta * stats.cbar, aux, state


# ---------------------------------------------------------------------------
# The composed server algorithm
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CompressionCarry:
    """Server carry of an error-feedback compressed composition (§16).

    Wraps the step's own state with the (d,) EF residual so EF rides the
    engines' existing scan/stream/checkpoint carry unchanged; every state
    touchpoint in ComposedAlgorithm unwraps ``inner`` before the step sees
    it.  Only built when the aggregation layer asks for error feedback —
    every other composition's carry shape is untouched.
    """

    ef: jax.Array
    inner: object


@dataclasses.dataclass(frozen=True)
class ComposedAlgorithm(ServerAlgorithm):
    """mechanism x aggregation x step as one engine-facing ServerAlgorithm.

    Frozen and hashable by configuration (every layer is a frozen dataclass),
    so compositions key the engine's compile cache exactly like the
    monolithic classes.  Unknown attributes forward to the layers
    (``alg.sigma_xi`` -> ``mechanism.sigma_xi``), preserving the monolithic
    classes' attribute surface.
    """

    mechanism: PrivacyMechanism
    step: GlobalStep
    aggregation: Aggregation = MeanAggregation()
    name: str = "composed"

    def __post_init__(self):
        if (self.aggregation.is_compressed
                and not self.mechanism.supports_compression):
            raise ValueError(
                f"{self.name!r} composes {type(self.mechanism).__name__} with "
                f"{type(self.aggregation).__name__}, but an LDP mechanism "
                "releases a full R^d vector per client — its noise is drawn "
                "BEFORE aggregation, so there is no sound compressed release "
                "(DESIGN.md §16).  Use CentralGaussian (noise is added to the "
                "compressed aggregate) or NoPrivacy, or drop the compression "
                "layer.")

    @property
    def is_private(self):
        """Whether the composed release carries a DP guarantee (the mechanism's)."""
        return self.mechanism.is_private

    @property
    def supports_static_count(self):
        """False for weighted aggregation: the moment count is a weight sum, not M."""
        return not self.aggregation.is_weighted

    @property
    def needs_round_index(self):
        """True when the mechanism is a genuinely varying NoiseSchedule —
        the engines thread the round index t into the round calls only then,
        so every fixed-noise composition keeps its exact pre-§17 trace."""
        return getattr(self.mechanism, "is_round_indexed", False)

    def _mech_at(self, t):
        """The mechanism executing this round: ``at_round(t)`` resolution for
        round-indexed mechanisms (traced sigma(t)), the mechanism itself —
        or a constant schedule's inner — otherwise."""
        if self.needs_round_index:
            if t is None:
                raise ValueError(
                    f"{self.name!r} carries a round-indexed noise schedule "
                    "but the engine did not thread the round index t into "
                    "this call")
            return self.mechanism.at_round(t)
        return self.mechanism.at_round(None)

    def comm_floats(self, d: int) -> int:
        """The §16 communication model: floats one client uploads / the round
        collective reduces — the aggregation layer's vector payload (d dense,
        k rand-k, width*depth sketch) + the three scalar moments + any
        psummed scalar extras (PrivUnit's sum_s_hat, the clip-bit count,
        weighted aggregation's client count)."""
        n = self.aggregation.comm_floats(d) + 3
        n += self.mechanism.n_scalar_extras
        if self.step.needs_clip_bits:
            n += 1                      # count_below rides the reduction
        if self.aggregation.is_weighted:
            n += 1                      # n_clients rides next to the weight sum
        return n

    def __getattr__(self, item):
        if item.startswith("__"):
            raise AttributeError(item)
        d = object.__getattribute__(self, "__dict__")
        for layer in ("mechanism", "step", "aggregation"):
            obj = d.get(layer)
            if obj is not None and hasattr(obj, item):
                return getattr(obj, item)
        raise AttributeError(
            f"{type(self).__name__} {d.get('name')!r} has no attribute {item!r}")

    # -- key / clip plumbing ----------------------------------------------

    def _split_keys(self, key):
        """(mechanism key, step extra keys) — the monolithic classes' exact
        splits: none unless the step needs xi and/or clip-bit streams."""
        n = self.step.n_extra_keys(self.mechanism)
        if n == 0:
            return key, ()
        ks = jax.random.split(key, n + 1)
        return ks[0], tuple(ks[i] for i in range(1, n + 1))

    # -- compression plumbing (DESIGN.md §16) -------------------------------

    def _inner_state(self, state):
        """The step's own carry, unwrapped from an EF CompressionCarry."""
        return state.inner if isinstance(state, CompressionCarry) else state

    def _round_plan(self, key, d):
        """This round's shared compression plan: derived from the ROUND key
        (fold_in with COMPRESS_TAG, outside every client-index stream), so
        shards, stream chunks, and the replicated finalize all rebuild the
        identical tables — the precondition for compressed additivity."""
        return self.aggregation.plan(
            jax.random.fold_in(key, compression.COMPRESS_TAG), d)

    def _compress_row_bound(self, clip):
        """L2 re-clip bound for compressed rows: sens_factor * C for private
        mechanisms whose compressor can grow a row (count-sketch); None when
        nothing binds (no clipping, or a contraction compressor)."""
        if not self.mechanism.is_private:
            return None
        sf = self.aggregation.sens_factor
        if sf <= 1.0:
            return None
        return sf * self.mechanism._clip(clip)

    # -- engine interface --------------------------------------------------

    def init_state(self, w):
        """Initial optimizer/clip carry for a run starting from ``w``."""
        inner = self.step.init(w)
        if self.aggregation.uses_error_feedback:
            return CompressionCarry(ef=jnp.zeros_like(w), inner=inner)
        return inner

    def apply_round_stateful(self, key, w, raw_deltas, state, t=None):
        """Stateful dense round: ``apply_round`` threading the optimizer/clip carry."""
        clip = self.step.clip_override(self._inner_state(state))
        k_mech, extra = self._split_keys(key)
        mech_t = self._mech_at(t)
        m = raw_deltas.shape[0]
        if self.aggregation.is_weighted or self.aggregation.is_compressed:
            # weighted and compressed compositions route the dense round
            # through the moment machinery (the reweighting / the compressed
            # partial sum live there).  The compressed route passes mask=None:
            # full participation needs no gate, and the all-ones where pass
            # is an O(M*d) tax the compressed path exists to shed (compression
            # excludes weighted and LDP aggregations, so only the None-aware
            # reductions ever see it); weighted aggregation keeps the ones
            # mask — its mechanisms index the mask directly.
            mask = (None if self.aggregation.is_compressed
                    else jnp.ones((m,), jnp.float32))
            moments = self.local_moments(key, w, raw_deltas, mask, 0, state,
                                         t=t)
            return self.apply_from_moments(key, w, moments, state, t=t)
        with jax.named_scope(spans.RELEASE):
            stats, extras = mech_t.release(k_mech, raw_deltas, clip, float(m))
            if self.step.needs_clip_bits:
                norms = jnp.linalg.norm(raw_deltas, axis=-1)
                extras = dict(extras)
                extras["count_below"] = jnp.sum(
                    (norms <= clip).astype(jnp.float32))
        with jax.named_scope(spans.SERVER_STEP):
            return self.step.apply(extra, w, stats, extras, mech_t, clip,
                                   float(m), state)

    def apply_round(self, key, w, raw_deltas, t=None):
        """One dense server round: ``(key, w, (M, d) raw deltas) -> (w_next, RoundAux)``."""
        if self.step.stateful:
            raise TypeError(f"{self.name} is stateful; use apply_round_stateful")
        w_next, aux, _ = self.apply_round_stateful(key, w, raw_deltas, (), t=t)
        return w_next, aux

    def local_moments(self, key, w, deltas, mask, start, state, t=None):
        """Shard/chunk-local partial sums of this algorithm's release (SUMS, psum-able)."""
        clip = self.step.clip_override(self._inner_state(state))
        mech_t = self._mech_at(t)
        weights = self.aggregation.row_weights(start, deltas.shape[0])
        # split exactly as the dense path does, so per-client randomness
        # (LDP noise rows, PrivUnit keys) is identical on every engine even
        # when the step reserves extra streams (e.g. PrivUnit x adaptive
        # clip).  For the monolithic-parity names this is the raw key
        # (no-split steps) or a key their mechanisms never read (CDP).
        k_mech, _ = self._split_keys(key)
        with jax.named_scope(spans.RELEASE):
            if self.aggregation.is_compressed:
                plan = self._round_plan(key, deltas.shape[-1])
                mom, extras = mech_t.moments(
                    k_mech, deltas, mask, start, clip, weights,
                    compress_fn=self.aggregation.compress_fn(plan),
                    compress_row_bound=self._compress_row_bound(clip))
            else:
                mom, extras = mech_t.moments(k_mech, deltas, mask, start,
                                             clip, weights)
            if self.step.needs_clip_bits:
                norms = jnp.linalg.norm(deltas, axis=-1)
                below = (norms <= clip).astype(jnp.float32)
                extras = dict(extras)
                extras["count_below"] = (jnp.sum(below) if mask is None
                                         else sum_dot(mask, below))
        if self.aggregation.is_weighted:
            # under weighted aggregation mom.count is a weight SUM; the
            # clip-quantile update and any realized-cohort noise need the
            # true participating-CLIENT count (psums additively)
            extras = dict(extras)
            extras["n_clients"] = (jnp.float32(deltas.shape[0])
                                   if mask is None else jnp.sum(mask))
        return mom, extras

    def apply_from_moments(self, key, w, moments, state, t=None):
        """Server update from the globally reduced moments (replicated math)."""
        mom, extras = moments
        inner = self._inner_state(state)
        clip = self.step.clip_override(inner)
        k_mech, extra = self._split_keys(key)
        mech_t = self._mech_at(t)
        # realized cohort size for mechanism noise: the CLIENT count, which
        # weighted compositions carry in extras (mom.count is their weight
        # sum); everywhere else mom.count is exactly it
        m_eff = extras.get("n_clients", mom.count) if isinstance(extras, dict) \
            else mom.count
        if self.aggregation.is_compressed:
            return self._apply_compressed(key, k_mech, extra, w, mom, extras,
                                          clip, m_eff, state, mech_t)
        with jax.named_scope(spans.RELEASE):
            stats, more = mech_t.finalize(k_mech, mom, extras, clip, m_eff)
        if more:
            extras = {**extras, **more}
        with jax.named_scope(spans.SERVER_STEP):
            return self.step.apply(extra, w, stats, extras, mech_t, clip,
                                   mom.count, state)

    def apply_round_sharded(self, key, w, deltas, mask, state, axis_name,
                            m_total=None, t=None):
        """Sharded round with the round index threaded into both halves
        (the base implementation is otherwise unchanged — DESIGN.md §9)."""
        start = jax.lax.axis_index(axis_name) * deltas.shape[0]
        moments = self.local_moments(key, w, deltas, mask, start, state, t=t)
        with jax.named_scope(spans.PSUM):
            moments = jax.lax.psum(moments, axis_name)
        if m_total is not None and self.supports_static_count:
            moments = set_moment_count(moments, m_total)
        return self.apply_from_moments(key, w, moments, state, t=t)

    def _apply_compressed(self, key, k_mech, extra, w, mom, extras, clip,
                          m_eff, state, mech_t):
        """Compressed finalize (DESIGN.md §16): noise in the compressed
        domain -> decompress -> error feedback -> support selection -> step.

        The mechanism's dense ``finalize`` is bypassed — its noise shape is
        (d,) and its agg_sq would be a compressed-domain norm.  Here the
        scalar moments pass through UNCOMPRESSED (they are the dense clipped
        values by construction of the moment path), central noise is added
        per compressed cell with the sens_factor-scaled std, and ``agg_sq``
        is the norm of the actually-applied (d,) estimate.
        """
        inner = self._inner_state(state)
        d = w.shape[-1]
        plan = self._round_plan(key, d)
        with jax.named_scope(spans.RELEASE):
            comp_mean = mom.sum_c / mom.count
            noise = mech_t.compressed_noise(k_mech, comp_mean.shape, clip,
                                            m_eff,
                                            self.aggregation.sens_factor)
            if noise is not None:
                comp_mean = comp_mean + noise
        g = self.aggregation.decompress(comp_mean, plan, d)
        if self.aggregation.uses_error_feedback:
            corrected = g + state.ef
            applied = self.aggregation.select(corrected)
            ef_next = corrected - applied
        else:
            applied = self.aggregation.select(g)
            ef_next = None
        stats = RoundStats(cbar=applied,
                           mean_sq=mom.sum_sq / mom.count,
                           agg_sq=jnp.sum(jnp.square(applied)),
                           mean_sq_clipped=mom.sum_sq_clipped / mom.count)
        with jax.named_scope(spans.SERVER_STEP):
            w_next, aux, inner_next = self.step.apply(
                extra, w, stats, extras, mech_t, clip, mom.count, inner)
        if ef_next is not None:
            return w_next, aux, CompressionCarry(ef=ef_next, inner=inner_next)
        return w_next, aux, inner_next

    # -- accounting --------------------------------------------------------

    def budget(self, delta: float, *, rounds: int, dim: int,
               sampling_q: float = 1.0) -> accounting.PrivacyReport:
        """Privacy budget of a ``rounds``-round run of this composition —
        the mechanism's accounting hook, told whether the step also releases
        the privatized FedEXP numerator (DESIGN.md §11)."""
        if not self.mechanism.is_private:
            raise ValueError(f"{self.name!r} is not a private algorithm")
        if self.step.needs_clip_bits and not self.mechanism.clip_independent_budget:
            # a fixed-sigma mechanism under an adaptive clip override has a
            # sensitivity/noise ratio that MOVES with the traced C; reporting
            # the static-clip_norm figure would be silently unsound
            raise ValueError(
                f"{self.name!r} composes a fixed-noise mechanism with adaptive "
                "clipping: its per-round guarantee tracks the realized clip "
                "threshold and has no static budget.  Use CentralGaussian("
                "z_mult=...) (noise tracks C) or PrivUnitLDP (pure-DP, "
                "C-independent) under AdaptiveClipStep.")
        with_num = self.step.uses_extrapolation and self.mechanism.needs_xi_key
        return self.mechanism.budget(delta, rounds=rounds, dim=dim,
                                     sampling_q=sampling_q,
                                     with_numerator=with_num)


def with_compression(alg: ComposedAlgorithm,
                     aggregation: Aggregation) -> ComposedAlgorithm:
    """A compressed variant of an existing composition (DESIGN.md §16).

    Swaps the aggregation layer and re-runs composition validation (LDP
    mechanisms reject compression with an actionable error), deriving a
    ``<name>+<layer>`` name so benchmark/telemetry output distinguishes the
    variants.  The mechanism and step are untouched — clip thresholds, key
    splits, and the budget accounting are exactly the base composition's.
    """
    if not isinstance(alg, ComposedAlgorithm):
        raise TypeError(
            f"with_compression needs a ComposedAlgorithm, got {type(alg).__name__}")
    if alg.aggregation.is_weighted:
        raise ValueError(
            f"{alg.name!r} uses weighted aggregation; replacing it with "
            f"{type(aggregation).__name__} would silently drop the per-client "
            "weights.  Compose a weighted-and-compressed layer explicitly if "
            "that is intended.")
    if isinstance(aggregation, RandKAggregation):
        tag = f"randk{aggregation.k}"
    elif isinstance(aggregation, CountSketchAggregation):
        tag = f"sketch{aggregation.width}x{aggregation.depth}"
        if aggregation.top_k is not None:
            tag += f"-top{aggregation.top_k}"
        if aggregation.error_feedback:
            tag += "-ef"
    else:
        tag = type(aggregation).__name__.lower()
    return dataclasses.replace(alg, aggregation=aggregation,
                               name=f"{alg.name}+{tag}")


def compose_algorithm(mechanism: PrivacyMechanism, step: GlobalStep,
                      aggregation: Aggregation | None = None,
                      *, name: str | None = None) -> ComposedAlgorithm:
    """Build a ComposedAlgorithm with a derived name when none is given."""
    agg = MeanAggregation() if aggregation is None else aggregation
    if name is None:
        parts = [type(mechanism).__name__.lower(), type(step).__name__.lower()]
        if agg.is_weighted:
            parts.insert(1, "weighted")
        name = "-".join(parts)
    return ComposedAlgorithm(mechanism=mechanism, step=step, aggregation=agg,
                             name=name)
