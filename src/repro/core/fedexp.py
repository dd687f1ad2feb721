"""Federated server algorithms: DP-FedEXP and its baselines.

Each algorithm is a stateless strategy object with

    apply_round(key, w, raw_deltas) -> (w_next, RoundAux)

where ``raw_deltas`` is the (M, d) matrix of *unclipped* local updates
``w_i^{(t-1,tau)} - w^{(t-1)}`` produced by ``repro.fedsim`` (or, in the
datacenter path, the per-client sharded pytrees flattened on the fly).
Client-side randomization (clipping + LDP noise) is executed inside
``apply_round`` with independent per-client keys — mathematically identical to
clients randomizing locally, which is how the privacy guarantee is stated.

Engine contract (DESIGN.md §8): algorithm dataclasses are FROZEN (hashable by
config, so the scan engine caches one compiled program per configuration) and
``RoundAux`` is fixed-shape — optional diagnostics are NaN sentinels, never
None — so a round can live inside ``jax.lax.scan``.  Algorithms that release
through ``fused_clip_aggregate`` carry a ``backend`` field ("auto" routes to
the Pallas kernel on TPU with in-kernel noise where applicable, and to the
tuned jnp path elsewhere).

Implemented algorithms (paper names):
    FedAvg, FedEXP                       -- non-private references
    DP-FedAvg (LDP-Gaussian / CDP)       -- McMahan et al. 2017b
    LDP-FedEXP (Gaussian)                -- Algorithm 1 + Eq. (6)
    LDP-FedEXP (PrivUnit)                -- Algorithm 1 + Eq. (7) / Algorithm 4
    CDP-FedEXP                           -- Algorithm 2 + Eq. (8)
    DP-FedAvg (PrivUnit)                 -- PrivUnit randomizer, eta_g = 1

Composable stack (DESIGN.md §11).  ``make_algorithm`` now builds every
registry name as a ``repro.core.compose.ComposedAlgorithm`` — a mechanism x
aggregation x step composition pinned bit-for-bit against the monolithic
classes below by ``tests/test_compose.py``.  The monolithic classes remain
the executable specification (and direct-construction API) of each
composition; new cross-product names (``ldp-gauss-fedadam``, ``cdp-fedmom``,
``privunit-fedexp-adaptive-clip``) have no monolithic counterpart.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import compose as _compose
from repro.core import mechanisms as mech
from repro.core import stepsize
from repro.core.aggregation import (
    RoundMoments,
    aggregate_stats,
    fused_clip_aggregate,
    materialize_ldp_noise,
    partial_clip_moments,
    raw_moments as _raw_moments,
    sum_dot,
)
from repro.core.algorithm import (
    RoundAux,
    ServerAlgorithm,
    clamp_moment_counts,
    client_keys,
    set_moment_count,
)

__all__ = [
    "RoundAux",
    "ServerAlgorithm",
    "client_keys",
    "FedAvg",
    "FedEXP",
    "DPFedAvgLDPGaussian",
    "LDPFedEXPGaussian",
    "DPFedAvgPrivUnit",
    "LDPFedEXPPrivUnit",
    "DPFedAvgCDP",
    "CDPFedEXP",
    "make_algorithm",
    "list_algorithms",
    "set_moment_count",
    "clamp_moment_counts",
]


# ---------------------------------------------------------------------------
# Non-private references
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FedAvg(ServerAlgorithm):
    """Non-private FedAvg: ``w <- w + mean_i Delta_i`` (McMahan et al. 2017)."""
    name: str = "fedavg"
    is_private: bool = False

    def apply_round(self, key, w, raw_deltas):
        """One dense server round: ``(key, w, (M, d) raw deltas) -> (w_next, RoundAux)``."""
        stats = aggregate_stats(raw_deltas)
        w_next = w + stats.cbar
        return w_next, RoundAux(eta_g=jnp.float32(1.0), update_norm=jnp.linalg.norm(stats.cbar))

    def local_moments(self, key, w, deltas, mask, start, state):
        """Shard/chunk-local partial sums of this algorithm's release (SUMS, psum-able)."""
        return _raw_moments(deltas, mask)

    def apply_from_moments(self, key, w, moments, state):
        """Server update from the globally reduced moments (replicated math)."""
        cbar = moments.sum_c / moments.count
        aux = RoundAux(eta_g=jnp.float32(1.0), update_norm=jnp.linalg.norm(cbar))
        return w + cbar, aux, state


@dataclasses.dataclass(frozen=True)
class FedEXP(ServerAlgorithm):
    """Non-private FedEXP: the adaptive extrapolated step size of Eq. (2)."""
    name: str = "fedexp"
    is_private: bool = False

    def apply_round(self, key, w, raw_deltas):
        """One dense server round: ``(key, w, (M, d) raw deltas) -> (w_next, RoundAux)``."""
        stats = aggregate_stats(raw_deltas)
        eta = stepsize.fedexp(stats.mean_sq, stats.agg_sq)
        return w + eta * stats.cbar, RoundAux(eta_g=eta, update_norm=eta * jnp.linalg.norm(stats.cbar))

    def local_moments(self, key, w, deltas, mask, start, state):
        """Shard/chunk-local partial sums of this algorithm's release (SUMS, psum-able)."""
        return _raw_moments(deltas, mask)

    def apply_from_moments(self, key, w, moments, state):
        """Server update from the globally reduced moments (replicated math)."""
        stats = moments.stats()
        eta = stepsize.fedexp(stats.mean_sq, stats.agg_sq)
        aux = RoundAux(eta_g=eta, update_norm=eta * jnp.linalg.norm(stats.cbar))
        return w + eta * stats.cbar, aux, state


# ---------------------------------------------------------------------------
# LDP — Gaussian mechanism
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DPFedAvgLDPGaussian(ServerAlgorithm):
    """DP-FedAvg under the Gaussian LDP randomizer: per-client clip + noise, eta_g = 1."""
    clip_norm: float
    sigma: float
    name: str = "dp-fedavg-ldp-gauss"
    backend: str = "auto"

    def _release(self, key, raw_deltas):
        return fused_clip_aggregate(raw_deltas, self.clip_norm,
                                    noise_key=key, noise_sigma=self.sigma,
                                    backend=self.backend)

    def apply_round(self, key, w, raw_deltas):
        """One dense server round: ``(key, w, (M, d) raw deltas) -> (w_next, RoundAux)``."""
        stats = self._release(key, raw_deltas)
        return w + stats.cbar, RoundAux(eta_g=jnp.float32(1.0))

    def local_moments(self, key, w, deltas, mask, start, state):
        # Per-client noise rows keyed by global index: the same rows the
        # single-device release materializes for this round key — bit-parity
        # wherever the unsharded backend materializes noise (jnp / kernel).
        # On TPU, unsharded "auto" resolves to kernel-fused, whose in-kernel
        # stream is shard-oblivious (every shard would repeat the same
        # block), so the sharded path always materializes and the TPU-auto
        # comparison is distributional, not bitwise (DESIGN.md §9).
        """Shard/chunk-local partial sums of this algorithm's release (SUMS, psum-able)."""
        noise = materialize_ldp_noise(key, *deltas.shape, self.sigma,
                                      deltas.dtype, start=start)
        return partial_clip_moments(deltas, self.clip_norm, noise,
                                    weight_mask=mask, backend=self.backend)

    def apply_from_moments(self, key, w, moments, state):
        """Server update from the globally reduced moments (replicated math)."""
        return w + moments.sum_c / moments.count, RoundAux(eta_g=jnp.float32(1.0)), state


@dataclasses.dataclass(frozen=True)
class LDPFedEXPGaussian(DPFedAvgLDPGaussian):
    """Algorithm 1 with the bias-corrected step size, Eq. (6)."""

    name: str = "ldp-fedexp-gauss"

    def _stepped(self, w, stats):
        d = w.shape[-1]
        eta = stepsize.ldp_gaussian(stats.mean_sq, stats.agg_sq, d, self.sigma)
        aux = RoundAux(
            eta_g=eta,
            eta_naive=stepsize.naive_noisy(stats.mean_sq, stats.agg_sq),
            eta_target=stepsize.target(stats.mean_sq_clipped, stats.agg_sq),
        )
        return w + eta * stats.cbar, aux

    def apply_round(self, key, w, raw_deltas):
        """One dense server round: ``(key, w, (M, d) raw deltas) -> (w_next, RoundAux)``."""
        return self._stepped(w, self._release(key, raw_deltas))

    def apply_from_moments(self, key, w, moments, state):
        """Server update from the globally reduced moments (replicated math)."""
        w_next, aux = self._stepped(w, moments.stats())
        return w_next, aux, state


# ---------------------------------------------------------------------------
# LDP — PrivUnit
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DPFedAvgPrivUnit(ServerAlgorithm):
    """DP-FedAvg under PrivUnit (direction) x ScalarDP (magnitude), eta_g = 1."""
    clip_norm: float
    eps0: float
    eps1: float
    eps2: float
    dim: int
    name: str = "dp-fedavg-privunit"

    def __post_init__(self):
        object.__setattr__(self, "pu", mech.make_privunit_params(self.dim, self.eps0, self.eps1))
        object.__setattr__(self, "sc", mech.make_scalardp_params(self.eps2, self.clip_norm))

    def _randomize(self, key, raw_deltas, start=0):
        """Per-client clip + PrivUnit release, keys by GLOBAL client index
        (``client_keys``), so shards reproduce their rows of the cohort."""
        m, _ = raw_deltas.shape
        keys = client_keys(key, m, start)
        norms = jnp.linalg.norm(raw_deltas, axis=-1)
        scale = jnp.minimum(1.0, self.clip_norm / jnp.maximum(norms, 1e-12))
        clipped = raw_deltas * scale[:, None]
        released = jax.vmap(lambda k, dlt: mech.privunit_randomize(k, dlt, self.pu, self.sc))(keys, clipped)
        return released, clipped

    def _release(self, key, raw_deltas):
        released, clipped = self._randomize(key, raw_deltas)
        stats = aggregate_stats(released)
        stats.mean_sq_clipped = (
            jnp.sum(jnp.sum(jnp.square(clipped), axis=-1)) / raw_deltas.shape[0])
        return released, stats

    def _released_moments(self, key, deltas, mask, start):
        released, clipped = self._randomize(key, deltas, start)
        released = jnp.where(mask[:, None] > 0, released, 0.0)
        # dots with the mask, not sum(mask * x): bit-parity with the
        # unsharded reference reductions (see _raw_moments)
        mom = RoundMoments(
            sum_c=sum_dot(mask, released),
            sum_sq=sum_dot(mask, jnp.sum(jnp.square(released), axis=-1)),
            sum_sq_clipped=sum_dot(mask, jnp.sum(jnp.square(clipped), axis=-1)),
            count=jnp.sum(mask))
        return released, mom

    def local_moments(self, key, w, deltas, mask, start, state):
        """Shard/chunk-local partial sums of this algorithm's release (SUMS, psum-able)."""
        _, mom = self._released_moments(key, deltas, mask, start)
        return mom

    def apply_round(self, key, w, raw_deltas):
        """One dense server round: ``(key, w, (M, d) raw deltas) -> (w_next, RoundAux)``."""
        _, stats = self._release(key, raw_deltas)
        return w + stats.cbar, RoundAux(eta_g=jnp.float32(1.0))

    def apply_from_moments(self, key, w, moments, state):
        """Server update from the globally reduced moments (replicated math)."""
        return w + moments.sum_c / moments.count, RoundAux(eta_g=jnp.float32(1.0)), state


@dataclasses.dataclass(frozen=True)
class LDPFedEXPPrivUnit(DPFedAvgPrivUnit):
    """Algorithm 1 with the PrivUnit norm-estimation step size, Eq. (7)."""

    name: str = "ldp-fedexp-privunit"

    def _stepped(self, w, stats, mean_s_hat):
        eta = stepsize.ldp_privunit(mean_s_hat, stats.agg_sq)
        aux = RoundAux(
            eta_g=eta,
            eta_naive=stepsize.naive_noisy(stats.mean_sq, stats.agg_sq),
            eta_target=stepsize.target(stats.mean_sq_clipped, stats.agg_sq),
        )
        return w + eta * stats.cbar, aux

    def apply_round(self, key, w, raw_deltas):
        """One dense server round: ``(key, w, (M, d) raw deltas) -> (w_next, RoundAux)``."""
        released, stats = self._release(key, raw_deltas)
        s_hat = jax.vmap(lambda c: mech.estimate_norm_sq(c, self.pu, self.sc))(released)
        return self._stepped(w, stats, jnp.sum(s_hat) / raw_deltas.shape[0])

    def local_moments(self, key, w, deltas, mask, start, state):
        """Shard/chunk-local partial sums of this algorithm's release (SUMS, psum-able)."""
        released, mom = self._released_moments(key, deltas, mask, start)
        s_hat = jax.vmap(lambda c: mech.estimate_norm_sq(c, self.pu, self.sc))(released)
        return mom, {"sum_s_hat": sum_dot(mask, s_hat)}

    def apply_from_moments(self, key, w, moments, state):
        """Server update from the globally reduced moments (replicated math)."""
        mom, extras = moments
        w_next, aux = self._stepped(w, mom.stats(), extras["sum_s_hat"] / mom.count)
        return w_next, aux, state


# ---------------------------------------------------------------------------
# CDP
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DPFedAvgCDP(ServerAlgorithm):
    """DP-FedAvg under central DP: clip-only clients + server noise on the mean."""
    clip_norm: float
    sigma: float           # paper's sigma; server noise std is sigma/sqrt(M)
    num_clients: int
    name: str = "dp-fedavg-cdp"
    backend: str = "auto"

    def _noised_cbar(self, key, cbar):
        """Post-reduction server noise — the ONLY randomness in the CDP
        release, drawn from the replicated round key, so the sharded and
        single-device paths add the identical (d,) draw."""
        d = cbar.shape[-1]
        server_noise = (self.sigma / jnp.sqrt(float(self.num_clients))) * jax.random.normal(key, (d,))
        return cbar + server_noise

    def _release(self, key, raw_deltas):
        stats = fused_clip_aggregate(raw_deltas, self.clip_norm, noise=None,
                                     backend=self.backend)
        return stats, self._noised_cbar(key, stats.cbar)

    def apply_round(self, key, w, raw_deltas):
        """One dense server round: ``(key, w, (M, d) raw deltas) -> (w_next, RoundAux)``."""
        _, cbar = self._release(key, raw_deltas)
        return w + cbar, RoundAux(eta_g=jnp.float32(1.0))

    def local_moments(self, key, w, deltas, mask, start, state):
        """Shard/chunk-local partial sums of this algorithm's release (SUMS, psum-able)."""
        return partial_clip_moments(deltas, self.clip_norm, None,
                                    weight_mask=mask, backend=self.backend)

    def apply_from_moments(self, key, w, moments, state):
        """Server update from the globally reduced moments (replicated math)."""
        cbar = self._noised_cbar(key, moments.sum_c / moments.count)
        return w + cbar, RoundAux(eta_g=jnp.float32(1.0)), state


@dataclasses.dataclass(frozen=True)
class CDPFedEXP(DPFedAvgCDP):
    """Algorithm 2 with the privatized-numerator step size, Eq. (8).

    sigma_xi defaults to the hyperparameter-free d * sigma^2 / M (§3.2).
    """

    sigma_xi: float | None = None
    name: str = "cdp-fedexp"

    def _stepped(self, k_xi, w, cbar, mean_sq_clipped):
        d = w.shape[-1]
        sigma_xi = self.sigma_xi if self.sigma_xi is not None else d * self.sigma**2 / self.num_clients
        xi = sigma_xi * jax.random.normal(k_xi, ())
        agg_sq = jnp.sum(jnp.square(cbar))
        eta = stepsize.cdp(mean_sq_clipped, xi, agg_sq)
        aux = RoundAux(
            eta_g=eta,
            eta_target=stepsize.target(mean_sq_clipped, agg_sq),
        )
        return w + eta * cbar, aux

    def apply_round(self, key, w, raw_deltas):
        """One dense server round: ``(key, w, (M, d) raw deltas) -> (w_next, RoundAux)``."""
        k_noise, k_xi = jax.random.split(key)
        stats, cbar = self._release(k_noise, raw_deltas)
        return self._stepped(k_xi, w, cbar, stats.mean_sq_clipped)

    def apply_from_moments(self, key, w, moments, state):
        """Server update from the globally reduced moments (replicated math)."""
        k_noise, k_xi = jax.random.split(key)
        cbar = self._noised_cbar(k_noise, moments.sum_c / moments.count)
        w_next, aux = self._stepped(k_xi, w, cbar, moments.sum_sq_clipped / moments.count)
        return w_next, aux, state


# ---------------------------------------------------------------------------
# Adaptive clipping (Andrew et al. 2021) x CDP-FedEXP — the combination the
# paper mentions but leaves out "for simplicity"
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CDPFedEXPAdaptiveClip(ServerAlgorithm):
    """CDP-FedEXP with a quantile-tracked clipping threshold.

    Per round: clip at the CURRENT C, release mean + FedEXP numerator with
    noise std scaled as z * C (fixed noise MULTIPLIER z, so the privacy
    guarantee is C-independent), update C from the privatized below-threshold
    fraction. The step-size rule reads the same round's C through sigma_xi =
    d * (zC)^2 / M — everything stays hyperparameter-free except gamma=0.5
    (a universal constant in Andrew et al.).

    The clip threshold is a TRACED scalar that changes every round; the
    kernel backend takes it as a prefetched operand, so no recompiles.
    """

    z_mult: float               # noise multiplier; per-round std = z*C/sqrt(M)
    num_clients: int
    dim: int
    c0: float = 1.0
    gamma: float = 0.5
    clip_lr: float = 0.2
    sigma_b: float = 10.0
    name: str = "cdp-fedexp-adaptive-clip"
    backend: str = "auto"

    def init_state(self, w):
        """Initial optimizer/clip carry for a run starting from ``w``."""
        from repro.core import adaptive_clip as ac
        return ac.init_state(self.c0)

    def _serve(self, key, w, cbar_mean, mean_sq_clipped, count_below, m, state):
        """Replicated server half: noise the mean, pick eta, track the clip.
        ``m`` may be a traced count — every use is value-identical to the
        static shape the unsharded path passes."""
        from repro.core import adaptive_clip as ac
        d = w.shape[-1]
        k_noise, k_xi, k_bit = jax.random.split(key, 3)
        c = state.clip
        sigma = self.z_mult * c                     # paper's sigma, tracking C
        server_noise = (sigma / jnp.sqrt(m)) * jax.random.normal(k_noise, (d,))
        cbar = cbar_mean + server_noise
        sigma_xi = d * sigma**2 / m
        xi = sigma_xi * jax.random.normal(k_xi, ())
        eta = stepsize.cdp(mean_sq_clipped, xi, jnp.sum(jnp.square(cbar)))

        cfg = ac.AdaptiveClipConfig(gamma=self.gamma, lr=self.clip_lr,
                                    sigma_b=self.sigma_b)
        state, _ = ac.update_clip_from_stats(k_bit, state, count_below, m, cfg)
        aux = RoundAux(eta_g=eta, update_norm=c)   # report the clip used
        return w + eta * cbar, aux, state

    def apply_round_stateful(self, key, w, raw_deltas, state):
        """Stateful dense round: ``apply_round`` threading the optimizer/clip carry."""
        m = raw_deltas.shape[0]
        stats = fused_clip_aggregate(raw_deltas, state.clip, None, backend=self.backend)
        norms = jnp.linalg.norm(raw_deltas, axis=-1)
        count_below = jnp.sum((norms <= state.clip).astype(jnp.float32))
        return self._serve(key, w, stats.cbar, stats.mean_sq_clipped,
                           count_below, float(m), state)

    def local_moments(self, key, w, deltas, mask, start, state):
        """Shard/chunk-local partial sums of this algorithm's release (SUMS, psum-able)."""
        mom = partial_clip_moments(deltas, state.clip, None,
                                   weight_mask=mask, backend=self.backend)
        norms = jnp.linalg.norm(deltas, axis=-1)
        below = sum_dot(mask, (norms <= state.clip).astype(jnp.float32))
        return mom, {"count_below": below}

    def apply_from_moments(self, key, w, moments, state):
        """Server update from the globally reduced moments (replicated math)."""
        mom, extras = moments
        return self._serve(key, w, mom.sum_c / mom.count,
                           mom.sum_sq_clipped / mom.count,
                           extras["count_below"], mom.count, state)

    def apply_round(self, key, w, raw_deltas):
        """One dense server round: ``(key, w, (M, d) raw deltas) -> (w_next, RoundAux)``."""
        raise TypeError("stateful algorithm; use apply_round_stateful")


# ---------------------------------------------------------------------------
# FedOpt family (Reddi et al., 2021) — the servers the paper argues against
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DPFedAdamCDP(DPFedAvgCDP):
    """DP-FedAdam: server Adam over the privatized pseudo-gradient.

    Identical privacy release to DP-FedAvg (CDP); the server applies Adam
    with a GLOBAL learning rate ``server_lr`` — the extra hyperparameter
    whose DP-safe tuning the paper identifies as the practical blocker
    (Papernot & Steinke: accounting the tuning can double/triple epsilon).
    Used by the E6 ablation to quantify that sensitivity vs the
    hyperparameter-free CDP-FedEXP.
    """

    server_lr: float = 0.1
    name: str = "dp-fedadam-cdp"

    def __post_init__(self):
        from repro import optim
        object.__setattr__(self, "_opt", optim.adam(lr=self.server_lr))

    def init_state(self, w):
        """Initial optimizer/clip carry for a run starting from ``w``."""
        return self._opt.init(w)

    def apply_round_stateful(self, key, w, raw_deltas, state):
        """Stateful dense round: ``apply_round`` threading the optimizer/clip carry."""
        _, cbar = self._release(key, raw_deltas)
        step, state = self._opt.update(cbar, state)
        return w + step, RoundAux(eta_g=jnp.float32(self.server_lr)), state

    def apply_from_moments(self, key, w, moments, state):
        """Server update from the globally reduced moments (replicated math)."""
        cbar = self._noised_cbar(key, moments.sum_c / moments.count)
        step, state = self._opt.update(cbar, state)
        return w + step, RoundAux(eta_g=jnp.float32(self.server_lr)), state

    def apply_round(self, key, w, raw_deltas):  # stateless misuse guard
        """One dense server round: ``(key, w, (M, d) raw deltas) -> (w_next, RoundAux)``."""
        raise TypeError("DPFedAdamCDP is stateful; use apply_round_stateful")


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def _backend(kw) -> str:
    return kw.get("backend", "auto")


def _gauss_ldp(kw) -> _compose.GaussianLDP:
    return _compose.GaussianLDP(kw["clip_norm"], kw["sigma"], backend=_backend(kw))


def _privunit(kw) -> _compose.PrivUnitLDP:
    return _compose.PrivUnitLDP(kw["clip_norm"], kw["eps0"], kw["eps1"],
                                kw["eps2"], kw["dim"])


def _cdp(kw) -> _compose.CentralGaussian:
    return _compose.CentralGaussian(clip_norm=kw["clip_norm"], sigma=kw["sigma"],
                                    num_clients=kw["num_clients"],
                                    sigma_xi=kw.get("sigma_xi"),
                                    backend=_backend(kw))


def _adaptive_cdp(kw) -> _compose.CentralGaussian:
    return _compose.CentralGaussian(z_mult=kw["z_mult"],
                                    num_clients=kw["num_clients"],
                                    backend=_backend(kw))


def _adaptive_step(kw) -> _compose.AdaptiveClipStep:
    return _compose.AdaptiveClipStep(c0=kw.get("c0", 1.0),
                                     gamma=kw.get("gamma", 0.5),
                                     clip_lr=kw.get("clip_lr", 0.2),
                                     sigma_b=kw.get("sigma_b", 10.0))


def _composed(name: str, mechanism, step) -> _compose.ComposedAlgorithm:
    return _compose.ComposedAlgorithm(mechanism=mechanism, step=step, name=name)


def _schedule(inner, kw) -> _compose.NoiseSchedule:
    return _compose.NoiseSchedule(inner=inner, decay=kw.get("decay", 1.0),
                                  boundaries=tuple(kw.get("boundaries", ())),
                                  scales=tuple(kw.get("scales", ())))


def _perclient_weighted(kw) -> _compose.ComposedAlgorithm:
    # heterogeneous privacy (§17): per-client sigmas from the public epsilons
    # + the matching public inverse-variance aggregation weights
    mechanism = _compose.PerClientGaussian(kw["clip_norm"],
                                           tuple(kw["epsilons"]), kw["delta"],
                                           backend=_backend(kw))
    return _compose.ComposedAlgorithm(
        mechanism=mechanism, step=_compose.FedEXPStep(),
        aggregation=_compose.WeightedAggregation(
            mechanism.inverse_variance_weights()),
        name="ldp-fedexp-perclient")


def _scaffold(kw) -> ServerAlgorithm:
    from repro.core.variance_reduction import DPScaffoldServer
    return DPScaffoldServer(clip_norm=kw["clip_norm"], sigma=kw["sigma"],
                            central=kw["central"],
                            num_clients=kw["num_clients"],
                            tau=kw["tau"], eta_l=kw["eta_l"])


# Every registry name is a (mechanism, step) composition under the uniform
# MeanAggregation — the first ten reproduce the monolithic classes above
# bit-for-bit (tests/test_compose.py); the rest are cross-product names the
# inheritance design could not express.  README.md tabulates the mapping.
_FACTORIES: dict[str, Callable[..., ServerAlgorithm]] = {
    "fedavg": lambda **kw: _composed(
        "fedavg", _compose.NoPrivacy(), _compose.FixedEta()),
    "fedexp": lambda **kw: _composed(
        "fedexp", _compose.NoPrivacy(), _compose.FedEXPStep()),
    "dp-fedavg-ldp-gauss": lambda **kw: _composed(
        "dp-fedavg-ldp-gauss", _gauss_ldp(kw), _compose.FixedEta()),
    "ldp-fedexp-gauss": lambda **kw: _composed(
        "ldp-fedexp-gauss", _gauss_ldp(kw), _compose.FedEXPStep()),
    "dp-fedavg-privunit": lambda **kw: _composed(
        "dp-fedavg-privunit", _privunit(kw), _compose.FixedEta()),
    "ldp-fedexp-privunit": lambda **kw: _composed(
        "ldp-fedexp-privunit", _privunit(kw), _compose.FedEXPStep()),
    "dp-fedavg-cdp": lambda **kw: _composed(
        "dp-fedavg-cdp", _cdp(kw), _compose.FixedEta()),
    "cdp-fedexp": lambda **kw: _composed(
        "cdp-fedexp", _cdp(kw), _compose.FedEXPStep()),
    "dp-fedadam-cdp": lambda **kw: _composed(
        "dp-fedadam-cdp", _cdp(kw),
        _compose.ServerOpt(kind="adam", lr=kw.get("server_lr", 0.1))),
    "cdp-fedexp-adaptive-clip": lambda **kw: _composed(
        "cdp-fedexp-adaptive-clip", _adaptive_cdp(kw), _adaptive_step(kw)),
    # -- cross-product compositions with no monolithic counterpart ---------
    "ldp-gauss-fedadam": lambda **kw: _composed(
        "ldp-gauss-fedadam", _gauss_ldp(kw),
        _compose.ServerOpt(kind="adam", lr=kw.get("server_lr", 0.1))),
    "cdp-fedmom": lambda **kw: _composed(
        "cdp-fedmom", _cdp(kw),
        _compose.ServerOpt(kind="momentum", lr=kw.get("server_lr", 1.0),
                           beta1=kw.get("server_beta", 0.9))),
    "privunit-fedexp-adaptive-clip": lambda **kw: _composed(
        "privunit-fedexp-adaptive-clip",
        _privunit({**kw, "clip_norm": kw.get("clip_norm", kw.get("c0", 1.0))}),
        _adaptive_step(kw)),
    # -- §17: heterogeneous privacy, noise schedules, control variates ------
    "ldp-fedexp-perclient": lambda **kw: _perclient_weighted(kw),
    "ldp-fedexp-schedule": lambda **kw: _composed(
        "ldp-fedexp-schedule", _schedule(_gauss_ldp(kw), kw),
        _compose.FedEXPStep()),
    "cdp-fedexp-schedule": lambda **kw: _composed(
        "cdp-fedexp-schedule", _schedule(_cdp(kw), kw),
        _compose.FedEXPStep()),
    "dp-scaffold": lambda **kw: _scaffold(kw),
}


def list_algorithms() -> list[str]:
    """Sorted names of every registered server algorithm."""
    return sorted(_FACTORIES)


def make_algorithm(name: str, **kwargs) -> ServerAlgorithm:
    """Build a registered server algorithm by name.

    Args:
      name: one of ``list_algorithms()`` (unknown names raise KeyError
        enumerating the registry).
      **kwargs: the composition's knobs (``clip_norm``, ``sigma``,
        ``num_clients``, ``eps0/1/2``, ``dim``, ``z_mult``, ``server_lr``,
        ... — see the README registry table).

    Returns:
      A frozen, hashable ``ServerAlgorithm`` (a ``ComposedAlgorithm``)
      pinned bit-for-bit against the monolithic classes for the first ten
      names.
    """
    if name not in _FACTORIES:
        raise KeyError(f"unknown algorithm {name!r}; valid names: "
                       f"{', '.join(list_algorithms())}")
    return _FACTORIES[name](**kwargs)
