"""Step-size rules (the paper's core): Eqs. (2)/(3)/(5)/(6)/(7)/(8)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mechanisms as mech
from repro.core import stepsize
from repro.core.aggregation import aggregate_stats, fused_clip_aggregate


def _heterogeneous_updates(key, m=256, d=64):
    """Updates with a shared mean + strong per-client spread (eta_target >> 1)."""
    k1, k2 = jax.random.split(key)
    shared = jax.random.normal(k1, (d,)) * 0.1
    spread = jax.random.normal(k2, (m, d))
    return shared[None, :] + spread


class TestRules:
    def test_fedexp_ge_one(self):
        u = _heterogeneous_updates(jax.random.PRNGKey(0))
        s = aggregate_stats(u)
        eta = stepsize.fedexp(s.mean_sq, s.agg_sq)
        assert float(eta) >= 1.0

    def test_fedexp_heterogeneity_drives_eta(self):
        """Diverse updates -> large eta; identical updates -> eta = 1."""
        u = _heterogeneous_updates(jax.random.PRNGKey(1))
        s = aggregate_stats(u)
        assert float(stepsize.fedexp(s.mean_sq, s.agg_sq)) > 5.0

        same = jnp.tile(u[:1], (u.shape[0], 1))
        s2 = aggregate_stats(same)
        # mean_sq (row norms, then a sum) and agg_sq (||column mean||^2) are
        # reduced in different orders, so for identical rows they agree only
        # to f32 rounding: eta = 1.0000012 under XLA:CPU (jax 0.9)
        assert float(stepsize.fedexp(s2.mean_sq, s2.agg_sq)) == pytest.approx(1.0, rel=1e-5)

    def test_naive_biased_up_corrected_close(self):
        """Fig. 2: naive rule is inflated by d*sigma^2; Eq. (6) tracks target."""
        m, d, sigma, c_clip = 512, 2000, 0.7, 1.0
        u = _heterogeneous_updates(jax.random.PRNGKey(2), m, d)
        # independent key: fold_in(k, 1) aliases split(k)[1], which would
        # correlate the noise with the spread and bias the cross term.
        noise = sigma * jax.random.normal(jax.random.PRNGKey(9002), (m, d))
        stats = fused_clip_aggregate(u, c_clip, noise)

        eta_naive = float(stepsize.naive_noisy(stats.mean_sq, stats.agg_sq))
        eta_corr = float(stepsize.ldp_gaussian(stats.mean_sq, stats.agg_sq, d, sigma))
        eta_target = float(stepsize.target(stats.mean_sq_clipped, stats.agg_sq))

        # naive >> target (bias d*sigma^2 ~ 980 vs ||Delta||^2 <= 1)
        assert eta_naive > 10 * max(eta_target, 1.0)
        # the corrected NUMERATOR is an unbiased estimate of mean||Delta||^2:
        # |(mean||c||^2 - d sigma^2) - mean||Delta||^2| = O(sqrt(d/M) sigma^2)
        num_corr = float(stats.mean_sq) - d * sigma**2
        num_true = float(stats.mean_sq_clipped)
        assert abs(num_corr - num_true) < 5.0 * np.sqrt(d / m) * sigma**2
        # and the rule clamps at 1 when the target is below 1 (Eq. 6)
        expected = max(1.0, num_corr / float(stats.agg_sq))
        assert abs(eta_corr - expected) < 1e-4 * max(1.0, expected)

    def test_ldp_gaussian_clamps_at_one(self):
        # heavily over-corrected numerator -> max{1, negative} = 1
        eta = stepsize.ldp_gaussian(jnp.float32(1.0), jnp.float32(1.0), 1000, 10.0)
        assert float(eta) == 1.0

    def test_cdp_rule_matches_target_when_xi_zero(self):
        u = _heterogeneous_updates(jax.random.PRNGKey(3))
        stats = fused_clip_aggregate(u, 1.0, None)
        eta = stepsize.cdp(stats.mean_sq_clipped, jnp.float32(0.0), stats.agg_sq)
        want = max(1.0, float(stats.mean_sq_clipped / stats.agg_sq))
        assert float(eta) == np.float32(want)

    def test_privunit_rule(self):
        """Eq. (7) numerator from Algorithm-4 estimates tracks the target."""
        m, d, c_clip = 256, 64, 1.0
        pu = mech.make_privunit_params(d, 2.0, 2.0)
        sc = mech.make_scalardp_params(2.0, c_clip)
        u = _heterogeneous_updates(jax.random.PRNGKey(4), m, d)
        norms = jnp.linalg.norm(u, axis=-1)
        clipped = u * jnp.minimum(1.0, c_clip / norms)[:, None]
        keys = jax.random.split(jax.random.PRNGKey(5), m)
        released = jax.vmap(lambda k, x: mech.privunit_randomize(k, x, pu, sc))(keys, clipped)
        s_hat = jax.vmap(lambda c: mech.estimate_norm_sq(c, pu, sc))(released)
        stats = aggregate_stats(released)
        eta = float(stepsize.ldp_privunit(jnp.mean(s_hat), stats.agg_sq))
        eta_target = float(stepsize.target(
            jnp.mean(jnp.sum(clipped**2, -1)), stats.agg_sq))
        assert eta >= 1.0
        assert abs(eta - eta_target) / eta_target < 0.6


class TestAdaptivity:
    def test_eta_grows_with_m(self):
        """Remark 3.1: effective noise d*sigma^2/M shrinks with M -> eta grows."""
        d, sigma = 500, 0.7
        etas = []
        for m in (16, 128, 1024):
            u = _heterogeneous_updates(jax.random.PRNGKey(7), m, d) * 0.05
            noise = sigma * jax.random.normal(jax.random.PRNGKey(8), (m, d))
            stats = fused_clip_aggregate(u, 1.0, noise)
            etas.append(float(stepsize.ldp_gaussian(stats.mean_sq, stats.agg_sq, d, sigma)))
        assert etas[0] <= etas[1] <= etas[2]
        assert etas[2] > 2.0
