"""Property-based tests (hypothesis) for the system's invariants."""
import math

import pytest

hypothesis = pytest.importorskip("hypothesis")

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.core import accounting as acc
from repro.core import mechanisms as mech
from repro.core import stepsize
from repro.core.aggregation import aggregate_stats
from repro.core.clipping import clip_batch, clip_by_l2, clip_tree, global_l2_norm_tree

SETTINGS = dict(deadline=None, max_examples=25,
                suppress_health_check=[hypothesis.HealthCheck.too_slow])

finite_f = st.floats(-1e3, 1e3, allow_nan=False, allow_infinity=False, width=32)


@st.composite
def update_matrix(draw, max_m=16, max_d=32):
    m = draw(st.integers(1, max_m))
    d = draw(st.integers(2, max_d))
    seed = draw(st.integers(0, 2**31 - 1))
    scale = draw(st.floats(1e-3, 1e3))
    return np.float32(scale) * np.asarray(
        jax.random.normal(jax.random.PRNGKey(seed), (m, d)))


class TestClipping:
    @given(u=update_matrix(), c=st.floats(1e-3, 1e2))
    @settings(**SETTINGS)
    def test_norm_bounded_and_direction_preserved(self, u, c):
        clipped = np.asarray(clip_batch(jnp.asarray(u), c))
        norms = np.linalg.norm(clipped, axis=-1)
        assert np.all(norms <= c * (1 + 1e-5))
        # direction preserved: clipped is a nonnegative multiple of u
        for i in range(u.shape[0]):
            nu = np.linalg.norm(u[i])
            if nu > 1e-6:
                cos = np.dot(clipped[i], u[i]) / (np.linalg.norm(clipped[i]) * nu + 1e-12)
                assert cos > 1 - 1e-4

    @given(u=update_matrix(), c=st.floats(1e-3, 1e2))
    @settings(**SETTINGS)
    def test_idempotent(self, u, c):
        once = clip_batch(jnp.asarray(u), c)
        twice = clip_batch(once, c)
        np.testing.assert_allclose(np.asarray(once), np.asarray(twice),
                                   rtol=1e-5, atol=1e-6)

    @given(u=update_matrix(max_m=4), c=st.floats(1e-2, 1e2))
    @settings(**SETTINGS)
    def test_tree_clip_matches_flat(self, u, c):
        """Clipping a pytree by global norm == clipping its flat concat."""
        tree = {"a": jnp.asarray(u[:, : u.shape[1] // 2]),
                "b": jnp.asarray(u[:, u.shape[1] // 2:])}
        clipped_tree, nrm = clip_tree(tree, c)
        flat = jnp.concatenate([u.reshape(-1)[: u.size]])
        want_norm = float(jnp.linalg.norm(jnp.asarray(u)))
        assert abs(float(nrm) - want_norm) < 1e-3 * max(1.0, want_norm)
        got = np.concatenate([np.asarray(clipped_tree["a"]).ravel(),
                              np.asarray(clipped_tree["b"]).ravel()])
        want = np.asarray(clip_by_l2(jnp.asarray(u).ravel(), c))
        np.testing.assert_allclose(np.sort(np.abs(got)), np.sort(np.abs(want)),
                                   rtol=1e-4, atol=1e-5)


class TestStepsizeInvariants:
    @given(u=update_matrix())
    @settings(**SETTINGS)
    def test_fedexp_ge_one_and_scale_invariant(self, u):
        s = aggregate_stats(jnp.asarray(u))
        eta = float(stepsize.fedexp(s.mean_sq, s.agg_sq))
        assert eta >= 1.0
        # eta is invariant to scaling all updates by the same c > 0
        s2 = aggregate_stats(jnp.asarray(3.7 * u))
        eta2 = float(stepsize.fedexp(s2.mean_sq, s2.agg_sq))
        assert abs(eta - eta2) < 1e-2 * max(1.0, eta)

    @given(u=update_matrix(), dim=st.integers(2, 1000), sigma=st.floats(1e-3, 10))
    @settings(**SETTINGS)
    def test_ldp_rule_ge_one(self, u, dim, sigma):
        s = aggregate_stats(jnp.asarray(u))
        eta = float(stepsize.ldp_gaussian(s.mean_sq, s.agg_sq, dim, sigma))
        assert eta >= 1.0
        assert math.isfinite(eta)

    @given(u=update_matrix(), xi=finite_f)
    @settings(**SETTINGS)
    def test_cdp_rule_ge_one(self, u, xi):
        s = aggregate_stats(jnp.asarray(u))
        eta = float(stepsize.cdp(s.mean_sq, jnp.float32(xi), s.agg_sq))
        assert eta >= 1.0


class TestAggregationInvariants:
    @given(u=update_matrix())
    @settings(**SETTINGS)
    def test_cauchy_schwarz(self, u):
        """||cbar||^2 <= mean ||c_i||^2 (why eta >= 1 is achievable)."""
        s = aggregate_stats(jnp.asarray(u))
        assert float(s.agg_sq) <= float(s.mean_sq) * (1 + 1e-4) + 1e-6

    @given(u=update_matrix())
    @settings(**SETTINGS)
    def test_mean_linearity(self, u):
        s = aggregate_stats(jnp.asarray(u))
        np.testing.assert_allclose(np.asarray(s.cbar), u.mean(0), rtol=1e-4, atol=1e-4)


class TestAccountingInvariants:
    @given(mu=st.floats(0.01, 50), delta=st.floats(1e-9, 0.4))
    @settings(**SETTINGS)
    def test_gdp_roundtrip(self, mu, delta):
        eps = acc.gdp_epsilon(mu, delta)
        if math.isfinite(eps) and eps > 0.0:
            assert abs(acc.gdp_delta(mu, eps) - delta) < 1e-6 * max(1.0, delta)
        else:
            # eps = 0 already satisfies the target delta
            assert acc.gdp_delta(mu, 0.0) <= delta * (1 + 1e-9)

    @given(c=st.floats(0.01, 10), s1=st.floats(0.1, 5), ratio=st.floats(1.1, 10))
    @settings(**SETTINGS)
    def test_eps_monotone_in_sigma(self, c, s1, ratio):
        e_low_noise = acc.ldp_gaussian_budget(c, s1, 1e-5).eps_numerical
        e_high_noise = acc.ldp_gaussian_budget(c, s1 * ratio, 1e-5).eps_numerical
        assert e_high_noise <= e_low_noise + 1e-9


class TestScalarDPProperties:
    @given(r=st.floats(0.0, 1.0), eps2=st.floats(0.5, 6.0), seed=st.integers(0, 2**31 - 1))
    @settings(**SETTINGS)
    def test_output_always_on_lattice(self, r, eps2, seed):
        sc = mech.make_scalardp_params(eps2, 1.0)
        out = float(mech.scalardp_magnitude(jax.random.PRNGKey(seed), jnp.float32(r), sc))
        j = out / sc.a + sc.b
        assert abs(j - round(j)) < 1e-3
        assert 0 <= round(j) <= sc.k

    @given(eps2=st.floats(0.5, 6.0))
    @settings(**SETTINGS)
    def test_debias_constants_positive(self, eps2):
        sc = mech.make_scalardp_params(eps2, 1.0)
        assert sc.a > 0 and sc.b >= 0 and sc.c1 > 0 and sc.c3 > 0


class TestSafePspec:
    @given(dim=st.integers(1, 4096), axes=st.sampled_from(["model", "data", None]))
    @settings(**SETTINGS)
    def test_divisibility_respected(self, dim, axes):
        from repro.launch.mesh import make_test_mesh
        from repro.launch.rules import safe_pspec
        mesh = make_test_mesh()
        rules = {"x": axes}
        spec = safe_pspec((dim,), ("x",), rules, mesh)
        # axis sizes are 1 here, so everything divides; just structural checks
        assert len(spec) <= 1

    def test_drops_non_dividing_axis(self):
        from repro.launch.rules import safe_pspec
        # simulate 16-way axis with a fake mesh via devices reshape is not
        # possible on 1 CPU; use the sizes logic directly instead.
        from repro.launch import rules as r
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh()
        sizes = r._axis_sizes(mesh)
        assert sizes == {"data": 1, "model": 1}
