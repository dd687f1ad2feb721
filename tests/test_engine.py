"""Scan-engine equivalence + in-kernel noise statistics (DESIGN.md §8).

Three layers of evidence that the compiled engine is the same algorithm:
  1. scan == eager, bit-for-bit, for every registered algorithm (same keys),
     including the stateful ones and chunked compilation.
  2. The Pallas kernel path == the jnp reference within tolerance for every
     fused_clip_aggregate call-site configuration (no noise / materialized
     noise / traced clip threshold / bf16 / ragged shapes).
  3. The in-kernel PRNG draws N(0, sigma^2) noise (moment + correlation
     checks) and the fused-noise pipeline agrees distributionally with the
     materialized-noise pipeline.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import fused_clip_aggregate
from repro.core.fedexp import make_algorithm
from repro.data.synthetic import distance_to_opt, linreg_loss, make_synthetic_linreg
from repro.fedsim import EngineSpec, FederatedSession, TrainSpec
from repro.kernels.dp_aggregate.ops import dp_aggregate, generate_ldp_noise

M, D, TAU, ETA_L, ROUNDS = 48, 24, 4, 0.1, 6

ALG_KWARGS = {
    "fedavg": {},
    "fedexp": {},
    "dp-fedavg-ldp-gauss": dict(clip_norm=0.3, sigma=0.21),
    "ldp-fedexp-gauss": dict(clip_norm=0.3, sigma=0.21),
    "dp-fedavg-privunit": dict(clip_norm=0.3, eps0=2.0, eps1=2.0, eps2=2.0, dim=D),
    "ldp-fedexp-privunit": dict(clip_norm=0.3, eps0=2.0, eps1=2.0, eps2=2.0, dim=D),
    "dp-fedavg-cdp": dict(clip_norm=0.3, sigma=0.2, num_clients=M),
    "cdp-fedexp": dict(clip_norm=0.3, sigma=0.2, num_clients=M),
    "dp-fedadam-cdp": dict(clip_norm=0.3, sigma=0.2, num_clients=M, server_lr=0.05),
    "cdp-fedexp-adaptive-clip": dict(z_mult=0.5, num_clients=M, dim=D),
}

# Materialized-LDP-noise runs: XLA:CPU (jax 0.9) fuses the noise draw and the
# clip differently in the scan body than in the one-round eager program, so
# weights differ by a few f32 ULP (<= 2.4e-7 at |w| ~ 0.5) and eta, a ratio of
# reductions fed back through the rounds, by ~1e-6 relative.
ULP_TOL = {"dp-fedavg-ldp-gauss": dict(rtol=1e-5, atol=1e-6),
           "ldp-fedexp-gauss": dict(rtol=1e-5, atol=1e-6)}


@pytest.fixture(scope="module")
def problem():
    data = make_synthetic_linreg(jax.random.PRNGKey(3), M, D)
    return data, jnp.zeros(D)


def _run(problem, name, engine, **kw):
    data, w0 = problem
    alg = make_algorithm(name, **ALG_KWARGS[name])
    session = FederatedSession(alg, linreg_loss, w0, data.client_batches(),
                               train=TrainSpec(rounds=ROUNDS, tau=TAU, eta_l=ETA_L),
                               engine=EngineSpec(engine=engine, **kw),
                               eval_fn=distance_to_opt(data.w_star))
    return session.run(jax.random.PRNGKey(11))


class TestScanEagerEquivalence:
    @pytest.mark.parametrize("name", sorted(ALG_KWARGS))
    def test_scan_matches_eager_exactly(self, problem, name):
        r_e = _run(problem, name, "eager")
        r_s = _run(problem, name, "scan")
        hist_tol = ULP_TOL.get(name, dict(rtol=0, atol=0))
        # XLA compiles adam's rsqrt(v)+eps divide differently inside the
        # scan body — a 1-ULP wobble on the weights; everything upstream of
        # the optimizer (histories) is still bit-exact below.
        w_tol = (dict(rtol=0, atol=1e-7) if name == "dp-fedadam-cdp"
                 else hist_tol)
        for field in ("final_w", "last_w"):
            np.testing.assert_allclose(np.asarray(getattr(r_e, field)),
                                       np.asarray(getattr(r_s, field)),
                                       err_msg=field, **w_tol)
        for field in ("eta_history", "metric_history", "eta_naive_history"):
            np.testing.assert_allclose(np.asarray(getattr(r_e, field)),
                                       np.asarray(getattr(r_s, field)),
                                       err_msg=field, **hist_tol)

    @pytest.mark.parametrize("name", ["ldp-fedexp-gauss", "cdp-fedexp-adaptive-clip",
                                      "dp-fedadam-cdp"])
    def test_chunked_matches_unchunked(self, problem, name):
        r_1 = _run(problem, name, "scan")
        r_c = _run(problem, name, "scan", chunk_rounds=2)
        # same 1-ULP adam and LDP-noise caveats as above (chunk length
        # changes the program)
        tol = ULP_TOL.get(name, dict(rtol=0, atol=0))
        w_tol = dict(rtol=0, atol=1e-7) if name == "dp-fedadam-cdp" else tol
        np.testing.assert_allclose(np.asarray(r_1.final_w), np.asarray(r_c.final_w),
                                   **w_tol)
        np.testing.assert_allclose(np.asarray(r_1.eta_history),
                                   np.asarray(r_c.eta_history), **tol)

    def test_unroll_is_bit_identical(self, problem):
        r_1 = _run(problem, "cdp-fedexp", "scan", scan_unroll=1)
        r_3 = _run(problem, "cdp-fedexp", "scan", scan_unroll=3)
        np.testing.assert_array_equal(np.asarray(r_1.final_w), np.asarray(r_3.final_w))

    def test_short_run_tail(self, problem):
        """rounds < avg_last: the iterate average covers all iterates."""
        data, w0 = problem
        alg = make_algorithm("fedexp")
        train = TrainSpec(rounds=1, tau=TAU, eta_l=ETA_L)
        key = jax.random.PRNGKey(1)
        r_e = FederatedSession(alg, linreg_loss, w0, data.client_batches(),
                               train=train,
                               engine=EngineSpec(engine="eager")).run(key)
        r_s = FederatedSession(alg, linreg_loss, w0, data.client_batches(),
                               train=train).run(key)
        np.testing.assert_array_equal(np.asarray(r_e.final_w), np.asarray(r_s.final_w))


class TestBatchedEngine:
    def test_batched_matches_single_runs(self, problem):
        data, w0 = problem
        alg = make_algorithm("ldp-fedexp-gauss", **ALG_KWARGS["ldp-fedexp-gauss"])
        keys = jnp.stack([jax.random.PRNGKey(21), jax.random.PRNGKey(22)])
        session = FederatedSession(alg, linreg_loss, w0, data.client_batches(),
                                   train=TrainSpec(rounds=ROUNDS, tau=TAU,
                                                   eta_l=ETA_L),
                                   eval_fn=distance_to_opt(data.w_star))
        rb = session.run_batched(keys)
        assert rb.final_w.shape == (2, D)
        assert rb.metric_history.shape == (2, ROUNDS)
        for s in range(2):
            r = session.run(keys[s])
            # vmap may reorder reductions (batched BLAS): tolerance, not exact
            np.testing.assert_allclose(np.asarray(rb.final_w[s]),
                                       np.asarray(r.final_w), rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(np.asarray(rb.eta_history[s]),
                                       np.asarray(r.eta_history), rtol=1e-4)

    def test_batched_w0_and_data(self, problem):
        data, _ = problem
        alg = make_algorithm("fedexp")
        keys = jnp.stack([jax.random.PRNGKey(0), jax.random.PRNGKey(1)])
        w0s = jnp.stack([jnp.zeros(D), 0.1 * jnp.ones(D)])
        batches = {k: jnp.stack([v, v]) for k, v in data.client_batches().items()}
        session = FederatedSession(alg, linreg_loss, w0s, batches,
                                   train=TrainSpec(rounds=3, tau=TAU, eta_l=ETA_L))
        rb = session.run_batched(keys, batched_w0=True, batched_data=True)
        assert rb.final_w.shape == (2, D)
        # different inits must give different trajectories
        assert not np.allclose(np.asarray(rb.final_w[0]), np.asarray(rb.final_w[1]))


class TestKernelVsJnp:
    """Every fused_clip_aggregate call-site configuration, kernel vs jnp."""

    def _check(self, stats_k, stats_j, rtol=2e-5, atol=2e-5):
        np.testing.assert_allclose(np.asarray(stats_k.cbar), np.asarray(stats_j.cbar),
                                   rtol=rtol, atol=atol)
        np.testing.assert_allclose(float(stats_k.mean_sq), float(stats_j.mean_sq),
                                   rtol=rtol, atol=atol)
        np.testing.assert_allclose(float(stats_k.mean_sq_clipped),
                                   float(stats_j.mean_sq_clipped), rtol=rtol, atol=atol)

    @pytest.mark.parametrize("m,d", [(8, 128), (24, 300), (10, 64), (33, 200)])
    @pytest.mark.parametrize("with_noise", [False, True])
    def test_shapes_and_noise(self, m, d, with_noise):
        key = jax.random.PRNGKey(m * d)
        u = 2.0 * jax.random.normal(key, (m, d))
        noise = (0.5 * jax.random.normal(jax.random.fold_in(key, 1), (m, d))
                 if with_noise else None)
        self._check(fused_clip_aggregate(u, 1.0, noise, backend="kernel"),
                    fused_clip_aggregate(u, 1.0, noise, backend="jnp"))

    def test_traced_clip_norm(self):
        """The adaptive-clip call site: clip is a traced per-round scalar."""
        u = jax.random.normal(jax.random.PRNGKey(5), (16, 96))

        from functools import partial

        @partial(jax.jit, static_argnames=("backend",))
        def release(c, backend):
            s = fused_clip_aggregate(u, c, None, backend=backend)
            return s.cbar, s.mean_sq_clipped

        for c in (0.25, 1.0, 4.0):
            ck, mk = release(jnp.float32(c), "kernel")
            cj, mj = release(jnp.float32(c), "jnp")
            np.testing.assert_allclose(np.asarray(ck), np.asarray(cj),
                                       rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(float(mk), float(mj), rtol=2e-5)

    def test_noise_key_routing(self):
        """noise_key + backend='kernel' materializes the SAME noise as jnp."""
        u = jax.random.normal(jax.random.PRNGKey(6), (16, 128))
        k = jax.random.PRNGKey(77)
        sk = fused_clip_aggregate(u, 0.5, noise_key=k, noise_sigma=0.3,
                                  backend="kernel")
        sj = fused_clip_aggregate(u, 0.5, noise_key=k, noise_sigma=0.3,
                                  backend="jnp")
        self._check(sk, sj)

    def test_bf16(self):
        u = jax.random.normal(jax.random.PRNGKey(7), (16, 128)).astype(jnp.bfloat16)
        sk = fused_clip_aggregate(u, 0.5, backend="kernel")
        sj = fused_clip_aggregate(u, 0.5, backend="jnp")
        np.testing.assert_allclose(np.asarray(sk.cbar, np.float32),
                                   np.asarray(sj.cbar, np.float32),
                                   rtol=2e-2, atol=2e-2)


class TestInKernelNoise:
    SIGMA = 1.3

    def test_moments(self):
        """Kernel-drawn noise matches N(0, sigma^2): mean, variance, and
        cross-row/column correlations within statistical tolerance."""
        m, d = 512, 256
        z = np.asarray(generate_ldp_noise(m, d, jax.random.PRNGKey(123), self.SIGMA))
        n = z.size
        assert abs(z.mean()) < 5 * self.SIGMA / np.sqrt(n)          # CLT bound
        np.testing.assert_allclose(z.std(), self.SIGMA, rtol=0.02)
        # fourth moment (kurtosis) distinguishes Gaussian from uniform bits
        np.testing.assert_allclose((z**4).mean(), 3 * self.SIGMA**4, rtol=0.1)
        # adjacent-lane and adjacent-row correlations ~ 0
        for a, b in ((z[:, :-1], z[:, 1:]), (z[:-1], z[1:])):
            corr = np.mean(a * b) / self.SIGMA**2
            assert abs(corr) < 5 / np.sqrt(a.size)

    def test_distinct_keys_distinct_noise(self):
        z1 = generate_ldp_noise(32, 128, jax.random.PRNGKey(1), 1.0)
        z2 = generate_ldp_noise(32, 128, jax.random.PRNGKey(2), 1.0)
        z1b = generate_ldp_noise(32, 128, jax.random.PRNGKey(1), 1.0)
        assert not np.allclose(np.asarray(z1), np.asarray(z2))
        np.testing.assert_array_equal(np.asarray(z1), np.asarray(z1b))

    def test_fused_pipeline_matches_kernel_noise_oracle(self):
        """dp_aggregate(fused) == dp_aggregate(materialized oracle noise)."""
        m, d = 40, 192
        key = jax.random.PRNGKey(9)
        u = jax.random.normal(key, (m, d))
        oracle = generate_ldp_noise(m, d, key, self.SIGMA)
        got = dp_aggregate(u, 0.5, noise_key=key, noise_sigma=self.SIGMA)
        want = dp_aggregate(u, 0.5, oracle)
        np.testing.assert_allclose(np.asarray(got.cbar), np.asarray(want.cbar),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(float(got.mean_sq), float(want.mean_sq), rtol=1e-5)

    def test_fused_pipeline_distribution_matches_materialized(self):
        """Full-pipeline distributional agreement: over repeated keys, the
        released mean_sq under in-kernel noise matches the materialized-noise
        path — both concentrate on mean_sq_clipped + d*sigma^2."""
        m, d, sigma = 64, 128, 0.7
        u = jax.random.normal(jax.random.PRNGKey(31), (m, d))
        fused, mat = [], []
        for i in range(8):
            k = jax.random.PRNGKey(1000 + i)
            fused.append(float(fused_clip_aggregate(
                u, 0.5, noise_key=k, noise_sigma=sigma,
                backend="kernel-fused").mean_sq))
            mat.append(float(fused_clip_aggregate(
                u, 0.5, noise_key=k, noise_sigma=sigma, backend="jnp").mean_sq))
        expected = float(fused_clip_aggregate(u, 0.5, backend="jnp").mean_sq_clipped)
        expected += d * sigma**2
        # both estimators target the same mean; each concentrates at
        # O(sigma^2 sqrt(d/m)) per draw, / sqrt(8) for the average
        tol = 5 * sigma**2 * np.sqrt(2.0 * d / m) / np.sqrt(8)
        assert abs(np.mean(fused) - expected) < tol
        assert abs(np.mean(mat) - expected) < tol

    def test_engine_with_fused_noise_backend_trains(self, problem):
        """End-to-end: the scan engine with the kernel-fused backend."""
        data, w0 = problem
        alg = make_algorithm("ldp-fedexp-gauss", clip_norm=0.3, sigma=0.21,
                             backend="kernel-fused")
        session = FederatedSession(alg, linreg_loss, w0, data.client_batches(),
                                   train=TrainSpec(rounds=3, tau=TAU, eta_l=ETA_L),
                                   eval_fn=distance_to_opt(data.w_star))
        r = session.run(jax.random.PRNGKey(2))
        assert np.all(np.isfinite(np.asarray(r.metric_history)))
        assert float(jnp.min(r.eta_history)) >= 1.0
