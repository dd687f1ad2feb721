"""Client-sharded engine equivalence (DESIGN.md §9).

The sharded engine must be the SAME algorithm as the single-device scan
engine for every registered algorithm: per-shard partial sums + one psum may
reorder reductions (allclose, rtol 1e-5), but all randomness — per-client
LDP noise and PrivUnit keys (global-index fold_in), post-reduction CDP noise
and xi (replicated round key), adaptive-clip bit noise — is derived
identically, and on meshes where the reduction order is unchanged many
algorithms stay bit-exact.

These tests run on however many devices the process sees: 1 locally (the
mesh still exercises shard_map + psum + padding), 8 under the CI leg that
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before pytest.
The realization-level LDP equivalence assumes the unsharded release
MATERIALIZES its noise, which backend="auto" guarantees off-TPU (this suite
runs on CPU); on TPU the auto path draws in-kernel noise from a different
stream and the comparison would be distributional only (DESIGN.md §9).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import (
    fused_clip_aggregate,
    materialize_ldp_noise,
    partial_clip_moments,
)
from repro.core.fedexp import make_algorithm
from repro.data.synthetic import distance_to_opt, linreg_loss, make_synthetic_linreg
from repro.fedsim import EngineSpec, FederatedSession, ShardSpec, TrainSpec
from repro.fedsim.local import pad_cohort
from repro.kernels.dp_aggregate.ops import dp_aggregate, dp_aggregate_sums
from repro.launch.mesh import auto_shard_count, client_shard_spec, make_client_mesh

# M deliberately NOT divisible by 8 (nor by 2/4): every multi-device CI leg
# exercises the zero-weight padding path.
M, D, TAU, ETA_L, ROUNDS = 44, 24, 4, 0.1, 6

N_DEV = len(jax.devices())

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


@pytest.fixture(scope="module")
def problem():
    data = make_synthetic_linreg(jax.random.PRNGKey(3), M, D)
    return data, jnp.zeros(D)


@pytest.fixture(scope="module")
def mesh():
    return make_client_mesh()


def _run(problem, name, *, mesh=None, rounds=ROUNDS):
    data, w0 = problem
    alg = make_algorithm(name, **ALG_KWARGS[name])
    session = FederatedSession(alg, linreg_loss, w0, data.client_batches(),
                               train=TrainSpec(rounds=rounds, tau=TAU, eta_l=ETA_L),
                               shard=ShardSpec(mesh=mesh),
                               eval_fn=distance_to_opt(data.w_star))
    return session.run(jax.random.PRNGKey(11))


class TestShardedEquivalence:
    @pytest.mark.parametrize("name", sorted(ALG_KWARGS))
    def test_sharded_matches_single_device(self, problem, mesh, name):
        """Weights and metrics match at rtol 1e-5 (atol floors the ~0
        components).  The eta histories get a looser relative bar: eta is a
        RATIO of reductions (mean_sq / ||cbar||²), so a 1-ULP reduction-order
        difference between the two separately-compiled XLA programs is
        amplified through rounds of eta-scaled feedback — the weights
        themselves demonstrably stay at 1e-5.
        """
        r1 = _run(problem, name)
        r2 = _run(problem, name, mesh=mesh)
        for field in ("final_w", "last_w", "metric_history"):
            np.testing.assert_allclose(
                np.asarray(getattr(r1, field)), np.asarray(getattr(r2, field)),
                rtol=1e-5, atol=1e-5, err_msg=f"{name}.{field}")
        for field in ("eta_history", "eta_naive_history", "eta_target_history"):
            np.testing.assert_allclose(
                np.asarray(getattr(r1, field)), np.asarray(getattr(r2, field)),
                rtol=1e-4, atol=1e-5, err_msg=f"{name}.{field}")

    @pytest.mark.parametrize("name", ["fedavg", "dp-fedavg-cdp"])
    def test_bit_exact_on_unit_mesh(self, problem, name):
        """Where the reduction order is unchanged (one shard, no padding:
        the mask is all-ones and every masked sum is the reference matvec),
        the sharded engine is bit-for-bit the scan engine."""
        if N_DEV != 1:
            pytest.skip("reduction order only preserved on a 1-device mesh")
        r1 = _run(problem, name)
        r2 = _run(problem, name, mesh=make_client_mesh(1))
        # fedavg's unclipped sums: XLA:CPU (jax 0.9) fuses the shard_map
        # program's mask dot differently, 1 f32 ULP (6e-8) at |w| ~ 0.5
        atol = 1e-7 if name == "fedavg" else 0
        np.testing.assert_allclose(np.asarray(r1.final_w), np.asarray(r2.final_w),
                                   rtol=0, atol=atol)
        np.testing.assert_array_equal(np.asarray(r1.eta_history),
                                      np.asarray(r2.eta_history))

    def test_explicit_padding_shards(self, problem):
        """Force a shard count that does NOT divide M on any device count:
        a 1-shard mesh over padded M=44 -> pad to 44 (no-op) vs the raw run is
        covered above; here pad_cohort itself is checked for mask layout."""
        data, _ = problem
        batches, mask = pad_cohort(data.client_batches(), 8)
        m_pad = mask.shape[0]
        assert m_pad % 8 == 0 and m_pad >= M
        assert float(jnp.sum(mask)) == M
        np.testing.assert_array_equal(np.asarray(mask[:M]), 1.0)
        np.testing.assert_array_equal(np.asarray(mask[M:]), 0.0)
        # padded rows replicate client 0, keeping any loss well-behaved
        for k, v in batches.items():
            assert v.shape[0] == m_pad
            np.testing.assert_array_equal(np.asarray(v[M:]),
                                          np.asarray(jnp.broadcast_to(
                                              v[:1], (m_pad - M,) + v.shape[1:])))

    def test_mesh_requires_scan_engine(self, problem, mesh):
        session = FederatedSession(make_algorithm("fedavg"), linreg_loss,
                                   problem[1], problem[0].client_batches(),
                                   train=TrainSpec(rounds=2, tau=1, eta_l=0.1),
                                   engine=EngineSpec(engine="eager"),
                                   shard=ShardSpec(mesh=mesh))
        with pytest.raises(ValueError, match="scan"):
            session.run(jax.random.PRNGKey(0))


class TestShardedBatched:
    def _batched(self, problem, alg, keys, *, mesh=None, eval_fn=None,
                 w0=None, batches=None, rounds=ROUNDS, **kw):
        data, w0_default = problem
        session = FederatedSession(
            alg, linreg_loss, w0 if w0 is not None else w0_default,
            batches if batches is not None else data.client_batches(),
            train=TrainSpec(rounds=rounds, tau=TAU, eta_l=ETA_L),
            shard=ShardSpec(mesh=mesh), eval_fn=eval_fn)
        return session.run_batched(keys, **kw)

    def test_batched_sharded_matches_batched(self, problem, mesh):
        data, _ = problem
        alg = make_algorithm("ldp-fedexp-gauss", **ALG_KWARGS["ldp-fedexp-gauss"])
        keys = jnp.stack([jax.random.PRNGKey(21), jax.random.PRNGKey(22)])
        ev = distance_to_opt(data.w_star)
        r1 = self._batched(problem, alg, keys, eval_fn=ev)
        r2 = self._batched(problem, alg, keys, mesh=mesh, eval_fn=ev)
        assert r2.final_w.shape == (2, D)
        # vmap may re-batch BLAS reductions: tolerance, not exact
        np.testing.assert_allclose(np.asarray(r1.final_w), np.asarray(r2.final_w),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(r1.eta_history),
                                   np.asarray(r2.eta_history), rtol=1e-4)

    def test_batched_w0_and_data_sharded(self, problem, mesh):
        data, _ = problem
        alg = make_algorithm("fedexp")
        keys = jnp.stack([jax.random.PRNGKey(0), jax.random.PRNGKey(1)])
        w0s = jnp.stack([jnp.zeros(D), 0.1 * jnp.ones(D)])
        batches = {k: jnp.stack([v, v]) for k, v in data.client_batches().items()}
        rb = self._batched(problem, alg, keys, mesh=mesh, w0=w0s,
                           batches=batches, rounds=3,
                           batched_w0=True, batched_data=True)
        assert rb.final_w.shape == (2, D)
        assert not np.allclose(np.asarray(rb.final_w[0]), np.asarray(rb.final_w[1]))


class TestMomentPrimitives:
    """The moment API against the stats API it decomposes."""

    def test_partial_moments_match_fused_stats(self):
        u = 2.0 * jax.random.normal(jax.random.PRNGKey(5), (32, 96))
        noise = materialize_ldp_noise(jax.random.PRNGKey(7), 32, 96, 0.4)
        stats = fused_clip_aggregate(u, 0.5, noise, backend="jnp")
        mom = partial_clip_moments(u, 0.5, noise, backend="jnp")
        np.testing.assert_allclose(np.asarray(mom.sum_c / mom.count),
                                   np.asarray(stats.cbar), rtol=1e-6)
        np.testing.assert_allclose(float(mom.sum_sq / mom.count),
                                   float(stats.mean_sq), rtol=1e-6)
        np.testing.assert_allclose(float(mom.sum_sq_clipped / mom.count),
                                   float(stats.mean_sq_clipped), rtol=1e-6)
        assert float(mom.count) == 32.0

    def test_partial_moments_shard_additivity(self):
        """moments(top) + moments(bottom) == moments(all): the psum law."""
        u = jax.random.normal(jax.random.PRNGKey(9), (40, 64))
        whole = partial_clip_moments(u, 0.7, backend="jnp")
        top = partial_clip_moments(u[:20], 0.7, backend="jnp")
        bot = partial_clip_moments(u[20:], 0.7, backend="jnp")
        np.testing.assert_allclose(np.asarray(top.sum_c + bot.sum_c),
                                   np.asarray(whole.sum_c), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(float(top.sum_sq + bot.sum_sq),
                                   float(whole.sum_sq), rtol=1e-5)
        assert float(top.count + bot.count) == float(whole.count)

    def test_weight_mask_drops_rows(self):
        u = jax.random.normal(jax.random.PRNGKey(11), (24, 32))
        mask = jnp.concatenate([jnp.ones(20), jnp.zeros(4)])
        # poison the padding rows: the mask must keep NaNs out of every sum
        u = u.at[20:].set(jnp.nan)
        mom = partial_clip_moments(u, 0.5, weight_mask=mask, backend="jnp")
        ref = partial_clip_moments(u[:20], 0.5, backend="jnp")
        assert np.all(np.isfinite(np.asarray(mom.sum_c)))
        np.testing.assert_allclose(np.asarray(mom.sum_c), np.asarray(ref.sum_c),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(float(mom.sum_sq), float(ref.sum_sq), rtol=1e-6)
        assert float(mom.count) == 20.0

    def test_row_weights_weight_released_rows(self):
        """row_weights (the weighted-aggregation layer) multiplies each
        RELEASED row and the count — exact weighted-mean moments."""
        u = jax.random.normal(jax.random.PRNGKey(21), (6, 16))
        mask = jnp.asarray([1.0, 1.0, 0.0, 1.0, 0.0, 1.0])
        v = jnp.asarray([2.0, 1.0, 7.0, 0.5, 7.0, 1.0])
        mom = partial_clip_moments(u, 1e9, weight_mask=mask, row_weights=v,
                                   backend="jnp")
        np.testing.assert_allclose(np.asarray(mom.sum_c),
                                   np.asarray((mask * v) @ u), rtol=1e-6)
        np.testing.assert_allclose(
            float(mom.sum_sq),
            float((mask * v) @ jnp.sum(jnp.square(u), axis=-1)), rtol=1e-6)
        assert float(mom.count) == pytest.approx(4.5)

    def test_kernel_sums_match_jnp_sums(self):
        u = jax.random.normal(jax.random.PRNGKey(13), (24, 300))
        noise = 0.3 * jax.random.normal(jax.random.PRNGKey(14), (24, 300))
        s_k, sq_k, sc_k = dp_aggregate_sums(u, 0.4, noise)
        jm = partial_clip_moments(u, 0.4, noise, backend="jnp")
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(jm.sum_c),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(float(sq_k), float(jm.sum_sq), rtol=2e-5)
        np.testing.assert_allclose(float(sc_k), float(jm.sum_sq_clipped), rtol=2e-5)

    def test_kernel_sums_consistent_with_dp_aggregate(self):
        u = jax.random.normal(jax.random.PRNGKey(15), (16, 128))
        s, sq, sc = dp_aggregate_sums(u, 0.6)
        stats = dp_aggregate(u, 0.6)
        np.testing.assert_allclose(np.asarray(s / 16), np.asarray(stats.cbar),
                                   rtol=1e-6)
        np.testing.assert_allclose(float(sq / 16), float(stats.mean_sq), rtol=1e-6)

    def test_ldp_noise_shard_offset_matches_rows(self):
        """Row r of the full cohort noise == row 0 of a shard starting at r."""
        key = jax.random.PRNGKey(17)
        full = materialize_ldp_noise(key, 12, 64, 0.9)
        shard = materialize_ldp_noise(key, 4, 64, 0.9, start=8)
        np.testing.assert_array_equal(np.asarray(full[8:]), np.asarray(shard))


class TestAutoShardCount:
    def test_caps_at_min_cohort_slice(self):
        """The heuristic never leaves a shard with < 24 clients (the measured
        collapse regime of the committed bench history)."""
        assert auto_shard_count(96, n_devices=8) == 4
        assert auto_shard_count(300, n_devices=8) == 8
        assert auto_shard_count(10, n_devices=8) == 1
        assert auto_shard_count(48, n_devices=2) == 2

    def test_auto_spec_builds_capped_mesh(self):
        spec = client_shard_spec("auto", num_clients=10_000)
        assert spec.mesh.shape["clients"] == min(N_DEV, 10_000 // 24)
        with pytest.raises(ValueError, match="num_clients"):
            client_shard_spec("auto")


class TestE7ShardedPath:
    def test_e7_sharded_rows(self):
        """The benchmark's sharded scaling curve runs and covers every
        power-of-two shard count up to the visible device count."""
        from benchmarks.e7_engine_throughput import _sharded_rows
        key = jax.random.PRNGKey(0)
        targets = jax.random.normal(key, (16, 64))
        rows = _sharded_rows(targets, jnp.zeros(64), key, rounds=3)
        counts = [r[0] for r in rows]
        assert counts == [n for n in (1, 2, 4, 8, 16) if n <= N_DEV]
        assert all(r[1] > 0 for r in rows)


@pytest.mark.skipif(N_DEV < 2, reason="needs >1 device (set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
class TestMultiDevice:
    def test_cohort_is_actually_sharded(self, problem, mesh):
        """The compiled sharded program places distinct client slices on
        distinct devices (not a replicated fallback)."""
        n = mesh.shape["clients"]
        assert n == N_DEV > 1
        r = _run(problem, "ldp-fedexp-gauss", mesh=mesh)
        assert np.all(np.isfinite(np.asarray(r.final_w)))
