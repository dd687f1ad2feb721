"""FederatedSession API (DESIGN.md §10): spec-driven runs, pytree models,
checkpoint/resume, and the deprecated-shim contract.

The resume tests are the acceptance criterion for resumable runs: a run to
round T must equal run-to-T/2 -> save -> resume -> run-to-T BIT-EXACTLY,
including the optimizer state (dp-fedadam-cdp) and the adaptive clip state
(cdp-fedexp-adaptive-clip) surviving the npz round trip.
"""
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.core.fedexp import list_algorithms, make_algorithm
from repro.data.synthetic import distance_to_opt, linreg_loss, make_synthetic_linreg
from repro.fedsim import (
    CohortSpec,
    EngineSpec,
    FederatedSession,
    TrainSpec,
    flatten_model,
)
from repro.fedsim.server import run_federated, run_federated_batched

M, D, TAU, ETA_L, ROUNDS = 32, 16, 3, 0.1, 6

ALG_KWARGS = {
    "fedavg": {},
    "cdp-fedexp": dict(clip_norm=0.3, sigma=0.2, num_clients=M),
    "cdp-fedexp-adaptive-clip": dict(z_mult=0.5, num_clients=M, dim=D),
    "dp-fedadam-cdp": dict(clip_norm=0.3, sigma=0.2, num_clients=M, server_lr=0.05),
}


@pytest.fixture(scope="module")
def problem():
    data = make_synthetic_linreg(jax.random.PRNGKey(3), M, D)
    return data, jnp.zeros(D)


def _session(problem, name, *, rounds=ROUNDS, **spec_kw):
    data, w0 = problem
    alg = make_algorithm(name, **ALG_KWARGS[name])
    return FederatedSession(
        alg, linreg_loss, w0, data.client_batches(),
        train=spec_kw.pop("train", TrainSpec(rounds=rounds, tau=TAU, eta_l=ETA_L)),
        eval_fn=distance_to_opt(data.w_star), **spec_kw)


class TestShims:
    """run_federated/_batched are DEPRECATED shims that must stay
    bit-identical to the session they wrap."""

    def test_run_federated_matches_session_and_warns(self, problem):
        data, w0 = problem
        alg = make_algorithm("cdp-fedexp", **ALG_KWARGS["cdp-fedexp"])
        kw = dict(rounds=ROUNDS, tau=TAU, eta_l=ETA_L)
        r_s = _session(problem, "cdp-fedexp").run(jax.random.PRNGKey(11))
        import repro.fedsim.server as srv
        srv._deprecation_warned = False
        with pytest.warns(DeprecationWarning, match="FederatedSession"):
            r_f = run_federated(alg, linreg_loss, w0, data.client_batches(),
                                key=jax.random.PRNGKey(11),
                                eval_fn=distance_to_opt(data.w_star), **kw)
        np.testing.assert_array_equal(np.asarray(r_s.final_w), np.asarray(r_f.final_w))
        np.testing.assert_array_equal(np.asarray(r_s.eta_history),
                                      np.asarray(r_f.eta_history))
        np.testing.assert_array_equal(np.asarray(r_s.metric_history),
                                      np.asarray(r_f.metric_history))
        # the warning fires once per process, then the shim goes quiet
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            run_federated(alg, linreg_loss, w0, data.client_batches(),
                          key=jax.random.PRNGKey(11), **kw)

    def test_run_federated_batched_matches_session(self, problem):
        data, w0 = problem
        alg = make_algorithm("cdp-fedexp", **ALG_KWARGS["cdp-fedexp"])
        keys = jnp.stack([jax.random.PRNGKey(1), jax.random.PRNGKey(2)])
        r_s = _session(problem, "cdp-fedexp").run_batched(keys)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            r_f = run_federated_batched(alg, linreg_loss, w0, data.client_batches(),
                                        rounds=ROUNDS, tau=TAU, eta_l=ETA_L,
                                        keys=keys,
                                        eval_fn=distance_to_opt(data.w_star))
        np.testing.assert_array_equal(np.asarray(r_s.final_w), np.asarray(r_f.final_w))
        np.testing.assert_array_equal(np.asarray(r_s.eta_history),
                                      np.asarray(r_f.eta_history))


class TestLower:
    """``session.lower(key)`` is the first chunk program ``run`` executes."""

    @pytest.mark.parametrize("engine", ["scan", "stream"])
    def test_lowers_the_first_chunk(self, problem, engine):
        session = _session(problem, "cdp-fedexp",
                           engine=EngineSpec(engine=engine, chunk_rounds=2))
        lowered = session.lower(jax.random.PRNGKey(5))
        carry, hist = lowered.out_info
        assert carry[0].shape == (D,)
        assert [h.shape for h in hist] == [(2,)] * 4   # rounds [0, 2)
        lowered.compile()

    def test_eager_has_no_program(self, problem):
        session = _session(problem, "cdp-fedexp",
                           engine=EngineSpec(engine="eager"))
        with pytest.raises(ValueError, match="compiled scan or stream"):
            session.lower(jax.random.PRNGKey(5))


class TestSessionReuse:
    def test_repeated_runs_deterministic_and_cached(self, problem):
        sess = _session(problem, "cdp-fedexp")
        import repro.fedsim.server as srv
        r1 = sess.run(jax.random.PRNGKey(5))
        hits_before = srv._cached_scan_chunk_fn.cache_info().hits
        r2 = sess.run(jax.random.PRNGKey(5))
        np.testing.assert_array_equal(np.asarray(r1.final_w), np.asarray(r2.final_w))
        # the session owns its closures: the second run hits the compile cache
        assert srv._cached_scan_chunk_fn.cache_info().hits > hits_before

    def test_eager_engine(self, problem):
        r_s = _session(problem, "fedavg").run(jax.random.PRNGKey(5))
        r_e = _session(problem, "fedavg",
                       engine=EngineSpec(engine="eager")).run(jax.random.PRNGKey(5))
        np.testing.assert_array_equal(np.asarray(r_s.final_w), np.asarray(r_e.final_w))

    def test_spec_validation(self):
        with pytest.raises(ValueError, match="rounds"):
            TrainSpec(rounds=0, tau=1, eta_l=0.1)
        with pytest.raises(ValueError, match="engine"):
            EngineSpec(engine="warp")
        with pytest.raises(ValueError, match="not both"):
            CohortSpec(q=0.5, size=4)
        with pytest.raises(ValueError, match="replace"):
            CohortSpec(replace=True)

    def test_checkpoint_every_requires_dir(self, problem):
        with pytest.raises(ValueError, match="checkpoint_dir"):
            _session(problem, "fedavg").run(jax.random.PRNGKey(0),
                                            checkpoint_every=2)

    def test_run_batched_rejects_eager(self, problem):
        sess = _session(problem, "fedavg", engine=EngineSpec(engine="eager"))
        with pytest.raises(ValueError, match="eager"):
            sess.run_batched(jnp.stack([jax.random.PRNGKey(0)]))

    def test_cohort_size_exceeds_clients(self, problem):
        sess = _session(problem, "fedavg", cohort=CohortSpec(size=M + 1))
        with pytest.raises(ValueError, match="exceeds"):
            sess.run(jax.random.PRNGKey(0))

    def test_batched_data_seed_axis_not_mistaken_for_clients(self, problem):
        """Validation must see the client axis (1 under batched_data), not
        the leading seed axis."""
        data, w0 = problem
        batches = {k: jnp.stack([v, v])
                   for k, v in data.client_batches().items()}  # (S=2, M, ...)
        sess = FederatedSession(
            make_algorithm("fedavg"), linreg_loss, w0, batches,
            train=TrainSpec(rounds=2, tau=1, eta_l=ETA_L),
            cohort=CohortSpec(size=M // 2))
        keys = jnp.stack([jax.random.PRNGKey(0), jax.random.PRNGKey(1)])
        rb = sess.run_batched(keys, batched_data=True)  # must not raise
        assert rb.final_w.shape == (2, D)


class TestEvalCadence:
    def test_eval_every_masks_offcadence_rounds(self, problem):
        r1 = _session(problem, "cdp-fedexp").run(jax.random.PRNGKey(5))
        r3 = _session(
            problem, "cdp-fedexp",
            train=TrainSpec(rounds=ROUNDS, tau=TAU, eta_l=ETA_L, eval_every=3),
        ).run(jax.random.PRNGKey(5))
        m1, m3 = np.asarray(r1.metric_history), np.asarray(r3.metric_history)
        on = np.arange(ROUNDS) % 3 == 2          # rounds 2, 5 evaluate
        np.testing.assert_array_equal(m3[on], m1[on])
        assert np.isnan(m3[~on]).all()
        # the trajectory itself is untouched by the cadence
        np.testing.assert_array_equal(np.asarray(r1.final_w), np.asarray(r3.final_w))


class TestPytreeModels:
    def _tree_problem(self):
        key = jax.random.PRNGKey(0)
        params = {"W": 0.1 * jax.random.normal(key, (8, 4)), "b": jnp.zeros(4)}
        batches = {
            "x": jax.random.normal(jax.random.fold_in(key, 1), (M, 10, 8)),
            "y": jax.random.normal(jax.random.fold_in(key, 2), (M, 10, 4)),
        }

        def loss(p, batch):
            pred = batch["x"] @ p["W"] + p["b"]
            return 0.5 * jnp.mean(jnp.sum(jnp.square(pred - batch["y"]), -1))

        return params, batches, loss

    def test_pytree_run_matches_manual_flatten(self):
        params, batches, loss = self._tree_problem()
        alg = make_algorithm("cdp-fedexp", **ALG_KWARGS["cdp-fedexp"])
        train = TrainSpec(rounds=4, tau=2, eta_l=0.05)
        r_tree = FederatedSession(alg, loss, params, batches, train=train).run(
            jax.random.PRNGKey(7))
        assert isinstance(r_tree.final_w, dict)
        assert r_tree.final_w["W"].shape == (8, 4)

        flat, unravel = flatten_model(params)
        r_flat = FederatedSession(
            alg, lambda wf, b: loss(unravel(wf), b), flat, batches,
            train=train).run(jax.random.PRNGKey(7))
        np.testing.assert_array_equal(
            np.asarray(flatten_model(r_tree.final_w)[0]), np.asarray(r_flat.final_w))
        np.testing.assert_array_equal(np.asarray(r_tree.eta_history),
                                      np.asarray(r_flat.eta_history))

    def test_pytree_batched_and_eval(self):
        params, batches, loss = self._tree_problem()
        alg = make_algorithm("fedavg")
        eval_fn = lambda p: jnp.sum(jnp.square(p["W"]))
        sess = FederatedSession(alg, loss, params, batches,
                                train=TrainSpec(rounds=3, tau=2, eta_l=0.05),
                                eval_fn=eval_fn)
        keys = jnp.stack([jax.random.PRNGKey(1), jax.random.PRNGKey(2)])
        rb = sess.run_batched(keys)
        assert rb.final_w["W"].shape == (2, 8, 4)
        assert np.all(np.isfinite(np.asarray(rb.metric_history)))

    def test_batched_w0_with_pytree_rejected(self):
        params, batches, loss = self._tree_problem()
        sess = FederatedSession(make_algorithm("fedavg"), loss, params, batches,
                                train=TrainSpec(rounds=2, tau=1, eta_l=0.05))
        with pytest.raises(ValueError, match="batched_w0"):
            sess.run_batched(jnp.stack([jax.random.PRNGKey(0)]), batched_w0=True)


class TestCheckpointResume:
    """Acceptance: kill/resume == uninterrupted, bit-exactly, with optimizer
    and clip state surviving the round trip."""

    @pytest.mark.parametrize("name", sorted(ALG_KWARGS))
    def test_resume_matches_uninterrupted(self, problem, name, tmp_path):
        key = jax.random.PRNGKey(11)
        half = ROUNDS // 2
        # uninterrupted, chunked at the same boundary the resume will use so
        # even adam's 1-ULP-per-program wobble cannot differ
        r_full = _session(problem, name,
                          engine=EngineSpec(chunk_rounds=half)).run(key)

        _session(problem, name, rounds=half).run(key, checkpoint_dir=str(tmp_path))
        assert ckpt.latest_step(str(tmp_path)) == half
        r_res = _session(problem, name).resume(str(tmp_path))

        for field in ("final_w", "last_w", "eta_history", "metric_history",
                      "eta_naive_history", "eta_target_history"):
            np.testing.assert_array_equal(
                np.asarray(getattr(r_full, field)),
                np.asarray(getattr(r_res, field)), err_msg=f"{name}.{field}")

    def test_resume_matches_single_chunk_run(self, problem):
        """Chunk boundaries don't change results: resume == one-chunk run."""
        key = jax.random.PRNGKey(11)
        r_one = _session(problem, "cdp-fedexp").run(key)
        r_chunked = _session(problem, "cdp-fedexp",
                             engine=EngineSpec(chunk_rounds=2)).run(key)
        np.testing.assert_array_equal(np.asarray(r_one.final_w),
                                      np.asarray(r_chunked.final_w))

    def test_periodic_checkpoints_and_resume_from_latest(self, problem, tmp_path):
        key = jax.random.PRNGKey(11)
        sess = _session(problem, "cdp-fedexp-adaptive-clip")
        r_full = sess.run(key, checkpoint_dir=str(tmp_path), checkpoint_every=2)
        steps = sorted(int(f[5:13]) for f in os.listdir(tmp_path)
                       if f.endswith(".npz"))
        assert steps == [2, 4, ROUNDS]
        r_res = _session(problem, "cdp-fedexp-adaptive-clip").resume(str(tmp_path))
        # latest checkpoint IS the full run: resume returns it as-is
        np.testing.assert_array_equal(np.asarray(r_full.final_w),
                                      np.asarray(r_res.final_w))
        np.testing.assert_array_equal(np.asarray(r_full.eta_history),
                                      np.asarray(r_res.eta_history))

    def test_sampled_run_resumes_bit_exact(self, problem, tmp_path):
        """Sampling masks derive from fold_in(key, t): resume redraws the
        identical cohorts."""
        key = jax.random.PRNGKey(11)
        cohort = CohortSpec(q=0.5)
        r_full = _session(problem, "cdp-fedexp", cohort=cohort).run(key)
        _session(problem, "cdp-fedexp", rounds=ROUNDS // 2, cohort=cohort).run(
            key, checkpoint_dir=str(tmp_path))
        r_res = _session(problem, "cdp-fedexp", cohort=cohort).resume(str(tmp_path))
        np.testing.assert_array_equal(np.asarray(r_full.final_w),
                                      np.asarray(r_res.final_w))

    def test_resume_algorithm_mismatch_rejected(self, problem, tmp_path):
        _session(problem, "fedavg", rounds=2).run(jax.random.PRNGKey(0),
                                                  checkpoint_dir=str(tmp_path))
        with pytest.raises(ValueError, match="algorithm"):
            _session(problem, "cdp-fedexp").resume(str(tmp_path))

    def test_resume_past_rounds_rejected(self, problem, tmp_path):
        _session(problem, "fedavg").run(jax.random.PRNGKey(0),
                                        checkpoint_dir=str(tmp_path))
        with pytest.raises(ValueError, match="past"):
            _session(problem, "fedavg", rounds=2).resume(str(tmp_path))


class TestCheckpointPackage:
    """Satellite: checkpoint robustness (ValueError not assert, atomic meta,
    registered-dataclass paths)."""

    def test_shape_mismatch_raises_value_error(self, tmp_path):
        ckpt.save_checkpoint(str(tmp_path), 0, {"w": jnp.zeros(4)})
        with pytest.raises(ValueError, match=r"'w'.*\(4,\)"):
            ckpt.load_checkpoint(str(tmp_path), {"w": jnp.zeros(5)})

    def test_missing_leaf_raises_value_error(self, tmp_path):
        ckpt.save_checkpoint(str(tmp_path), 0, {"w": jnp.zeros(4)})
        with pytest.raises(ValueError, match="missing leaf"):
            ckpt.load_checkpoint(str(tmp_path), {"v": jnp.zeros(4)})

    def test_no_tmp_files_left_behind(self, tmp_path):
        ckpt.save_checkpoint(str(tmp_path), 3, {"w": jnp.zeros(4)},
                             extra={"note": "x"})
        files = sorted(os.listdir(tmp_path))
        assert files == ["ckpt_00000003.json", "ckpt_00000003.npz"]

    def test_registered_dataclass_roundtrip(self, tmp_path):
        from repro.core.adaptive_clip import AdaptiveClipState
        state = {"clipstate": AdaptiveClipState(clip=jnp.float32(0.7)),
                 "opt": (jnp.arange(3.0), (), jnp.int32(5))}
        ckpt.save_checkpoint(str(tmp_path), 1, state)
        loaded, meta = ckpt.load_checkpoint(str(tmp_path), state)
        assert float(loaded["clipstate"].clip) == pytest.approx(0.7)
        np.testing.assert_array_equal(np.asarray(loaded["opt"][0]),
                                      np.asarray(state["opt"][0]))
        assert int(loaded["opt"][2]) == 5
        assert meta["step"] == 1


class TestRegistry:
    # the 10 paper-era names, pinned bit-for-bit against their monolithic
    # classes by tests/test_compose.py; the registry also carries the newer
    # cross-product compositions (ldp-gauss-fedadam, ...)
    LEGACY_NAMES = {
        "fedavg", "fedexp", "dp-fedavg-ldp-gauss", "ldp-fedexp-gauss",
        "dp-fedavg-privunit", "ldp-fedexp-privunit", "dp-fedavg-cdp",
        "cdp-fedexp", "dp-fedadam-cdp", "cdp-fedexp-adaptive-clip",
    }

    def test_list_algorithms(self):
        names = list_algorithms()
        assert names == sorted(names) and len(names) == len(set(names))
        assert self.LEGACY_NAMES <= set(names)
        assert {"ldp-gauss-fedadam", "cdp-fedmom",
                "privunit-fedexp-adaptive-clip"} <= set(names)

    def test_unknown_name_enumerates(self):
        with pytest.raises(KeyError, match="cdp-fedexp"):
            make_algorithm("no-such-algorithm")

    def test_exported_from_core(self):
        from repro import core
        assert core.list_algorithms is list_algorithms
        assert core.make_algorithm is make_algorithm


class TestPrivacyReport:
    def test_subsampled_report_accounts_for_sampling(self, problem):
        """Sampling at FIXED sigma is not a free privacy win: the
        count-normalized mean's conditional sensitivity inflates by 1/q, and
        the subsampled-GDP amplification at best cancels it — the report must
        reflect the mechanism actually implemented, not a naive q-discount."""
        full = _session(problem, "cdp-fedexp").privacy_report(1e-5)
        samp = _session(problem, "cdp-fedexp",
                        cohort=CohortSpec(q=0.25)).privacy_report(1e-5)
        assert "q=0.25" in samp.setting
        assert samp.eps_numerical >= 0.9 * full.eps_numerical  # no free lunch
        # unsampled q path is the exact composition (unchanged numbers)
        from repro.core import accounting
        alg_kw = ALG_KWARGS["cdp-fedexp"]
        sigma_xi = D * alg_kw["sigma"] ** 2 / M
        ref = accounting.cdp_budget(alg_kw["clip_norm"], alg_kw["sigma"], M,
                                    ROUNDS, 1e-5, sigma_xi=sigma_xi)
        assert full.eps_numerical == pytest.approx(ref.eps_numerical)

    def test_adaptive_clip_sampled_report(self, problem):
        """The adaptive-clip report composes the 1/sqrt(q) conditional
        inflation (its noise tracks the realized cohort)."""
        import math
        samp = _session(problem, "cdp-fedexp-adaptive-clip",
                        cohort=CohortSpec(q=0.25)).privacy_report(1e-5)
        z, q = ALG_KWARGS["cdp-fedexp-adaptive-clip"]["z_mult"], 0.25
        from repro.core import accounting
        mu_round = math.sqrt((2.0 / (z * math.sqrt(q * M))) ** 2
                             + (1.0 / (D * z**2)) ** 2)
        assert samp.mu == pytest.approx(
            accounting.subsampled_gdp_mu(mu_round, q, ROUNDS))

    def test_non_private_raises(self, problem):
        with pytest.raises(ValueError, match="not a private"):
            _session(problem, "fedavg").privacy_report(1e-5)
