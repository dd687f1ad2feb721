"""Heterogeneous privacy + adaptive noise schedules (DESIGN.md §17).

The §17 contract this file pins:

  1. **Degenerate cases are bit-for-bit.**  A constant ``NoiseSchedule``
     resolves to its inner mechanism's OWN object (same trace), so the
     scheduled registry names with decay 1 reproduce the fixed-sigma runs
     bit-identically; equal per-client epsilons reduce ``PerClientGaussian``
     to ``GaussianLDP`` with the common sigma; the migrated ``dp-scaffold``
     session reproduces the legacy ``run_dp_scaffold`` loop bit-for-bit on
     its supported path (central at any sigma, local at sigma 0).
  2. **Cross-engine parity.**  Every §17 composition — per-client sigmas,
     sigma(t) schedules (exponential + step), DP-SCAFFOLD central/local —
     agrees across scan / eager / stream / gather / sharded engines at
     rtol 1e-5 (scan == eager bit-exact; multi-chunk streams reassociate
     sums, hence the rtol contract, DESIGN.md §12).
  3. **Telemetry tells the truth.**  The per-round ``sigma`` event matches
     the declared schedule at f32 tolerance on every executed round, and the
     §15 cumulative ledger equals ``session.privacy_report`` to 1e-9 under a
     NON-constant schedule — including resumed runs and §13 retried rounds.
  4. **Accounting composes honestly** (hypothesis): the scheduled ledger is
     monotone in executed rounds, permutation-invariant, reduces EXACTLY to
     the uniform accountants on homogeneous schedules, and the heterogeneous
     report is the worst client's guarantee (every client's own budget is
     within it).
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # the property layer needs hypothesis (CI installs it); everything
    import hypothesis.strategies as st  # else below always runs
    from hypothesis import given, settings
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

from repro.core import accounting
from repro.core.compose import (
    CentralGaussian,
    FedEXPStep,
    GaussianLDP,
    NoiseSchedule,
    PerClientGaussian,
    compose_algorithm,
)
from repro.core.fedexp import make_algorithm
from repro.core.mechanisms import per_client_sigmas
from repro.data.synthetic import distance_to_opt, linreg_loss, make_synthetic_linreg
from repro.fedsim import (
    CohortSpec,
    EngineSpec,
    FaultSpec,
    FederatedSession,
    LocalSpec,
    ShardSpec,
    StreamSpec,
    TrainSpec,
)
from repro.fedsim.scaffold import DPScaffoldConfig, run_dp_scaffold
from repro.fedsim.session import RecoveryPolicy
from repro.launch.mesh import make_client_mesh
from repro.telemetry import JsonlTracker, Tracker

M, D, TAU, ETA_L, ROUNDS = 16, 10, 2, 0.1, 4
DELTA = 1e-5  # == TelemetrySpec().ledger_delta, so ledger lines match reports
KEY = jax.random.PRNGKey(11)

# heterogeneous per-client budgets: five distinct epsilon tiers across M
EPS_HETERO = tuple(0.5 + 0.25 * (i % 5) for i in range(M))

# the §17 compositions under test: name -> (algorithm factory, session kw)
ALGS = {
    "ldp-schedule": (
        lambda: make_algorithm("ldp-fedexp-schedule", clip_norm=0.3,
                               sigma=0.3, decay=0.8, boundaries=(2,),
                               scales=(0.5,)),
        {}),
    "cdp-schedule": (
        lambda: make_algorithm("cdp-fedexp-schedule", clip_norm=0.3,
                               sigma=0.25, num_clients=M, decay=0.9),
        {}),
    "perclient": (
        lambda: make_algorithm("ldp-fedexp-perclient", clip_norm=0.3,
                               epsilons=EPS_HETERO, delta=DELTA),
        {}),
    "scaffold-central": (
        lambda: make_algorithm("dp-scaffold", clip_norm=1.0, sigma=0.5,
                               central=True, num_clients=M, tau=TAU,
                               eta_l=ETA_L),
        dict(local=LocalSpec(control_variates=True))),
    "scaffold-local": (
        lambda: make_algorithm("dp-scaffold", clip_norm=1.0, sigma=0.5,
                               central=False, num_clients=M, tau=TAU,
                               eta_l=ETA_L),
        dict(local=LocalSpec(control_variates=True))),
}

RESULT_FIELDS = ("final_w", "last_w", "eta_history", "metric_history",
                 "eta_naive_history", "eta_target_history")


@pytest.fixture(scope="module")
def problem():
    data = make_synthetic_linreg(jax.random.PRNGKey(3), M, D)
    return data, jnp.zeros(D)


def _session(problem, alg, *, rounds=ROUNDS, **spec_kw):
    data, w0 = problem
    return FederatedSession(
        alg, linreg_loss, w0, data.client_batches(),
        train=spec_kw.pop("train",
                          TrainSpec(rounds=rounds, tau=TAU, eta_l=ETA_L)),
        eval_fn=spec_kw.pop("eval_fn", distance_to_opt(data.w_star)),
        **spec_kw)


def _assert_bitwise(r_a, r_b, label=""):
    for field in RESULT_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(r_a, field)), np.asarray(getattr(r_b, field)),
            err_msg=f"{label}.{field}")


def _assert_close(r_a, r_b, label="", rtol=1e-5, atol=1e-6):
    for field in ("final_w", "last_w", "eta_history"):
        np.testing.assert_allclose(
            np.asarray(getattr(r_a, field)), np.asarray(getattr(r_b, field)),
            rtol=rtol, atol=atol, err_msg=f"{label}.{field}")


class _ListTracker(Tracker):
    """In-memory sink for the sigma/ledger event assertions."""

    def __init__(self):
        self.events = []

    def log(self, step, event):
        self.events.append((step, dict(event)))

    def rounds(self):
        return [e for _, e in self.events if "event" not in e]


# ---------------------------------------------------------------------------
# 1. Degenerate cases are bit-for-bit
# ---------------------------------------------------------------------------

class TestDegenerateBitwise:
    """decay=1 schedules, equal epsilons, and the migrated scaffold all
    reproduce their pre-§17 counterparts bit-identically."""

    @pytest.mark.parametrize("sched,fixed,kw", [
        ("ldp-fedexp-schedule", "ldp-fedexp-gauss",
         dict(clip_norm=0.3, sigma=0.21)),
        ("cdp-fedexp-schedule", "cdp-fedexp",
         dict(clip_norm=0.3, sigma=0.2, num_clients=M)),
    ])
    def test_constant_schedule_is_fixed_sigma(self, problem, sched, fixed, kw):
        alg_s = make_algorithm(sched, **kw)
        # a constant schedule resolves to the inner mechanism's OWN object,
        # so the engines run the identical trace — no round-index threading
        assert not alg_s.needs_round_index
        assert alg_s.mechanism.at_round(3) is alg_s.mechanism.inner
        r_s = _session(problem, alg_s).run(KEY)
        r_f = _session(problem, make_algorithm(fixed, **kw)).run(KEY)
        _assert_bitwise(r_s, r_f, label=sched)

    def test_constant_schedule_budget_is_fixed_budget(self):
        kw = dict(clip_norm=0.3, sigma=0.2, num_clients=M)
        rep_s = make_algorithm("cdp-fedexp-schedule", **kw).budget(
            DELTA, rounds=ROUNDS, dim=D)
        rep_f = make_algorithm("cdp-fedexp", **kw).budget(
            DELTA, rounds=ROUNDS, dim=D)
        assert rep_s == rep_f  # same floats AND same setting string

    def test_equal_epsilons_reduce_to_homogeneous(self, problem):
        """eps_i all equal: the per-client mechanism short-circuits to
        GaussianLDP's expressions with the common sigma — bit-identical
        under the same (mean) aggregation."""
        mech = PerClientGaussian(0.3, (1.0,) * M, DELTA)
        assert mech.n_scalar_extras == 0  # no mixed-noise extra rides psum
        (common,) = set(mech.sigmas)
        r_h = _session(problem,
                       compose_algorithm(mech, FedEXPStep())).run(KEY)
        r_u = _session(problem, compose_algorithm(
            GaussianLDP(0.3, common), FedEXPStep())).run(KEY)
        _assert_bitwise(r_h, r_u, label="perclient-uniform")

    @pytest.mark.parametrize("sigma,central", [
        (2.0, True), (0.0, True), (0.0, False)])
    def test_scaffold_matches_legacy_loop(self, problem, sigma, central):
        """The migrated session path reproduces the deprecated standalone
        loop bit-for-bit: central mode at ANY sigma (the (d,) server draws
        are shared), local mode at sigma 0 (the legacy monolithic (M,d)
        noise draw is replaced by the engine-reproducible per-row stream,
        identical exactly where no noise is drawn)."""
        data, w0 = problem
        cfg = DPScaffoldConfig(clip_norm=1.0, sigma=sigma, central=central,
                               num_clients=M)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            leg = run_dp_scaffold(cfg, linreg_loss, w0, data.client_batches(),
                                  rounds=ROUNDS, tau=TAU, eta_l=ETA_L,
                                  key=KEY, eval_fn=distance_to_opt(data.w_star))
        alg = make_algorithm("dp-scaffold", clip_norm=1.0, sigma=sigma,
                             central=central, num_clients=M, tau=TAU,
                             eta_l=ETA_L)
        mig = _session(problem, alg,
                       local=LocalSpec(control_variates=True)).run(KEY)
        for field in ("final_w", "last_w", "metric_history"):
            np.testing.assert_array_equal(
                np.asarray(getattr(leg, field)),
                np.asarray(getattr(mig, field)), err_msg=field)
        np.testing.assert_array_equal(np.asarray(mig.eta_history),
                                      np.ones(ROUNDS))

    def test_migrated_scaffold_does_not_warn(self, problem):
        """Satellite: only the LEGACY entry point is deprecated — building
        and running the session composition must emit nothing."""
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            alg = make_algorithm("dp-scaffold", clip_norm=1.0, sigma=0.5,
                                 central=True, num_clients=M, tau=TAU,
                                 eta_l=ETA_L)
            _session(problem, alg, rounds=1,
                     local=LocalSpec(control_variates=True)).run(KEY)


# ---------------------------------------------------------------------------
# 2. Cross-engine parity matrix
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def scan_ref(problem):
    """Scan-engine reference runs, built once per algorithm."""
    cache = {}

    def get(name):
        if name not in cache:
            factory, kw = ALGS[name]
            cache[name] = _session(problem, factory(), **kw).run(KEY)
        return cache[name]

    return get


class TestCrossEngineParity:
    """Every §17 composition, every engine, one contract: rtol 1e-5."""

    @pytest.mark.parametrize("name", sorted(ALGS))
    def test_eager_bit_exact(self, problem, scan_ref, name):
        factory, kw = ALGS[name]
        r = _session(problem, factory(),
                     engine=EngineSpec(engine="eager"), **kw).run(KEY)
        _assert_bitwise(r, scan_ref(name), label=f"{name}.eager")

    @pytest.mark.parametrize("name", sorted(ALGS))
    def test_stream_single_chunk(self, problem, scan_ref, name):
        """One covering chunk degenerates to the dense moment program."""
        factory, kw = ALGS[name]
        r = _session(problem, factory(),
                     engine=EngineSpec(engine="stream"),
                     stream=StreamSpec(chunk_clients=M), **kw).run(KEY)
        _assert_close(r, scan_ref(name), label=f"{name}.stream1")

    @pytest.mark.parametrize("name", sorted(ALGS))
    def test_stream_multi_chunk(self, problem, scan_ref, name):
        """Chunked additive moments reassociate the sums: rtol, not bits."""
        factory, kw = ALGS[name]
        r = _session(problem, factory(),
                     engine=EngineSpec(engine="stream"),
                     stream=StreamSpec(chunk_clients=6), **kw).run(KEY)
        _assert_close(r, scan_ref(name), label=f"{name}.streamN")

    @pytest.mark.parametrize("name", sorted(ALGS))
    def test_sharded(self, problem, scan_ref, name):
        """shard_map + psum (runs 1- and 8-device under the CI matrix);
        the scaffold's variate-table update rides the psum as an extra."""
        factory, kw = ALGS[name]
        r = _session(problem, factory(),
                     shard=ShardSpec(mesh=make_client_mesh()), **kw).run(KEY)
        _assert_close(r, scan_ref(name), label=f"{name}.sharded")

    @pytest.mark.parametrize("name", sorted(ALGS))
    def test_gather_matches_dense_sampled(self, problem, name):
        """Sampled cohorts: the §14 gathered slot table must be the same
        release as the dense masked round (per-client noise and the
        per-client sigma/variate rows key by GLOBAL index)."""
        factory, kw = ALGS[name]
        dense = _session(problem, factory(),
                         cohort=CohortSpec(q=0.5), **kw).run(KEY)
        sparse = _session(problem, factory(),
                          cohort=CohortSpec(q=0.5, gather=True), **kw).run(KEY)
        _assert_close(sparse, dense, label=f"{name}.gather")


# ---------------------------------------------------------------------------
# 3. Telemetry: per-round sigma + the ledger under non-constant schedules
# ---------------------------------------------------------------------------

class TestSigmaTelemetry:
    def test_schedule_sigma_tracks_declared_schedule(self, problem):
        alg = make_algorithm("ldp-fedexp-schedule", clip_norm=0.3, sigma=0.3,
                             decay=0.8, boundaries=(2,), scales=(0.5,))
        sink = _ListTracker()
        _session(problem, alg).run(KEY, tracker=sink)
        rounds = sink.rounds()
        assert len(rounds) == ROUNDS
        for t, event in enumerate(rounds):
            want = alg.mechanism.sigma_value(t)
            # the device computes sigma(t) in f32; compare at f32 rtol
            assert event["sigma"] == pytest.approx(want, rel=1e-5), t
        # the step drop actually happened: sigma(2) < sigma(1) * decay
        assert rounds[2]["sigma"] < 0.9 * rounds[1]["sigma"] * 0.8

    def test_validator_pins_exponential_schedule(self, problem, tmp_path):
        """tools/check_telemetry.py --sigma0/--sigma-decay accepts the
        emitted stream and rejects a wrong declaration (the CI smoke)."""
        import os
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        from check_telemetry import check_stream
        alg = make_algorithm("cdp-fedexp-schedule", clip_norm=0.3, sigma=0.25,
                             num_clients=M, decay=0.9)
        out = tmp_path / "sched.jsonl"
        _session(problem, alg).run(KEY, tracker=JsonlTracker(str(out)))
        text = out.read_text().splitlines()
        assert check_stream(text, rounds=ROUNDS, sigma0=0.25,
                            sigma_decay=0.9) == []
        bad = check_stream(text, rounds=ROUNDS, sigma0=0.25, sigma_decay=0.8)
        assert len(bad) == ROUNDS - 1  # every round but t=0 breaks the pin

    def test_fixed_sigma_algorithms_emit_constant_sigma(self, problem):
        sink = _ListTracker()
        alg = make_algorithm("cdp-fedexp", clip_norm=0.3, sigma=0.2,
                             num_clients=M)
        _session(problem, alg).run(KEY, tracker=sink)
        # the tap payload is f32: 0.2 round-trips at f32 resolution
        assert all(e["sigma"] == pytest.approx(0.2, rel=1e-6)
                   for e in sink.rounds())

    def test_scaffold_emits_its_sigma(self, problem):
        sink = _ListTracker()
        alg = make_algorithm("dp-scaffold", clip_norm=1.0, sigma=0.5,
                             central=True, num_clients=M, tau=TAU,
                             eta_l=ETA_L)
        _session(problem, alg,
                 local=LocalSpec(control_variates=True)).run(KEY, tracker=sink)
        assert all(e["sigma"] == 0.5 for e in sink.rounds())

    def test_non_private_omits_sigma(self, problem):
        sink = _ListTracker()
        _session(problem, make_algorithm("fedexp")).run(KEY, tracker=sink)
        assert all("sigma" not in e for e in sink.rounds())


class TestScheduleLedger:
    """privacy_report == the §15 ledger to 1e-9 under NON-constant sigma."""

    SCHED_KW = dict(clip_norm=0.3, sigma=0.25, num_clients=M, decay=0.9)

    def _sched_session(self, problem, *, rounds=6, **kw):
        return _session(problem,
                        make_algorithm("cdp-fedexp-schedule", **self.SCHED_KW),
                        rounds=rounds, **kw)

    def test_ledger_is_the_composed_schedule(self, problem):
        sink = _ListTracker()
        sess = self._sched_session(problem)
        sess.run(KEY, tracker=sink)
        rounds = sink.rounds()
        alg = sess.algorithm
        for t, event in enumerate(rounds):
            # every prefix of the ledger is the honest composition of the
            # sigmas actually executed so far — not T-th of the final budget
            rep = alg.budget(DELTA, rounds=t + 1, dim=D)
            assert event["ledger_rounds"] == t + 1
            assert abs(event["mu"] - rep.mu) < 1e-9
            assert abs(event["eps"] - rep.eps_numerical) < 1e-9
            assert abs(event["eps_rdp"] - rep.eps_rdp) < 1e-9
        rep = sess.privacy_report(DELTA)
        assert abs(rounds[-1]["eps"] - rep.eps_numerical) < 1e-9
        assert abs(rounds[-1]["mu"] - rep.mu) < 1e-9

    def test_decaying_sigma_ledger_accelerates(self, problem):
        """Decaying sigma spends MORE per later round: the per-round mu
        increments strictly increase (the honest non-uniform composition,
        not a uniform T-fold average)."""
        sink = _ListTracker()
        self._sched_session(problem).run(KEY, tracker=sink)
        mus = [e["mu"] for e in sink.rounds()]
        inc = np.diff(np.square(mus))  # GDP composes in mu^2
        assert np.all(inc > 0)
        assert np.all(np.diff(inc) > 0)

    def test_resume_continues_the_ledger(self, problem, tmp_path):
        ck = str(tmp_path / "ck")
        self._sched_session(problem, rounds=3).run(KEY, checkpoint_dir=ck)
        sink = _ListTracker()
        sess = self._sched_session(problem)
        r = sess.resume(ck, tracker=sink)
        rounds = sink.rounds()
        assert [e["ledger_rounds"] for e in rounds] == [4, 5, 6]
        rep = sess.privacy_report(DELTA)
        assert abs(rounds[-1]["eps"] - rep.eps_numerical) < 1e-9
        # and the resumed trajectory is the uninterrupted one, sigma(t)
        # indexed by the ABSOLUTE round across the checkpoint boundary
        r_ref = self._sched_session(problem).run(KEY)
        np.testing.assert_array_equal(np.asarray(r_ref.final_w),
                                      np.asarray(r.final_w))

    def test_retried_rounds_charge_the_ledger(self, problem, tmp_path):
        """§13 recovery under a schedule: rolled-back rounds re-execute with
        their ORIGINAL sigma(t) (bit-exact with an unkilled run) and the
        retries join the composition the report and ledger agree on."""
        sess = self._sched_session(problem, fault=FaultSpec(watchdog=True),
                                   engine=EngineSpec(chunk_rounds=2))

        def poison_first_attempt(carry, attempt):
            if attempt >= 1:
                return carry
            w = carry[0].at[0].set(jnp.nan)
            return (w,) + tuple(carry[1:])

        sess._inject_divergence = poison_first_attempt
        sink = _ListTracker()
        r = sess.run(KEY, checkpoint_dir=str(tmp_path / "ck"),
                     checkpoint_every=2,
                     on_divergence=RecoveryPolicy(max_retries=2),
                     tracker=sink)
        assert r.fault_round is None
        last = sink.rounds()[-1]
        assert last["ledger_rounds"] == 6 + 1  # one round re-run
        rep = sess.privacy_report(DELTA)
        assert abs(last["eps"] - rep.eps_numerical) < 1e-9
        assert abs(last["mu"] - rep.mu) < 1e-9
        r_ref = self._sched_session(problem, fault=FaultSpec(watchdog=True),
                                    engine=EngineSpec(chunk_rounds=2)).run(KEY)
        np.testing.assert_array_equal(np.asarray(r_ref.final_w),
                                      np.asarray(r.final_w))

    def test_scaffold_ledger_matches_report(self, problem):
        """The two-release scaffold accounting rides the same ledger."""
        sink = _ListTracker()
        alg = make_algorithm("dp-scaffold", clip_norm=1.0, sigma=0.5,
                             central=True, num_clients=M, tau=TAU,
                             eta_l=ETA_L)
        sess = _session(problem, alg, local=LocalSpec(control_variates=True))
        sess.run(KEY, tracker=sink)
        last = sink.rounds()[-1]
        rep = sess.privacy_report(DELTA)
        assert "SCAFFOLD" in rep.setting
        assert abs(last["eps"] - rep.eps_numerical) < 1e-9
        assert abs(last["mu"] - rep.mu) < 1e-9


# ---------------------------------------------------------------------------
# 4. Construction / spec validation
# ---------------------------------------------------------------------------

class TestValidation:
    def test_scaffold_requires_control_variates_spec(self, problem):
        alg = make_algorithm("dp-scaffold", clip_norm=1.0, sigma=0.5,
                             central=True, num_clients=M, tau=TAU,
                             eta_l=ETA_L)
        with pytest.raises(ValueError, match="control_variates"):
            _session(problem, alg)

    def test_control_variates_requires_scaffold_algorithm(self, problem):
        with pytest.raises(ValueError, match="control_variates"):
            _session(problem, make_algorithm("fedexp"),
                     local=LocalSpec(control_variates=True))

    def test_control_variates_excludes_minibatch_fields(self):
        with pytest.raises(ValueError, match="control_variates"):
            LocalSpec(control_variates=True, batch_size=4)

    def test_scaffold_table_must_match_cohort(self, problem):
        alg = make_algorithm("dp-scaffold", clip_norm=1.0, sigma=0.5,
                             central=True, num_clients=M + 1, tau=TAU,
                             eta_l=ETA_L)
        with pytest.raises(ValueError, match="num_clients"):
            _session(problem, alg,
                     local=LocalSpec(control_variates=True)).run(KEY)

    def test_schedule_wraps_only_fixed_sigma_gaussians(self):
        with pytest.raises(ValueError, match="fixed-sigma"):
            NoiseSchedule(inner=CentralGaussian(z_mult=0.5, num_clients=M),
                          decay=0.9)
        with pytest.raises(ValueError, match="NoiseSchedule wraps"):
            NoiseSchedule(inner=PerClientGaussian(0.3, (1.0,) * 4, DELTA),
                          decay=0.9)

    def test_schedule_boundary_validation(self):
        inner = GaussianLDP(0.3, 0.21)
        with pytest.raises(ValueError, match="boundaries"):
            NoiseSchedule(inner=inner, boundaries=(3, 1), scales=(0.5, 0.5))
        with pytest.raises(ValueError, match="one-to-one"):
            NoiseSchedule(inner=inner, boundaries=(2,), scales=())
        with pytest.raises(ValueError, match="decay"):
            NoiseSchedule(inner=inner, decay=0.0)

    def test_per_client_epsilon_validation(self):
        with pytest.raises(ValueError, match="epsilons"):
            PerClientGaussian(0.3, (), DELTA)
        with pytest.raises(ValueError, match="positive"):
            per_client_sigmas((1.0, -1.0), DELTA, 0.3)

    def test_schedule_budget_needs_positive_sigma(self):
        alg = make_algorithm("dp-scaffold", clip_norm=1.0, sigma=0.0,
                             central=True, num_clients=M, tau=TAU,
                             eta_l=ETA_L)
        with pytest.raises(ValueError):
            alg.budget(DELTA, rounds=ROUNDS, dim=D)


# ---------------------------------------------------------------------------
# 5. Accounting properties (hypothesis; pure-python, no jax)
# ---------------------------------------------------------------------------

if HAS_HYPOTHESIS:
    # sigma >= 0.5 keeps every composed mu below ~3.4, where gdp_epsilon's
    # bisection is numerically monotone (the Balle-Wang delta(eps) suffers
    # tail cancellation past mu ~3.9 / eps ~24 — a regime where the
    # guarantee is vacuous anyway); mu itself is asserted monotone exactly
    SIGMAS = st.lists(st.floats(0.5, 5.0, allow_nan=False), min_size=1,
                      max_size=8)
    PROP = settings(max_examples=50, deadline=None)

    class TestAccountingProperties:
        @PROP
        @given(sigmas=SIGMAS)
        def test_schedule_ledger_is_monotone(self, sigmas):
            """Executing one more round never refunds budget: mu and eps of
            every prefix are nondecreasing (the §15 ledger invariant)."""
            mus, epss = [], []
            for t in range(1, len(sigmas) + 1):
                rep = accounting.schedule_ldp_budget(0.3, sigmas[:t], DELTA)
                mus.append(rep.mu)
                epss.append(rep.eps_numerical)
            assert all(a < b + 1e-12 for a, b in zip(mus, mus[1:]))
            assert all(a < b + 1e-9 for a, b in zip(epss, epss[1:]))

        @PROP
        @given(sigmas=SIGMAS, data=st.data())
        def test_composition_is_permutation_invariant(self, sigmas, data):
            """WHEN noise is spent must not matter, only the multiset of
            per-round scales — for the exact q=1 composition and the
            sampled CLT alike."""
            perm = data.draw(st.permutations(sigmas))
            for q in (1.0, 0.25):
                a = accounting.composed_gdp_mu(
                    [2.0 * 0.3 / s for s in sigmas], q=q)
                b = accounting.composed_gdp_mu(
                    [2.0 * 0.3 / s for s in perm], q=q)
                assert a == pytest.approx(b, rel=1e-9)

        @PROP
        @given(sigma=st.floats(0.1, 5.0), rounds=st.integers(1, 20),
               q=st.sampled_from([1.0, 0.25]))
        def test_homogeneous_reduction_is_exact(self, sigma, rounds, q):
            """A uniform schedule must reproduce the uniform accountants
            with the SAME floats — the degenerate case never drifts."""
            mu_u = accounting.composed_gdp_mu([2.0 * 0.3 / sigma] * rounds, q)
            assert mu_u == accounting.subsampled_gdp_mu(2.0 * 0.3 / sigma, q,
                                                        rounds)
            rep_s = accounting.schedule_cdp_budget(0.3, [sigma] * rounds, M,
                                                   DELTA, sampling_q=q)
            rep_f = accounting.cdp_budget(0.3, sigma, M, rounds, DELTA,
                                          sampling_q=q)
            assert rep_s.mu == rep_f.mu
            assert rep_s.eps_numerical == rep_f.eps_numerical
            # rho accumulates per round vs rounds*x: same to float precision
            assert rep_s.eps_rdp == pytest.approx(rep_f.eps_rdp, rel=1e-12)

        @PROP
        @given(eps=st.lists(st.floats(0.2, 8.0), min_size=1, max_size=12))
        def test_heterogeneous_report_is_worst_client(self, eps):
            """The per-client report is the WORST client's guarantee: every
            client's own single-release budget fits within it, and it equals
            the largest-epsilon client's own bound."""
            mech = PerClientGaussian(0.3, tuple(eps), DELTA)
            rep = mech.budget(DELTA, rounds=1, dim=D, sampling_q=1.0,
                              with_numerator=False)
            own = [accounting.ldp_gaussian_budget(0.3, s, DELTA)
                   for s in mech.sigmas]
            assert all(o.mu <= rep.mu + 1e-12 for o in own)
            assert rep.mu == max(o.mu for o in own)
            # calibration inverts the GDP curve: the report recovers the
            # declared worst epsilon (bisection tolerance)
            assert rep.eps_numerical == pytest.approx(max(eps), rel=1e-6)

        @PROP
        @given(eps=st.lists(st.floats(0.2, 8.0), min_size=2, max_size=12,
                            unique=True))
        def test_sigma_calibration_is_antitone(self, eps):
            """A bigger budget buys a smaller sigma: strictly where two
            budgets differ by more than the calibration resolves (1e-6
            relative, as above); budgets a float ULP apart, such as 0.2 and
            0.20000000000000004, may calibrate to one sigma."""
            eps = sorted(eps)
            sigmas = per_client_sigmas(tuple(eps), DELTA, 0.3)
            for e0, e1, s0, s1 in zip(eps, eps[1:], sigmas, sigmas[1:]):
                if e1 > e0 * (1.0 + 1e-6):
                    assert s1 < s0, (e0, e1)
                else:
                    assert s1 <= s0, (e0, e1)

        @PROP
        @given(sigma=st.floats(0.2, 2.0), rounds=st.integers(2, 10),
               decay=st.floats(0.5, 0.99))
        def test_decay_spends_more_than_constant(self, sigma, rounds, decay):
            """sigma(t) <= sigma0 everywhere implies the schedule's budget
            dominates the constant-sigma0 run — and is itself dominated by
            the constant run at the schedule's SMALLEST sigma."""
            sig = [sigma * decay ** t for t in range(rounds)]
            rep = accounting.schedule_ldp_budget(0.3, sig, DELTA)
            lo = accounting.schedule_ldp_budget(0.3, [sigma] * rounds, DELTA)
            hi = accounting.schedule_ldp_budget(0.3, [sig[-1]] * rounds, DELTA)
            assert lo.mu <= rep.mu <= hi.mu
