"""Property-based tests (hypothesis) for fault degradation (DESIGN.md §13).

The invariant: ANY garbage update block (NaN / Inf / huge rows) under ANY
dropout mask degrades to finite moments and a finite global model — for
every registry algorithm, on the dense and streaming engines alike.  The
deterministic twin in ``tests/test_faults.py`` covers the same contract
where hypothesis is unavailable.
"""
import pytest

hypothesis = pytest.importorskip("hypothesis")

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.core.fedexp import make_algorithm
from repro.data.synthetic import linreg_loss, make_synthetic_linreg
from repro.fedsim import (
    EngineSpec,
    FaultSpec,
    FederatedSession,
    LocalSpec,
    StreamSpec,
    TrainSpec,
)
from repro.fedsim.faults import apply_faults, fault_masks

M, D, ETA_L = 44, 24, 0.1

# mirrors tests/test_faults.py's registry-complete table (pinned there
# against list_algorithms())
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
    "ldp-gauss-fedadam": dict(clip_norm=0.3, sigma=0.21, server_lr=0.05),
    "cdp-fedmom": dict(clip_norm=0.3, sigma=0.2, num_clients=M, server_lr=0.5),
    "privunit-fedexp-adaptive-clip": dict(eps0=2.0, eps1=2.0, eps2=2.0,
                                          z_mult=0.5, num_clients=M, dim=D),
    # §17 tier (tau/eta_l mirror the TrainSpec below)
    "ldp-fedexp-perclient": dict(clip_norm=0.3,
                                 epsilons=tuple(2.0 + 0.5 * (i % 4)
                                                for i in range(M)),
                                 delta=1e-5),
    "ldp-fedexp-schedule": dict(clip_norm=0.3, sigma=0.21, decay=0.9),
    "cdp-fedexp-schedule": dict(clip_norm=0.3, sigma=0.2, num_clients=M,
                                decay=0.9),
    "dp-scaffold": dict(clip_norm=0.3, sigma=0.2, num_clients=M,
                        central=True, tau=1, eta_l=ETA_L),
}

SETTINGS = dict(deadline=None, max_examples=25,
                suppress_health_check=[hypothesis.HealthCheck.too_slow])


@pytest.fixture(scope="module")
def problem():
    data = make_synthetic_linreg(jax.random.PRNGKey(3), M, D)
    return data, jnp.zeros(D)


@st.composite
def garbage_rows(draw, max_m=12, max_d=16):
    """(m, d) update block where arbitrary entries carry NaN/Inf/huge
    garbage, plus an arbitrary participation mask."""
    m = draw(st.integers(2, max_m))
    d = draw(st.integers(2, max_d))
    seed = draw(st.integers(0, 2**31 - 1))
    # a writable copy: np.asarray of a jax array is a read-only view
    base = np.array(jax.random.normal(jax.random.PRNGKey(seed), (m, d)),
                    dtype=np.float32)
    poison = draw(st.lists(
        st.tuples(st.integers(0, m - 1), st.integers(0, d - 1),
                  st.sampled_from([np.nan, np.inf, -np.inf, 1e38])),
        max_size=m))
    for i, j, v in poison:
        base[i, j] = np.float32(v)
    mask = np.asarray(draw(st.lists(st.sampled_from([0.0, 1.0]),
                                    min_size=m, max_size=m)), dtype=np.float32)
    return base, mask


class TestGarbageRowProperties:
    @given(data=garbage_rows(), drop_seed=st.integers(0, 2**31 - 1),
           dropout=st.floats(0.0, 0.9))
    @settings(**SETTINGS)
    def test_apply_faults_always_finite(self, data, drop_seed, dropout):
        """ANY garbage block under ANY dropout mask degrades to finite rows
        with the bad rows zero-weighted — the where-gated masked-moment
        contract that makes 0*NaN impossible."""
        deltas, mask = data
        m = deltas.shape[0]
        alive = None
        if dropout > 0.0:
            alive = fault_masks(FaultSpec(dropout=dropout),
                                jax.random.PRNGKey(drop_seed), m)[0]
        out, eff = apply_faults(jnp.asarray(deltas), jnp.asarray(mask),
                                alive, None)
        out, eff = np.asarray(out), np.asarray(eff)
        assert np.all(np.isfinite(out))
        bad = ~np.all(np.isfinite(deltas), axis=-1)
        assert np.all(eff[bad] == 0.0)
        np.testing.assert_array_equal(out[bad], np.zeros_like(out[bad]))
        assert np.all(eff <= mask)

    @given(name=st.sampled_from(sorted(ALG_KWARGS)),
           engine=st.sampled_from(["scan", "stream"]),
           seed=st.integers(0, 2**31 - 1),
           corrupt=st.floats(0.01, 0.5), dropout=st.floats(0.0, 0.9))
    @settings(deadline=None, max_examples=15,
              suppress_health_check=[hypothesis.HealthCheck.too_slow])
    def test_faulty_round_keeps_global_model_finite(self, problem, name,
                                                    engine, seed, corrupt,
                                                    dropout):
        """NaN-corrupted rows under any dropout rate leave the round moments
        and the global model finite, for every registry algorithm, on the
        dense and streaming engines alike."""
        data, w0 = problem
        alg = make_algorithm(name, **ALG_KWARGS[name])
        kw = dict(engine=EngineSpec(engine="stream"),
                  stream=StreamSpec(chunk_clients=16)) if engine == "stream" \
            else {}
        if name == "dp-scaffold":
            kw["local"] = LocalSpec(control_variates=True)
        sess = FederatedSession(
            alg, linreg_loss, w0, data.client_batches(),
            train=TrainSpec(rounds=2, tau=1, eta_l=ETA_L),
            fault=FaultSpec(dropout=dropout, corrupt=corrupt), **kw)
        r = sess.run(jax.random.PRNGKey(seed))
        assert np.all(np.isfinite(np.asarray(r.final_w)))
        assert np.all(np.isfinite(np.asarray(r.last_w)))
        assert np.all(np.isfinite(np.asarray(r.eta_history)))
