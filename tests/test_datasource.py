"""Host-resident client data: ClientDataSource contract (DESIGN.md §14).

The source protocol decouples WHERE client data lives from the engines that
consume it.  Contracts pinned here:

* ``ArraySource`` (the in-memory default) unwraps to the historical
  device-resident engine — literally the same compiled program, bit-exact;
* host/npz/synthetic sources stream chunk-staged data through the §12 inner
  accumulation in the identical order, matching device-resident runs at the
  engine-parity tolerance (rtol 1e-5; within 1 ulp in practice — the chunk
  add fuses differently across the two programs, see DESIGN.md §14) while
  the STAGING itself is bit-invariant: prefetch depth, source kind, and
  double-buffering never change a single bit;
* kill/resume through a host-resident run reproduces the uninterrupted run
  bit-for-bit (host round keys are the same ``fold_in(key, t)``);
* the session rejects source configurations it cannot honor (non-stream
  engines, client meshes, fault injection, contradictory DataSpec kinds)
  rather than silently mis-staging.
"""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fedexp import make_algorithm
from repro.data.synthetic import linreg_loss, make_synthetic_linreg
from repro.fedsim import (
    ArraySource,
    CohortSpec,
    DataSpec,
    EngineSpec,
    FaultSpec,
    FederatedSession,
    HostArraySource,
    NpzSource,
    ShardSpec,
    StreamSpec,
    SyntheticSource,
    TrainSpec,
)
from repro.launch.mesh import auto_chunk_clients, make_client_mesh

M, D, TAU, ETA_L, ROUNDS, CHUNK = 44, 24, 2, 0.1, 4, 16
KEY = jax.random.PRNGKey(11)


@pytest.fixture(scope="module")
def problem():
    data = make_synthetic_linreg(jax.random.PRNGKey(3), M, D)
    return data.client_batches(), jnp.zeros(D)


def _host_batches(batches):
    return {k: np.asarray(v) for k, v in batches.items()}


def _session(batches, w0, *, rounds=ROUNDS, **kw):
    alg = make_algorithm("ldp-fedexp-gauss", clip_norm=0.3, sigma=0.21)
    kw.setdefault("engine", EngineSpec(engine="stream"))
    kw.setdefault("stream", StreamSpec(chunk_clients=CHUNK))
    return FederatedSession(alg, linreg_loss, w0, batches,
                            train=TrainSpec(rounds=rounds, tau=TAU,
                                            eta_l=ETA_L), **kw)


class TestSourceContract:
    def test_fetch_arbitrary_indices(self, problem):
        """fetch() serves non-monotone indices with repeats — the §14 gather
        path fetches by slot table."""
        batches, _ = problem
        idx = np.asarray([5, 2, 2, 41, 0])
        for src in (ArraySource(batches), HostArraySource(batches)):
            rows = src.fetch(idx)
            np.testing.assert_array_equal(np.asarray(rows["x"]),
                                          np.asarray(batches["x"])[idx])
            assert src.num_clients == M

    def test_npz_round_trip(self, problem):
        batches, _ = problem
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "cohort.npz")
            np.savez(path, **_host_batches(batches))
            src = NpzSource(path)
            assert src.num_clients == M
            assert src.kind == "npz"
            rows = src.fetch(np.asarray([3, 1]))
            np.testing.assert_array_equal(
                np.asarray(rows["y"]), np.asarray(batches["y"])[[3, 1]])

    def test_synthetic_source_is_index_pure(self):
        def gen(idx):
            rng = [np.random.default_rng(1000 + int(i)) for i in idx]
            return {"x": np.stack([r.normal(size=(D,)) for r in rng]),
                    "y": np.zeros(len(idx))}

        src = SyntheticSource(gen, num_clients=10**6)
        a = src.fetch(np.asarray([7, 123456]))
        b = src.fetch(np.asarray([7, 123456]))
        np.testing.assert_array_equal(a["x"], b["x"])
        with pytest.raises(ValueError, match="num_clients"):
            SyntheticSource(gen, num_clients=0)

    def test_mismatched_leading_dims_rejected(self):
        with pytest.raises(ValueError, match="leading"):
            HostArraySource({"x": np.zeros((4, 2)), "y": np.zeros((5,))})


class TestArraySourcePassthrough:
    def test_bit_exact_with_raw_arrays(self, problem):
        """ArraySource unwraps to the device-resident path: the IDENTICAL
        compiled program, bit-for-bit — on the default scan engine too."""
        batches, w0 = problem
        for engine_kw in ({"engine": EngineSpec(), "stream": StreamSpec()},
                          {"engine": EngineSpec(engine="stream"),
                           "stream": StreamSpec(chunk_clients=CHUNK)}):
            raw = _session(batches, w0, **engine_kw).run(KEY)
            wrapped = _session(ArraySource(batches), w0, **engine_kw).run(KEY)
            np.testing.assert_array_equal(np.asarray(raw.final_w),
                                          np.asarray(wrapped.final_w))
            np.testing.assert_array_equal(np.asarray(raw.eta_history),
                                          np.asarray(wrapped.eta_history))


class TestHostResidentRuns:
    def test_matches_device_resident_stream(self, problem):
        batches, w0 = problem
        dev = _session(batches, w0).run(KEY)
        host = _session(HostArraySource(batches), w0).run(KEY)
        np.testing.assert_allclose(np.asarray(host.final_w),
                                   np.asarray(dev.final_w),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(host.eta_history),
                                   np.asarray(dev.eta_history),
                                   rtol=1e-5, atol=1e-6)

    def test_single_chunk_is_bit_exact_with_device(self, problem):
        """One chunk covering the cohort: staging degenerates to one
        device_put and the arithmetic is the identical accumulation."""
        batches, w0 = problem
        dev = _session(batches, w0, stream=StreamSpec(chunk_clients=64)).run(KEY)
        host = _session(HostArraySource(batches), w0,
                        stream=StreamSpec(chunk_clients=64)).run(KEY)
        np.testing.assert_array_equal(np.asarray(host.final_w),
                                      np.asarray(dev.final_w))

    def test_prefetch_depth_is_bit_invariant(self, problem):
        """The double-buffer contract: staging depth changes WHEN transfers
        happen, never WHAT is computed — bit-for-bit across depths."""
        batches, w0 = problem
        runs = [
            _session(HostArraySource(batches), w0,
                     data=DataSpec(kind="host", prefetch=depth)).run(KEY)
            for depth in (1, 2, 4)
        ]
        for other in runs[1:]:
            np.testing.assert_array_equal(np.asarray(runs[0].final_w),
                                          np.asarray(other.final_w))
            np.testing.assert_array_equal(np.asarray(runs[0].eta_history),
                                          np.asarray(other.eta_history))

    def test_source_kind_is_bit_invariant(self, problem):
        """host / npz / synthetic sources serving the same rows produce the
        same bits — the driver is source-blind past fetch()."""
        batches, w0 = problem
        hb = _host_batches(batches)
        host = _session(HostArraySource(batches), w0).run(KEY)
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "cohort.npz")
            np.savez(path, **hb)
            npz = _session(NpzSource(path), w0).run(KEY)
        synth = _session(
            SyntheticSource(lambda idx: {k: v[idx] for k, v in hb.items()},
                            num_clients=M), w0).run(KEY)
        for other in (npz, synth):
            np.testing.assert_array_equal(np.asarray(host.final_w),
                                          np.asarray(other.final_w))

    def test_sampled_gather_matches_dense_reference(self, problem):
        """Host-resident × §14 gather: only ~cap clients are ever fetched,
        and the release matches the dense sampled device run."""
        batches, w0 = problem
        fetched = []

        def spy(idx):
            fetched.append(np.asarray(idx))
            return {k: np.asarray(v)[idx] for k, v in batches.items()}

        dense = _session(batches, w0, engine=EngineSpec(),
                         stream=StreamSpec(),
                         cohort=CohortSpec(q=0.4)).run(KEY)
        host = _session(SyntheticSource(spy, num_clients=M), w0,
                        cohort=CohortSpec(q=0.4, gather=True),
                        stream=StreamSpec(chunk_clients=8)).run(KEY)
        np.testing.assert_allclose(np.asarray(host.final_w),
                                   np.asarray(dense.final_w),
                                   rtol=1e-5, atol=1e-6)
        cap = CohortSpec(q=0.4, gather=True).resolved_cap(M)
        per_round = sum(len(i) for i in fetched) / ROUNDS
        assert per_round <= -(-cap // 8) * 8  # slot grid, not the cohort

    def test_kill_resume_bit_exact(self, problem):
        """Checkpoint/resume drives the host driver through the same carry
        machinery: a killed host-resident run resumes bit-for-bit."""
        batches, w0 = problem
        src = HostArraySource(batches)

        with tempfile.TemporaryDirectory() as tmp:
            full = _session(src, w0).run(KEY, checkpoint_dir=tmp + "/full",
                                         checkpoint_every=2)
            _session(src, w0, rounds=2).run(
                KEY, checkpoint_dir=tmp + "/killed", checkpoint_every=2)
            resumed = _session(src, w0).resume(tmp + "/killed")
        np.testing.assert_array_equal(np.asarray(resumed.final_w),
                                      np.asarray(full.final_w))
        np.testing.assert_array_equal(np.asarray(resumed.eta_history),
                                      np.asarray(full.eta_history))

    def test_run_batched_sweeps_host_session(self, problem):
        batches, w0 = problem
        session = _session(HostArraySource(batches), w0, rounds=2)
        keys = jax.random.split(jax.random.PRNGKey(5), 2)
        batched = session.run_batched(keys)
        single = session.run(keys[1])
        np.testing.assert_array_equal(np.asarray(batched.final_w[1]),
                                      np.asarray(single.final_w))


class TestSessionValidation:
    def test_source_requires_stream_engine(self, problem):
        batches, w0 = problem
        with pytest.raises(ValueError, match="engine='stream'"):
            _session(HostArraySource(batches), w0, engine=EngineSpec(),
                     stream=StreamSpec())

    def test_source_rejects_client_mesh(self, problem):
        batches, w0 = problem
        with pytest.raises(ValueError, match="mesh"):
            _session(HostArraySource(batches), w0,
                     shard=ShardSpec(mesh=make_client_mesh(),
                                     client_axis="clients"))

    def test_source_rejects_fault_injection(self, problem):
        batches, w0 = problem
        with pytest.raises(ValueError, match="fault"):
            _session(HostArraySource(batches), w0,
                     fault=FaultSpec(dropout=0.2))

    def test_dataspec_kind_must_match_input(self, problem):
        batches, w0 = problem
        with pytest.raises(ValueError, match="contradicts"):
            _session(batches, w0, data=DataSpec(kind="host"))
        with pytest.raises(ValueError, match="contradicts"):
            _session(HostArraySource(batches), w0,
                     data=DataSpec(kind="npz"))

    def test_dataspec_validation(self):
        with pytest.raises(ValueError, match="kind"):
            DataSpec(kind="carrier-pigeon")
        with pytest.raises(ValueError, match="prefetch"):
            DataSpec(prefetch=0)


class TestAutoChunk:
    def test_session_resolves_auto(self, problem):
        batches, w0 = problem
        session = _session(batches, w0, stream=StreamSpec(chunk_clients="auto"))
        assert isinstance(session.stream.chunk_clients, int)
        assert session.stream.chunk_clients >= 1
        out = session.run(KEY)
        dense = _session(batches, w0, stream=StreamSpec(chunk_clients=64)).run(KEY)
        np.testing.assert_allclose(np.asarray(out.final_w),
                                   np.asarray(dense.final_w),
                                   rtol=1e-5, atol=1e-6)

    def test_auto_spec_rejected_off_stream(self, problem):
        batches, w0 = problem
        with pytest.raises(ValueError, match="stream"):
            _session(batches, w0, engine=EngineSpec(),
                     stream=StreamSpec(chunk_clients="auto"))

    def test_heuristic_scales_with_budget(self):
        small = auto_chunk_clients(D, 100, budget_bytes=1 << 20)
        large = auto_chunk_clients(D, 100, budget_bytes=1 << 24)
        assert 1 <= small < large

    def test_actionable_error_when_one_client_cannot_fit(self):
        with pytest.raises(ValueError, match="chunk_clients=1"):
            auto_chunk_clients(dim=10**6, client_bytes=0, budget_bytes=1024)

    @pytest.mark.parametrize("platform,stats,want", [
        ("cpu", None, 1 << 30),                   # no limit: 4 GiB fallback
        ("tpu", {"bytes_limit": 16 << 30}, 4 << 30),
        ("tpu", {}, RuntimeError),                # a TPU must report its limit
    ])
    def test_device_memory_budget(self, monkeypatch, platform, stats, want):
        import types

        from repro.launch import mesh as mesh_mod

        device = types.SimpleNamespace(platform=platform, device_kind="test",
                                       memory_stats=lambda: stats)
        monkeypatch.setattr(mesh_mod.jax, "devices", lambda: [device])
        if want is RuntimeError:
            with pytest.raises(RuntimeError, match="bytes_limit"):
                mesh_mod.device_memory_budget()
        else:
            assert mesh_mod.device_memory_budget() == want

    def test_spec_validates_auto_literal(self):
        assert StreamSpec(chunk_clients="auto").is_auto
        with pytest.raises(ValueError, match="auto"):
            StreamSpec(chunk_clients="automatic")
