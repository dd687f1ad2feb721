"""The round's layers, named in the compiled program and on the host (§15).

Device scopes (``repro.telemetry.spans``) must reach the
``op_name`` metadata of every engine's compiled chunk program, since a
profiler trace's device ops are mapped to layers through it; host spans
(``spans.SPANS``) must reach a ``jax.profiler`` trace of a tracked run, with
the tap's ledger and log spans inside their round's emit span.
"""
import glob
import json
import os
import re
import subprocess
import sys
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fedexp import make_algorithm
from repro.data.synthetic import distance_to_opt, linreg_loss, make_synthetic_linreg
from repro.fedsim import (
    CohortSpec,
    EngineSpec,
    FederatedSession,
    StreamSpec,
    TrainSpec,
)
from repro.telemetry import JsonlTracker, spans

M, D = 32, 16
KEY = jax.random.PRNGKey(11)
ROOT = os.path.join(os.path.dirname(__file__), "..")

ROUND = {spans.LOCAL_UPDATE, spans.RELEASE, spans.SERVER_STEP, spans.EVAL}


@pytest.fixture(scope="module")
def problem():
    data = make_synthetic_linreg(jax.random.PRNGKey(3), M, D)
    return data, jnp.zeros(D)


def _session(problem, rounds=2, **spec_kw):
    data, w0 = problem
    alg = make_algorithm("cdp-fedexp", clip_norm=0.3, sigma=0.2, num_clients=M)
    return FederatedSession(alg, linreg_loss, w0, data.client_batches(),
                            train=TrainSpec(rounds=rounds, tau=2, eta_l=0.1),
                            eval_fn=distance_to_opt(data.w_star), **spec_kw)


def hlo_scopes(text: str) -> set[str]:
    """Every ``fedsim.*`` component of the HLO's ``op_name`` metadata."""
    return {part for name in re.findall(r'op_name="([^"]*)"', text)
            for part in name.split("/") if part.startswith("fedsim.")}


ENGINES = {
    "scan": {},
    "stream": dict(engine=EngineSpec(engine="stream"),
                   stream=StreamSpec(chunk_clients=16)),
    "gather_stream": dict(engine=EngineSpec(engine="stream"),
                          stream=StreamSpec(chunk_clients=16),
                          cohort=CohortSpec(q=0.5, gather=True)),
    "sampled": dict(cohort=CohortSpec(q=0.5)),
}


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_round_scopes_reach_the_compiled_chunk(problem, engine):
    text = _session(problem, **ENGINES[engine]).lower(KEY).compile().as_text()
    assert hlo_scopes(text) == ROUND


@pytest.mark.parametrize("engine", ["scan", "stream"])
def test_tap_lowering_adds_only_the_tap_scope(problem, engine):
    session = _session(problem, **ENGINES[engine])
    off = session.lower(KEY).compile().as_text()
    on = session.lower(KEY, tap=True).compile().as_text()
    assert hlo_scopes(on) - hlo_scopes(off) == {spans.TAP}
    assert hlo_scopes(off) == ROUND
    assert "callback" in on and "callback" not in off


# Run on 4 virtual CPU devices, in a process of its own: the device count is
# fixed when JAX starts.  Prints {config: the scopes of its chunk program}.
SHARDED = """
import json, jax, jax.numpy as jnp
from repro.core.fedexp import make_algorithm
from repro.data.synthetic import distance_to_opt, linreg_loss, make_synthetic_linreg
from repro.fedsim import (CohortSpec, EngineSpec, FederatedSession, ShardSpec,
                          StreamSpec, TrainSpec)
from repro.launch.mesh import make_client_mesh
assert jax.device_count() == 4, jax.devices()
data = make_synthetic_linreg(jax.random.PRNGKey(3), 32, 16)
configs = {
    "sharded": {},
    "sharded_sampled": dict(cohort=CohortSpec(q=0.5)),
    "sharded_stream": dict(engine=EngineSpec(engine="stream"),
                           stream=StreamSpec(chunk_clients=4)),
}
out = {}
for name, kw in configs.items():
    alg = make_algorithm("cdp-fedexp", clip_norm=0.3, sigma=0.2, num_clients=32)
    session = FederatedSession(
        alg, linreg_loss, jnp.zeros(16), data.client_batches(),
        train=TrainSpec(rounds=2, tau=2, eta_l=0.1),
        eval_fn=distance_to_opt(data.w_star),
        shard=ShardSpec(mesh=make_client_mesh(4)), **kw)
    out[name] = {tap: session.lower(jax.random.PRNGKey(11), tap=tap)
                 .compile().as_text() for tap in (False, True)}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded_hlo():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", SHARDED], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("config", ["sharded", "sharded_sampled", "sharded_stream"])
def test_sharded_engines_name_the_psum(sharded_hlo, config):
    off, on = sharded_hlo[config]["false"], sharded_hlo[config]["true"]
    assert hlo_scopes(off) == ROUND | {spans.PSUM}
    assert hlo_scopes(on) == ROUND | {spans.PSUM, spans.TAP}


def _host_events(trace_dir):
    """{span name: [(line, start_ns, end_ns, stats)]} of the trace's host planes."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    events = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in spans.SPANS:
                    events[ev.name].append((line.name, ev.start_ns, ev.end_ns,
                                            dict(ev.stats)))
    return events


def test_tracked_run_writes_every_host_span(problem, tmp_path):
    session = _session(problem)
    session.run(KEY, tracker=JsonlTracker(str(tmp_path / "warm.jsonl")))
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        res = session.run(KEY, tracker=JsonlTracker(str(tmp_path / "run.jsonl")))
        jax.block_until_ready(res.final_w)
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(trace_dir)
    counts = {name: len(ev[name]) for name in spans.SPANS}
    assert counts == {spans.RUN: 1, spans.DISPATCH: 1, spans.ASSEMBLE: 1,
                      spans.FLUSH: 1, spans.EMIT: 2, spans.LEDGER: 2,
                      spans.LOG: 2}
    # one call's spans share its identifier
    (run,) = ev[spans.RUN]
    (dispatch,) = ev[spans.DISPATCH]
    assert run[3]["call"] == dispatch[3]["call"] == 2
    assert dispatch[3]["rounds"] == "0:2"
    assert sorted(e[3]["round"] for e in ev[spans.EMIT]) == [0, 1]
    assert all(e[3]["call"] == 2 for e in ev[spans.EMIT])
    # the ledger and the log of each round run inside its emit span
    for child in (spans.LEDGER, spans.LOG):
        for line, start, end, _ in ev[child]:
            assert any(line == e[0] and e[1] <= start and end <= e[2]
                       for e in ev[spans.EMIT]), child


def test_untracked_run_opens_no_tap_span(problem, tmp_path):
    session = _session(problem)
    jax.block_until_ready(session.run(KEY).final_w)
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        res = session.run(KEY)
        jax.block_until_ready(res.final_w)
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(trace_dir)
    assert {name for name in spans.SPANS if ev[name]} == {
        spans.RUN, spans.DISPATCH, spans.ASSEMBLE}
    np.testing.assert_array_equal(np.asarray(res.final_w),
                                  np.asarray(session.run(KEY).final_w))
