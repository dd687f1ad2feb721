"""Device time by the program's named scopes, and its host spans, read from
a trace recorded on a TPU v5e, and the layer readers (CPU).

``testdata/e2-cdp-cnn.telemetry.r2.*``: the traced window of
``chipbench/layers.py --workload e2-cdp-cnn.telemetry --rounds 2 --save``,
2-round calls each in a ``chipbench.call`` annotation on one v5e chip, and
the compiled HLO text of the round program those calls ran.
"""
import gzip
import importlib
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import scopes, trace  # noqa: E402

DATA = ROOT / "chipbench" / "testdata"
TRACKED = "e2-cdp-cnn.telemetry.r2"
READERS = ("local_update_ms_per_round", "release_us_per_round",
           "server_step_us_per_round", "tap_ms_per_round",
           "tap_host_ms_per_round")


def profile(name: str):
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(gzip.open(DATA / name).read())


@pytest.fixture(scope="module")
def tracked():
    prof = profile(f"{TRACKED}.xplane.pb.gz")
    text = gzip.open(DATA / f"{TRACKED}.hlo.txt.gz", "rt").read()
    calls = sum(1 for _, _, n in trace.host_spans(prof) if n == trace.WINDOW_SPAN)
    return scopes.reduce(prof, text, chips=1), 2 * calls


@pytest.fixture(scope="module")
def untagged():
    # a program older than the scopes: no HLO text, no program spans
    return scopes.reduce(profile("e2-cdp-cnn.full.r2.xplane.pb.gz"), None, chips=1)


def test_every_op_of_the_round_program_is_found_in_its_hlo(tracked):
    reduced, _ = tracked
    assert reduced["found_share"] == pytest.approx(1.0)


def test_scope_times_and_unscoped_share_out_the_busy_time(tracked):
    reduced, _ = tracked
    assert sum(reduced["scope_s"].values()) == pytest.approx(reduced["busy_s"], rel=1e-2)
    assert set(reduced["scope_s"]) >= {"fedsim.local_update", "fedsim.release",
                                       "fedsim.server_step", "fedsim.eval",
                                       "fedsim.tap"}
    assert "fedsim.psum" not in reduced["scope_s"]
    # the named scopes cover the round program's device time
    assert reduced["scope_s"][scopes.UNSCOPED] < 0.1 * reduced["busy_s"]


def test_host_spans_of_the_tracked_calls(tracked):
    reduced, rounds = tracked
    n = reduced["host_n"]
    assert n["telemetry.emit"] == n["telemetry.ledger"] == n["telemetry.log"] == rounds
    assert n["fedsim.run"] == n["fedsim.dispatch"] == n["telemetry.flush"] == rounds // 2
    assert reduced["host_s"]["telemetry.ledger"] < reduced["host_s"]["telemetry.emit"]


def test_idle_gaps_are_named_by_program_spans(tracked):
    reduced, _ = tracked
    gaps = reduced["idle_gaps"]
    assert gaps and all(t > 0 for _, t in gaps)
    assert all(name.startswith(scopes.SPAN_PREFIXES) or name == "no program span"
               for name, _ in gaps)


def read(metric, reduced, rounds):
    ctx = {"scopes": reduced, "rounds": rounds}
    return importlib.import_module(f"chipbench.metrics.{metric}").read(ctx)


def test_every_layer_reader_reads_the_tracked_recording(tracked):
    reduced, rounds = tracked
    for metric in READERS:
        assert read(metric, reduced, rounds) > 0, metric
    assert read("local_update_ms_per_round", reduced, rounds) == pytest.approx(
        1e3 * reduced["scope_s"]["fedsim.local_update"] / rounds)
    assert read("tap_host_ms_per_round", reduced, rounds) == pytest.approx(
        1e3 * reduced["host_s"]["telemetry.emit"] / rounds)


def test_a_program_without_scopes_reads_nothing(untagged):
    assert set(untagged["scope_s"]) == {scopes.UNSCOPED}
    assert untagged["scope_s"][scopes.UNSCOPED] == pytest.approx(untagged["busy_s"], rel=1e-2)
    assert untagged["host_s"] == {} and untagged["found_share"] == 0.0
    for metric in READERS:
        assert read(metric, untagged, 4) is None, metric
        assert importlib.import_module(f"chipbench.metrics.{metric}").read(
            {"rounds": 4}) is None


def test_hlo_scopes_take_the_first_fedsim_component():
    text = "\n".join([
        '  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata='
        '{op_name="jit(chunk)/while/body/fedsim.release/fedsim.eval/mul" stack_frame_id=2}',
        '  ROOT %copy.1 = f32[8]{0} copy(%fusion.3)',
        '  %dp_aggregate.1 = (f32[1,5120]) custom-call(%pad), custom_call_target='
        '"tpu_custom_call", metadata={op_name='
        '"jit(chunk)/fedsim.release/dp_aggregate/pallas_call"}',
        "ENTRY %main.9 (p: f32[8]) -> f32[8] {",
    ])
    assert scopes.hlo_scopes(text) == {"fusion.3": "fedsim.release", "copy.1": None,
                                       "dp_aggregate.1": "fedsim.release"}


def test_idle_gaps_name_the_innermost_program_span():
    spans = [(0, 100, "fedsim.run"), (40, 60, "fedsim.assemble")]
    gaps = scopes.idle_gaps([(10, 30), (70, 90)], 0, 100, spans, top=3)
    assert gaps == [["fedsim.assemble", 40e-9], ["fedsim.run", 10e-9],
                    ["fedsim.run", 10e-9]]
    assert scopes.idle_gaps([], 0, 10, [], top=1) == [["no program span", 10e-9]]



class RecordedSession:
    """Stands in for the session after the window: lowers to the recorded
    round program."""

    def __init__(self, text: str):
        self.text = text

    def lower(self, key, *, tap: bool):
        assert tap
        return self

    def compile(self):
        return self

    def as_text(self) -> str:
        return self.text


class OlderSession:
    """A session whose ``lower`` takes no ``tap``."""

    def lower(self, key):
        raise AssertionError("not reached")


def test_the_traced_run_reads_every_per_layer_metric_of_its_cell():
    import jax

    from chipbench import cell as cell_mod, run
    from chipbench.peaks import peaks

    prof = profile(f"{TRACKED}.xplane.pb.gz")
    text = gzip.open(DATA / f"{TRACKED}.hlo.txt.gz", "rt").read()
    bench = cell_mod.benchmark()
    workload = "e2-cdp-cnn.telemetry"
    _, cfg, traffic = cell_mod.find(workload, bench)
    rounds = 2 * sum(1 for _, _, n in trace.host_spans(prof) if n == trace.WINDOW_SPAN)
    reduced = trace.reduce(prof, chips=1)
    got, hlo = run.window_scopes(RecordedSession(text), jax.random.PRNGKey(0),
                                 True, prof, chips=1, rounds=rounds)
    assert hlo == text and got == scopes.reduce(prof, text, chips=1)
    ctx = {"cfg": cfg, "traffic": traffic, "rounds": rounds, "chips": 1,
           "window_s": reduced["window_s"], "peaks": peaks("TPU v5 lite"),
           "trace": reduced, "scopes": got}
    metrics = run.read_metrics(bench["per_layer"], workload, ctx)
    assert set(metrics) == {m["name"] for m in bench["per_layer"]
                            if workload in m["workloads"]}
    assert all(m["value"] > 0 for m in metrics.values())


def test_a_program_whose_lower_takes_no_tap_gives_no_scope_readings(untagged):
    import jax

    from chipbench import run

    got, hlo = run.window_scopes(OlderSession(), jax.random.PRNGKey(0), False,
                                 profile("e2-cdp-cnn.full.r2.xplane.pb.gz"),
                                 chips=1, rounds=4)
    assert hlo is None and got == untagged
