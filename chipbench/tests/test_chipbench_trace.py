"""The reduction of a trace recorded on a TPU v5e, and the metric readers (CPU).

``testdata/e2-cdp-cnn.full.r2.xplane.pb.gz``: two 2-round calls of the
e2-cdp-cnn.full session, each in a ``chipbench.call`` annotation, traced on
one v5e chip, by a program whose ``dp_aggregate`` kernel had no stable name
yet (its Mosaic calls are ``%_impl.N``).
``testdata/e2-cdp-cnn.telemetry.r2.xplane.pb.gz``: the same for the
e2-cdp-cnn.telemetry session, by a program that names the kernel
(``%dp_aggregate.N``).
"""
import gzip
import importlib
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import trace  # noqa: E402
from chipbench.peaks import peaks  # noqa: E402

DATA = ROOT / "chipbench" / "testdata"
CFG = json.loads((ROOT / "chipbench" / "configs" / "e2-cdp-cnn.json").read_text())


def profile(name: str):
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(gzip.open(DATA / name).read())


@pytest.fixture(scope="module")
def one_chip():
    return trace.reduce(profile("e2-cdp-cnn.full.r2.xplane.pb.gz"), chips=1)


@pytest.fixture(scope="module")
def named():
    return trace.reduce(profile("e2-cdp-cnn.telemetry.r2.xplane.pb.gz"), chips=1)


def test_busy_and_idle_over_the_annotated_window(one_chip):
    assert one_chip["window_s"] == pytest.approx(0.292450029)
    assert one_chip["busy_s"] == pytest.approx(0.282727999)
    gaps = one_chip["breakdown"]["idle_gaps"]
    assert len(gaps) == 10 and gaps[0] == ["chipbench.call", pytest.approx(0.002059829)]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)


def test_kernel_events_are_the_round_programs_mosaic_call(named, one_chip):
    # one dp_aggregate call a round: 2 calls of 2 rounds
    assert named["dp_aggregate_events"] == 4
    assert named["dp_aggregate_s"] == pytest.approx(4 * 16.537e-6, rel=1e-6)
    # a Mosaic call of another name is not dp_aggregate
    assert one_chip["dp_aggregate_events"] == 0 and one_chip["dp_aggregate_s"] == 0


def test_device_ops_leave_out_control_flow(one_chip):
    ops = one_chip["breakdown"]["device_ops"]
    assert len(ops) == 10 and not any(trace.is_container(n) for n, _ in ops)
    assert ops[0][0] == "fusion.350" and ops[0][1] == pytest.approx(0.068694769)


def test_op_names_parse_from_hlo_text():
    call = '(f32[1,5120]) custom-call(%pad.68), custom_call_target="tpu_custom_call"'
    text = f"%dp_aggregate.10 = {call}"
    assert trace.op_name(text) == "dp_aggregate.10" and trace.is_dp_aggregate(text)
    assert not trace.is_dp_aggregate(f"%_impl.10 = {call}")
    assert not trace.is_dp_aggregate(f"%flash_attention.3 = {call}")
    assert not trace.is_dp_aggregate("%dp_aggregate.4 = f32[2] fusion(%p)")
    assert trace.is_container("%while.27 = (s32[]) while(%tuple)")
    assert not trace.is_container("%fusion.1 = f32[2] fusion(%while.27)")


def test_union_merges_and_clips():
    assert trace.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10) == [(1, 4), (5, 10)]
    assert trace.union([(0, 1)], 2, 3) == []


def test_idle_gaps_are_named_by_the_innermost_host_span():
    spans = [(0, 100, "chipbench.call"), (40, 60, "Allocate")]
    gaps = trace.idle_gaps([(10, 30), (70, 90)], 0, 100, spans, top=2)
    assert gaps == [["Allocate", 40e-9], ["chipbench.call", 10e-9]]


def read(metric, reduced, rounds, window_s=0.29245, chips=1):
    ctx = {"trace": reduced, "cfg": CFG, "rounds": rounds, "window_s": window_s,
           "chips": chips, "peaks": peaks("TPU v5 lite")}
    return importlib.import_module(f"chipbench.metrics.{metric}").read(ctx)


def test_metric_readers_on_the_recorded_trace(one_chip, named):
    from chipbench.counts import round_flops

    assert read("mfu", one_chip, 4) == pytest.approx(
        100 * round_flops(CFG) * 4 / 0.29245 / 197e12)
    assert read("device_idle_share", one_chip, 4) == pytest.approx(
        100 * (1 - 0.282727999 / 0.292450029))
    assert read("dp_aggregate_us_per_round", named, 4) == pytest.approx(16.537, rel=1e-6)


def test_readers_that_find_nothing_return_none(one_chip):
    empty = {"window_s": 0.0, "busy_s": 0.0, "dp_aggregate_s": 0.0,
             "dp_aggregate_events": 0}
    for metric in ("mfu", "device_idle_share", "dp_aggregate_us_per_round"):
        assert read(metric, empty, 0) is None
    assert read("dp_aggregate_us_per_round", one_chip, 4) is None
