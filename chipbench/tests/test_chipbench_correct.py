"""``correct`` holds a sound run and refuses the control and each fault.

The rest of a run, everything after the look for a chip, driven on the CPU
at a size a test run can hold: 16 clients of 12 images, the test set cut to
100 images, 3-round calls.  The widths and the cell's own limits
(``limits/<workload>.json``) are as on the chip.
"""
import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import cell as cell_mod, compare  # noqa: E402

SEED = 2**31 + 29


def small(workload: str):
    bench = cell_mod.benchmark()
    entry, cfg, traffic = cell_mod.find(workload, bench)
    cfg = copy.deepcopy(cfg)
    cfg["clients"] = 16
    cfg["data"].update(train_images=16 * 12, test_images=100)
    return entry, cfg, dict(traffic, rounds_per_call=3), bench


def run_small(workload: str, fault=None) -> dict:
    import contextlib

    import jax

    from chipbench import run
    from chipbench.faults import FAULTS

    entry, cfg, traffic, bench = small(workload)
    with FAULTS[fault]() if fault else contextlib.nullcontext():
        return run.run_cell(workload, entry, cfg, traffic, seed=SEED,
                            seconds=0.0, trace_on=False, devices=jax.devices(),
                            bench=bench)


@pytest.mark.parametrize("workload", ["e2-cdp-cnn.full", "e2-cdp-cnn.telemetry"])
def test_a_sound_run_is_correct(workload):
    result = run_small(workload)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["checks"]) >= set(compare.limits(workload))
    bench = cell_mod.benchmark()
    assert set(result["metrics"]) == {
        m["name"] for m in bench["end_to_end"]
        if workload in m.get("workloads", [workload])}


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_a_planted_fault_is_not_correct(fault):
    result = run_small("e2-cdp-cnn.full", fault)
    assert not result["correct"], result["checks"]


def test_the_bfloat16_control_is_not_correct():
    from chipbench import calibrate, inputs, reference

    _, cfg, traffic, _ = small("e2-cdp-cnn.full")
    inp = cell_mod.family(cfg).make_inputs(SEED, cfg)
    key = inputs.run_key(inp["runs_key"], 1)
    rounds = traffic["rounds_per_call"]
    ref = reference.run(cfg, inp["w0"], inp["batches"], inp["test"], key, rounds=rounds)
    control = calibrate.control_call(cfg, inp, key, rounds)
    got = compare.readings(control, ref, inp["w0"])
    lim = compare.limits("e2-cdp-cnn.full")
    assert any(got[k] > lim[k] for k in lim), (got, lim)
