"""A model family is new files only (CPU).

A two-layer MLP on seeded random vectors, with a nested weight pytree
(``{"l1": {"w", "b"}, "l2": {"w", "b"}}``), is defined here as a family
module and registered as ``chipbench.families.mlp_test``; its configuration
and its ``BENCHMARK.json`` entries are held in memory.  It runs through
``run.run_cell`` with no edit to any harness file: the run is correct, a
planted fault is refused, and ``mfu`` reads the family's ``round_flops``.
"""
import copy
import math
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import cell as cell_mod, compare  # noqa: E402

SEED = 2**31 + 41
FAMILY = "mlp_test"
WORKLOAD = "mlp-test.full"
CFG = {"name": "mlp-test", "family": FAMILY, "algorithm": "cdp-fedexp",
       "mechanism": "cdp", "clients": 8, "tau": 3, "eta_l": 0.5, "clip": 1.0,
       "sigma": 0.05, "avg_last": 2,
       "data": {"per_client": 8, "test": 64, "features": 16, "classes": 4},
       "model": {"hidden": 32}}
TRAFFIC = {"rounds_per_call": 3, "engine": "scan", "tracker": False, "chips": 1}


# -- the family ------------------------------------------------------------------

def make_inputs(seed: int, cfg: dict) -> dict:
    """Labels from a random linear teacher; He-initialised weights."""
    import jax
    import jax.numpy as jnp

    from chipbench.inputs import stream_keys

    keys = stream_keys(seed)
    m, data, h = cfg["clients"], cfg["data"], cfg["model"]["hidden"]
    f, c, n = data["features"], data["classes"], data["per_client"]

    @jax.jit
    def make(key):
        k_teacher, k_x, k_test, k1, k2 = jax.random.split(key, 5)
        teacher = jax.random.normal(k_teacher, (f, c))
        x = jax.random.normal(k_x, (m, n, f))
        test_x = jax.random.normal(k_test, (data["test"], f))
        w0 = {"l1": {"w": jax.random.normal(k1, (f, h)) * math.sqrt(2 / f),
                     "b": jnp.zeros(h)},
              "l2": {"w": jax.random.normal(k2, (h, c)) * math.sqrt(2 / h),
                     "b": jnp.zeros(c)}}
        batches = {"x": x, "y": jnp.argmax(x @ teacher, -1),
                   "mask": jnp.ones((m, n), jnp.float32)}
        return batches, {"x": test_x, "y": jnp.argmax(test_x @ teacher, -1)}, w0

    batches, test, w0 = make(keys["weights"])
    return {"batches": batches, "test": test, "w0": w0, "runs_key": keys["runs"]}


def xent(params, batch):
    import jax
    import jax.numpy as jnp

    hidden = jax.nn.relu(batch["x"] @ params["l1"]["w"] + params["l1"]["b"])
    logp = jax.nn.log_softmax(hidden @ params["l2"]["w"] + params["l2"]["b"])
    nll = -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1)[:, 0]
    mask = batch.get("mask")
    if mask is None:
        return jnp.mean(nll)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def matmul_flops(cfg: dict) -> int:
    data, h = cfg["data"], cfg["model"]["hidden"]
    return 2 * (data["features"] * h + h * data["classes"])


FUNCTIONS = {
    "make_inputs": make_inputs,
    "program_loss": lambda cfg: xent,
    "ref_loss": lambda params, batch, cfg: xent(params, batch),
    "ref_test_loss": lambda params, test, cfg: xent(params, test),
    "ref_block": lambda cfg: cfg["clients"] // 2,
    "round_flops": lambda cfg: (
        3 * matmul_flops(cfg) * cfg["clients"] * cfg["data"]["per_client"] * cfg["tau"]
        + matmul_flops(cfg) * cfg["data"]["test"]),
    "params": lambda cfg: sum(a * b + b for a, b in (
        (cfg["data"]["features"], cfg["model"]["hidden"]),
        (cfg["model"]["hidden"], cfg["data"]["classes"]))),
}


@pytest.fixture(scope="module")
def family():
    import chipbench.families  # noqa: F401  (the package the module joins)

    name = f"chipbench.families.{FAMILY}"
    module = types.ModuleType(name)
    module.__dict__.update(FUNCTIONS)
    sys.modules[name] = module
    try:
        yield module
    finally:
        del sys.modules[name]


@pytest.fixture
def bench(monkeypatch):
    """BENCHMARK.json with the entries a new family's PR appends, and its
    limits (the CNN cell's, in place of ``limits/mlp-test.full.json``)."""
    bench = copy.deepcopy(cell_mod.benchmark())
    bench["workloads"].append({"name": WORKLOAD, "config": CFG["name"],
                               "traffic": "full", "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "rounds_per_s.mlp-test", "unit": "rounds/s",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock", "workloads": [WORKLOAD]})
    bench["per_layer"].append({"name": "mfu.mlp-test", "unit": "%", "better": "higher",
                               "source": "host_clock", "layer": "round step",
                               "moves": "rounds_per_s.mlp-test",
                               "workloads": [WORKLOAD]})
    limits = compare.limits("e2-cdp-cnn.full")
    monkeypatch.setattr(compare, "limits", lambda workload: limits)
    return bench


def run_mlp(bench, fault=None) -> dict:
    import contextlib

    import jax

    from chipbench import run
    from chipbench.faults import FAULTS

    entry = bench["workloads"][-1]
    with FAULTS[fault]() if fault else contextlib.nullcontext():
        return run.run_cell(WORKLOAD, entry, CFG, TRAFFIC, seed=SEED, seconds=0.0,
                            trace_on=False, devices=jax.devices(), bench=bench)


def test_a_new_family_runs_correct_through_run_cell(family, bench):
    import jax

    assert cell_mod.family(CFG) is family
    inp = family.make_inputs(SEED, CFG)
    assert jax.tree_util.tree_structure(inp["w0"]).num_leaves == 4
    result = run_mlp(bench)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"rounds_per_s.mlp-test", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result["checks"]) == {"loss_end", "eta_target_r1", "change"}


def test_a_planted_fault_is_refused_in_a_new_family(family, bench):
    result = run_mlp(bench, "unchanged_state")
    assert not result["correct"], result["checks"]
    assert result["checks"]["change"]["value"] == pytest.approx(1.0)


def test_mfu_reads_the_family_round_flops(family, bench):
    from chipbench import run
    from chipbench.peaks import peaks

    ctx = {"cfg": CFG, "rounds": 30, "window_s": 2.0, "chips": 1,
           "peaks": peaks("TPU v5 lite")}
    got = run.read_metrics(bench["per_layer"], WORKLOAD, ctx)
    assert set(got) == {"mfu.mlp-test"}
    assert got["mfu.mlp-test"]["value"] == pytest.approx(
        100 * family.round_flops(CFG) * 15 / 197e12, rel=1e-12)
