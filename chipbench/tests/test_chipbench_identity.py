"""The harness feeds the program and follows the reference as it did before
the CNN's code moved into ``families/cnn.py`` (CPU).

The digests and readings below were recorded with the harness as it stood
before the move (inputs from ``chipbench/inputs.py``, the reference's CNN
in ``chipbench/reference.py``), seed 2**31 + 29:

- a sha256 of the inputs (w0, client batches, test set), at the cells' own
  size and at the tests' small size (16 clients of 12 images, 100 test
  images), each leaf by key path, dtype, shape and bytes;
- the reference's whole history, its loss at w0 and a sha256 of its
  ``final_w``, at the small size, 3 rounds from the run key of call 1,
  compared exactly.

The round program each cell lowers is compared with the one lowered from a
session built as the harness built it then, from the program's public entry
points, so that the test follows the program where the program changes.
"""
import copy
import hashlib
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import cell as cell_mod  # noqa: E402

SEED = 2**31 + 29
INPUTS = {
    "full": {
        "w0": "0a33164da4b2afe7b260ea592a1a9dabd5f13c56f2c80e0513c4e7e0134e5c4f",
        "batches": "6dd19698f8e3d475dee25e1d87f89a4ebe217e84ab1782a4e4006bdbfb956401",
        "test": "96817ddf2ee8541a24d94dfaab88b480e60ff754929b2dae66138fea8c2dc2bf",
    },
    "small": {
        "w0": "0a33164da4b2afe7b260ea592a1a9dabd5f13c56f2c80e0513c4e7e0134e5c4f",
        "batches": "ed50c44fbd4b145263be6d544dbcc07218cf640a69177890f56865c05362b222",
        "test": "f4781236da94a07ad835f7a3d3c2a1f289b23fe236590c4b0bd263516dcd6a5c",
    },
}
HISTORY = {
    "agg_sq": [7.978963851928711, 8.094354629516602, 7.7866668701171875],
    "eta": [1.6732228994369507, 1.836555004119873, 1.7443361282348633],
    "eta_target": [0.11142633855342865, 0.11452934890985489, 0.12347840517759323],
    "loss": [2.3530147075653076, 2.615337371826172, 2.7148196697235107],
    "mean_sq_clipped": [0.889066755771637, 0.9270411729812622, 0.9614852070808411],
}
LOSS0 = 2.5305256843566895
FINAL_W = "356fe1932226666f456c8afefa1585b3042b1ac1a0de859c0051748f49d6bb11"


def digest(tree) -> str:
    import jax
    import numpy as np

    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}|".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def config(size: str) -> dict:
    _, cfg, _ = cell_mod.find("e2-cdp-cnn.full", cell_mod.benchmark())
    if size == "small":
        cfg = copy.deepcopy(cfg)
        cfg["clients"] = 16
        cfg["data"].update(train_images=16 * 12, test_images=100)
    return cfg


@pytest.fixture(scope="module")
def full_inputs():
    return cell_mod.family(config("full")).make_inputs(SEED, config("full"))


@pytest.mark.parametrize("size", ["full", "small"])
def test_inputs_are_as_before_the_move(size, full_inputs):
    cfg = config(size)
    inp = full_inputs if size == "full" else cell_mod.family(cfg).make_inputs(SEED, cfg)
    assert {k: digest(inp[k]) for k in INPUTS[size]} == INPUTS[size]


def test_the_reference_follows_as_before_the_move():
    from chipbench import inputs, reference

    cfg = config("small")
    inp = cell_mod.family(cfg).make_inputs(SEED, cfg)
    ref = reference.run(cfg, inp["w0"], inp["batches"], inp["test"],
                        inputs.run_key(inp["runs_key"], 1), rounds=3)
    assert ref["history"] == HISTORY
    assert ref["loss0"] == LOSS0
    assert digest(ref["final_w"]) == FINAL_W


def session_as_before(cfg: dict, traffic: dict, inp: dict):
    """The session as the harness built it for a one-chip CNN cell before
    the move."""
    from repro.core.fedexp import make_algorithm
    from repro.fedsim import (EngineSpec, FederatedSession, ShardSpec,
                              TrainSpec)
    from repro.models.cnn import pytree_xent_loss

    algorithm = make_algorithm(cfg["algorithm"], clip_norm=cfg["clip"],
                               sigma=cfg["sigma"], backend="auto",
                               num_clients=cfg["clients"])
    loss = pytree_xent_loss()
    test = inp["test"]
    return FederatedSession(
        algorithm, loss, inp["w0"], inp["batches"],
        train=TrainSpec(rounds=traffic["rounds_per_call"], tau=cfg["tau"],
                        eta_l=cfg["eta_l"], avg_last=cfg["avg_last"]),
        engine=EngineSpec(engine=traffic["engine"]),
        shard=ShardSpec(mesh=None),
        eval_fn=lambda params: loss(params, test))


@pytest.mark.parametrize("workload", ["e2-cdp-cnn.full", "e2-cdp-cnn.telemetry"])
def test_each_cell_lowers_the_round_program_as_before_the_move(workload, full_inputs):
    from chipbench import inputs

    _, cfg, traffic = cell_mod.find(workload, cell_mod.benchmark())
    key = inputs.run_key(full_inputs["runs_key"], 0)
    tap = bool(traffic["tracker"])
    texts = [make(cfg, traffic, full_inputs).lower(key, tap=tap).as_text()
             for make in (cell_mod.make_session, session_as_before)]
    assert texts[0] == texts[1]
