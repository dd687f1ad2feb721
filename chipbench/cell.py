"""A cell of ``BENCHMARK.json``: its configuration, its traffic mix, its
model family, and the program's ``FederatedSession`` built for them.

Everything that belongs to one configuration or one traffic mix is a data
file found by name: ``configs/<config>.json`` and ``traffic/<traffic>.json``
beside this module.  What depends on the model is the module of the
family the configuration names, ``families/<family>.py``.  This is the only
module that builds the system under test; it reaches the program through
its public entry points alone.
"""
from __future__ import annotations

import importlib
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(CHECKOUT / "BENCHMARK.json")


def find(workload: str, bench: dict) -> tuple[dict, dict, dict]:
    """(the workloads entry, its configuration, its traffic mix)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    cfg = load_json(HERE / "configs" / f"{cell['config']}.json")
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return cell, cfg, traffic


def family(cfg: dict):
    """The module of the configuration's model family (``families/``)."""
    if "family" not in cfg:
        raise KeyError(f"configuration {cfg.get('name')!r} names no "
                       f"\"family\"; chipbench/families/ has one module each")
    return importlib.import_module(f"chipbench.families.{cfg['family']}")


def program_path() -> None:
    """Make the program's package importable (it lives under ``src/``)."""
    src = str(CHECKOUT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def make_session(cfg: dict, traffic: dict, inputs: dict):
    """The session a user of the simulator would build for this cell.

    Scan engine, ``backend="auto"`` (on a TPU the Pallas ``dp_aggregate``
    kernel clips, noises and reduces), default matmul precision, the
    family's ``program_loss`` for training and for the eval of the held-out
    set after every round.  ``traffic["chips"] > 1`` shards the cohort over
    a ``clients`` mesh of that many chips.
    """
    program_path()
    from repro.core.fedexp import make_algorithm
    from repro.fedsim import (EngineSpec, FederatedSession, ShardSpec,
                              TrainSpec)

    kwargs = dict(clip_norm=cfg["clip"], sigma=cfg["sigma"], backend="auto")
    if cfg["mechanism"] == "cdp":
        kwargs["num_clients"] = cfg["clients"]
    algorithm = make_algorithm(cfg["algorithm"], **kwargs)
    mesh = None
    if traffic["chips"] > 1:
        from repro.launch.mesh import make_client_mesh

        mesh = make_client_mesh(traffic["chips"])
    loss = family(cfg).program_loss(cfg)
    test = inputs["test"]
    return FederatedSession(
        algorithm, loss, inputs["w0"], inputs["batches"],
        train=TrainSpec(rounds=traffic["rounds_per_call"], tau=cfg["tau"],
                        eta_l=cfg["eta_l"], avg_last=cfg["avg_last"]),
        engine=EngineSpec(engine=traffic["engine"]),
        shard=ShardSpec(mesh=mesh),
        eval_fn=lambda params: loss(params, test))
