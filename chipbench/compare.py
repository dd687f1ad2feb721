"""What decides ``correct``: a call of the timed path against the reference.

The last call of the window (its 50 rounds, its run key, the program's own
compiled round program) is followed by the plain reference (``reference.py``)
from the same initial weights, client data and run key, with the same
release noise.  A cell compares the numbers that its
``limits/<workload>.json`` gives a limit:

- ``loss_end``: the mean held-out loss (the CNN's test cross-entropy) of
  the last five rounds, gap as a share of the held-out loss at w0: the
  whole call's training;
- ``eta_target_r1``: round 1's eta_target (mean clipped squared norm over
  the noised mean's squared norm), relative gap.  Round 1 starts both runs
  from one w0, so it holds local training, clipping and the release of all
  M clients before later rounds amplify rounding;
- ``change``: the change of the weights the call hands back (the average
  of the last ``avg_last`` iterates) from w0, by the worst weight kernel
  (every leaf of the weight pytree with two or more dimensions, paired by
  key path): the gap between the two runs' norms of that leaf's change,
  over the larger of the reference's norm of it and of the median leaf's.
  The biases (4 to 32 entries in the CNN) are left out: their change over
  50 rounds is the sum of few noisy coordinates (``PERF.md`` section 6).

Beside them, where the cell streams telemetry, an exact check with the
limit 0: each call's stream holds each round once, in order, with the eta,
eta_target and metric that the call returned.  A call that raises or
returns non-finite weights or histories is counted as failed by the run.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import numpy as np

from chipbench import reference

HERE = Path(__file__).resolve().parent


def limits(workload: str) -> dict[str, float]:
    with open(HERE / "limits" / f"{workload}.json") as f:
        return json.load(f)["limits"]


def rel(a: float, b: float) -> float:
    return abs(float(a) - float(b)) / abs(float(b))


def leaves(tree) -> dict[str, np.ndarray]:
    """Key path -> leaf, as float64 NumPy, of a weight pytree."""
    return {jax.tree_util.keystr(path): np.asarray(leaf, np.float64)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def change_gap(w, w_ref, w0) -> float:
    """Worst weight kernel's gap between the norms of the two runs' changes,
    over the larger of its reference norm and the median leaf's."""
    start = leaves(w0)

    def norms(a):
        return {k: float(np.linalg.norm(v - start[k])) for k, v in leaves(a).items()}
    got, ref = norms(w), norms(w_ref)
    floor = float(np.median(list(ref.values())))
    return max(abs(got[k] - ref[k]) / max(ref[k], floor)
               for k, v in start.items() if v.ndim >= 2)


def readings(out: dict, ref: dict, w0) -> dict[str, float]:
    """The compared numbers of one program call against one reference run."""
    h = ref["history"]
    return {
        "loss_end": abs(float(np.mean(out["metric_history"][-5:]))
                        - float(np.mean(h["loss"][-5:]))) / ref["loss0"],
        "eta_target_r1": rel(out["eta_target_history"][0], h["eta_target"][0]),
        "change": change_gap(out["final_w"], ref["final_w"], w0),
    }


def stream_errors(lines: list[str], out: dict, clients: int) -> int:
    """Rounds whose streamed telemetry is missing, repeated, out of order,
    or differs from what the call returned."""
    rounds = len(out["eta_history"])
    errors, seen = 0, []
    for line in lines:
        try:
            ev = json.loads(line)
        except json.JSONDecodeError:
            errors += 1
            continue
        if "round" not in ev or "event" in ev:
            continue
        t = ev["round"]
        seen.append(t)
        if not 0 <= t < rounds:
            errors += 1
            continue
        for key, hist in (("eta", "eta_history"), ("eta_target", "eta_target_history"),
                          ("metric", "metric_history")):
            v = ev.get(key)
            if v is None or not math.isclose(v, float(out[hist][t]), rel_tol=1e-6):
                errors += 1
                break
        else:
            errors += ev.get("participants") != clients
    return errors + sum(a != b for a, b in zip(seen, range(rounds))) + abs(len(seen) - rounds)


def check(workload: str, cfg: dict, inp: dict, key, out: dict | None,
          streams: list[tuple[list[str], dict]] | None = None) -> dict:
    """Each compared number with its limit, for one finite program call
    ``out`` made with run key ``key`` (None: no call of the window
    completed with finite results)."""
    if out is None:
        return {"no_call_completed": {"value": 1, "limit": 0}}
    lim = limits(workload)
    ref = reference.run(cfg, inp["w0"], inp["batches"], inp["test"], key,
                        rounds=len(out["eta_history"]))
    checks = {name: {"value": value, "limit": lim[name]}
              for name, value in readings(out, ref, inp["w0"]).items()
              if name in lim}
    if streams is not None:
        bad = sum(stream_errors(lines, o, cfg["clients"]) for lines, o in streams)
        checks["stream_errors"] = {"value": bad, "limit": 0}
    return checks


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
