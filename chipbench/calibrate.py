#!/usr/bin/env python3
"""Readings that set the limits of ``correct``, on the chip, at the cell's size.

    python3 chipbench/calibrate.py --workload e2-cdp-cnn.full --seeds 11 12 13 \\
        [--control] [--faults half_batch unchanged_state]

For each seed it builds the cell's inputs and session as a run does, makes
the warm-up call and one call with the window's first run key, and prints
one JSON line per reading: the sound program against the reference
(``"run": "program"``), the control, which is the reference computed in
bfloat16, the precision below the configuration's float32 at default matmul
precision, put in the program's place (``"control"``), and the
program with each named fault planted (``faults.py``).  All seeds and
variants run in one process, which holds the chip.  The benchmark's own
runs never do this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import cell as cell_mod  # noqa: E402
from chipbench.run import require_tpu, to_host, use_compile_cache  # noqa: E402


def program_call(cfg, traffic, inp, key) -> dict:
    import jax

    from chipbench.inputs import run_key

    session = cell_mod.make_session(cfg, traffic, inp)
    warm = session.run(run_key(inp["runs_key"], 0))
    jax.block_until_ready(warm.final_w)
    return to_host(session.run(key))


def control_call(cfg, inp, key, rounds: int) -> dict:
    """The reference in bfloat16, shaped as a program call's output."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench import reference

    ctl = reference.run(cfg, inp["w0"], inp["batches"], inp["test"], key,
                        rounds=rounds, precision="default", dtype=jnp.bfloat16)
    h = ctl["history"]
    return {"eta_history": np.asarray(h["eta"]),
            "eta_target_history": np.asarray(h["eta_target"]),
            "metric_history": np.asarray(h["loss"]), "final_w": ctl["final_w"]}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", nargs="*", default=[])
    args = ap.parse_args()
    entry, cfg, traffic = cell_mod.find(args.workload, cell_mod.benchmark())
    use_compile_cache(cell_mod.CHECKOUT)
    require_tpu(entry["chips"])
    cell_mod.program_path()
    from chipbench import compare, inputs, reference
    from chipbench.faults import FAULTS

    for seed in args.seeds:
        inp = cell_mod.family(cfg).make_inputs(seed, cfg)
        key = inputs.run_key(inp["runs_key"], 1)
        t0 = time.perf_counter()
        ref = reference.run(cfg, inp["w0"], inp["batches"], inp["test"], key,
                            rounds=traffic["rounds_per_call"])
        t_ref = time.perf_counter() - t0
        runs = {"program": lambda: program_call(cfg, traffic, inp, key)}
        if args.control:
            runs["control"] = lambda: control_call(cfg, inp, key, traffic["rounds_per_call"])
        for name in args.faults:
            def faulty(name=name):
                with FAULTS[name]():
                    return program_call(cfg, traffic, inp, key)
            runs[name] = faulty
        for name, call in runs.items():
            out = call()
            line = {"workload": args.workload, "seed": seed, "run": name,
                    "reference_s": t_ref, **compare.readings(out, ref, inp["w0"])}
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
