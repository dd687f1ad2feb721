#!/usr/bin/env python3
"""A cell's round split by the program's own layers, on the chip.

    python3 chipbench/layers.py --workload e2-cdp-cnn.telemetry --seed 7 --seconds 30

Builds the cell's session as ``run.py`` does, warms it up, runs one window
of back-to-back calls untraced and one traced (``run.measure``, both
``--seconds`` long), then reads the traced window by the program's named
scopes and host spans (``run.window_scopes``, as the traced run of
``run.py`` reads them).  Its last stdout line is one JSON object: both
windows' rounds a second (their ratio is what tracing costs), the cell's
``per_layer`` metrics of ``BENCHMARK.json``, read by ``metrics/<name>.py``
as ``run.py`` reads them, and the scope breakdown, which is on stderr too.

``--rounds`` shortens the cell's calls and ``--save PREFIX`` writes the
traced window's ``.xplane.pb`` and the round program's HLO text, gzipped,
to ``PREFIX.xplane.pb.gz`` and ``PREFIX.hlo.txt.gz``: the recording that
``chipbench/tests/test_chipbench_scopes.py`` reads.  The benchmark's own runs never
run this script.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import cell as cell_mod  # noqa: E402
from chipbench.run import (  # noqa: E402
    CompileCounter,
    log,
    measure,
    read_metrics,
    require_tpu,
    use_compile_cache,
    window_scopes,
)


def save(prefix: str, trace_dir: str, hlo_text: str | None) -> None:
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    Path(prefix).parent.mkdir(parents=True, exist_ok=True)
    with open(path, "rb") as src, gzip.open(f"{prefix}.xplane.pb.gz", "wb",
                                            compresslevel=9) as dst:
        dst.write(src.read())
    with gzip.open(f"{prefix}.hlo.txt.gz", "wt", compresslevel=9) as dst:
        dst.write(hlo_text or "")
    log(f"wrote {prefix}.xplane.pb.gz and {prefix}.hlo.txt.gz")


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)
    bench = cell_mod.benchmark()
    entry, cfg, traffic = cell_mod.find(args.workload, bench)
    use_compile_cache(cell_mod.CHECKOUT)
    devices = require_tpu(entry["chips"])
    cell_mod.program_path()
    import jax

    from chipbench import inputs, trace
    from chipbench.peaks import peaks

    if args.rounds is not None:
        traffic = dict(traffic, rounds_per_call=args.rounds)
    tracked = bool(traffic["tracker"])
    inp = cell_mod.family(cfg).make_inputs(args.seed, cfg)
    session = cell_mod.make_session(cfg, traffic, inp)
    scratch = tempfile.mkdtemp(prefix="chipbench-layers-")
    try:
        tracker_dir = scratch if tracked else None
        tracker = None
        if tracked:
            from repro.telemetry import JsonlTracker
            tracker = JsonlTracker(os.path.join(scratch, "warm.jsonl"))
        warm = session.run(inputs.run_key(inp["runs_key"], 0), tracker=tracker)
        jax.block_until_ready(warm.final_w)
        counter = CompileCounter()
        windows = {}
        for name, trace_dir in (("untraced", None),
                                ("traced", os.path.join(scratch, "trace"))):
            w = measure(session, traffic, inp["runs_key"], args.seconds,
                        tracker_dir=tracker_dir, counter=counter,
                        trace_dir=trace_dir)
            windows[name] = w
            log(f"{name} window {w['window_s']:.3f} s: {w['calls']} calls, "
                f"{w['failed']} failed, {w['rounds']} rounds, "
                f"{w['rounds'] / w['window_s']:.4f} rounds/s")
        trace_dir = os.path.join(scratch, "trace")
        profile = trace.load(trace_dir)
        traced = windows["traced"]
        ctx = {"cfg": cfg, "traffic": traffic, "rounds": traced["rounds"],
               "window_s": traced["window_s"], "chips": entry["chips"],
               "peaks": peaks(devices[0].device_kind),
               "trace": trace.reduce(profile, chips=entry["chips"])}
        ctx["scopes"], hlo_text = window_scopes(
            session, inp["runs_key"], tracked, profile, chips=entry["chips"],
            rounds=traced["rounds"])
        if args.save:
            save(args.save, trace_dir, hlo_text)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    result = {
        "workload": args.workload, "seed": args.seed,
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "compiles_in_windows": counter.count,
        "rounds_per_s": {name: w["rounds"] / w["window_s"]
                         for name, w in windows.items()},
        "metrics": read_metrics(bench["per_layer"], args.workload, ctx),
        "scopes": ctx["scopes"]}
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
