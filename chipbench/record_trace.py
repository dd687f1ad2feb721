#!/usr/bin/env python3
"""Record the small chip trace that the trace-reduction tests read.

    python3 chipbench/record_trace.py --workload e2-cdp-cnn.full --rounds 2 \\
        --out chipbench/testdata/e2-cdp-cnn.full.r2.xplane.pb.gz

Builds the cell's session with ``--rounds``-round calls (seed 5), warms it
up, then traces two calls, each in a ``chipbench.call`` annotation as the
run's window does, and writes the trace's ``.xplane.pb`` gzipped to
``--out``.  The benchmark's own runs never do this.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import cell as cell_mod  # noqa: E402
from chipbench.run import require_tpu, use_compile_cache  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    entry, cfg, traffic = cell_mod.find(args.workload, cell_mod.benchmark())
    use_compile_cache(cell_mod.CHECKOUT)
    require_tpu(entry["chips"])
    cell_mod.program_path()
    import jax

    from chipbench import inputs

    traffic = dict(traffic, rounds_per_call=args.rounds)
    inp = cell_mod.family(cfg).make_inputs(5, cfg)
    session = cell_mod.make_session(cfg, traffic, inp)
    jax.block_until_ready(session.run(inputs.run_key(inp["runs_key"], 0)).final_w)
    with tempfile.TemporaryDirectory() as tmp:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        for i in (1, 2):
            with jax.profiler.TraceAnnotation("chipbench.call"):
                res = session.run(inputs.run_key(inp["runs_key"], i))
                jax.block_until_ready(res.final_w)
        jax.profiler.stop_trace()
        (trace,) = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(trace, "rb") as src, gzip.open(args.out, "wb", compresslevel=9) as dst:
            dst.write(src.read())
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
