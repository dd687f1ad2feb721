"""Benchmark driver: one benchmark per paper table/figure + perf tracking.

    PYTHONPATH=src python -m benchmarks.run             # everything
    PYTHONPATH=src python -m benchmarks.run --only e3 e4
    PYTHONPATH=src python -m benchmarks.run --quick     # reduced sizes (CI)
    PYTHONPATH=src python -m benchmarks.run --json      # + results/bench/*.json

Benchmarks:
    e1  Fig. 1 left   — synthetic linreg convergence (3 DP settings x 3 algs)
    e2  Fig. 1 right / Table 4 — MNIST-like CNN test accuracy
    e3  Fig. 2        — step-size bias correction vs M
    e4  Table 1       — privacy budgets
    e5  Fig. 3        — eta_g trajectories
    e6  (beyond-paper) FedOpt server-lr sensitivity vs hyperparameter-free
    e7  engine throughput — scan engine vs per-round dispatch; always emits
        BENCH_engine.json (results/bench/ + repo root) for trajectory tracking
    e8  million-client rounds — sparse sampled cohorts + host-resident data
        (DESIGN.md §14); merges its sections into BENCH_engine.json
    e9  compressed communication — rand-k + count-sketch vs dense at d >= 2**20
        (DESIGN.md §16); merges its sections into BENCH_engine.json
    roofline          — §Roofline tables (baseline + optimized) from dry-runs
"""
from __future__ import annotations

import argparse
import time

ALL = ("e1", "e2", "e3", "e4", "e5", "e6", "e7", "e8", "e9", "roofline")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help=f"subset of: {' '.join(ALL)}")
    ap.add_argument("--quick", action="store_true", help="reduced sizes (CI)")
    ap.add_argument("--json", action="store_true",
                    help="emit results/bench/<name>.json per benchmark")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    which = set(args.only) if args.only else set(ALL)
    if args.quick and not args.only and "e2" in which:
        # the CNN cells compile for ~100 s EACH on a 2-vCPU CI box (seed
        # state was no faster); e2 stays full-run / --only-e2 territory
        which.discard("e2")
        print("skipping e2 under --quick (CNN cells compile ~100 s each; "
              "run with --only e2 to include it)")

    emitted = {}

    def record(name, rows):
        if args.json and rows is not None:
            from benchmarks.common import write_json
            emitted[name] = write_json(f"{name}.json", {"benchmark": name,
                                                        "quick": args.quick,
                                                        "rows": rows})

    t0 = time.time()
    if "e4" in which:  # closed-form, instant
        from benchmarks import e4_privacy
        record("e4_privacy", e4_privacy.main())
    if "e3" in which:
        from benchmarks import e3_stepsize
        if args.quick:
            record("e3_stepsize", e3_stepsize.main(ms=(50, 200, 1000), trials=4))
        else:
            record("e3_stepsize", e3_stepsize.main())
    if "e1" in which:
        from benchmarks import e1_synthetic
        if args.quick:
            record("e1_synthetic", e1_synthetic.main(clients=300, rounds=20, seeds=2))
        else:
            record("e1_synthetic", e1_synthetic.main())
    if "e5" in which:
        from benchmarks import e5_trajectories
        if args.quick:
            record("e5_trajectories", e5_trajectories.main(clients=300, rounds=20))
        else:
            record("e5_trajectories", e5_trajectories.main())
    if "e2" in which:
        from benchmarks import e2_mnist
        if args.quick:
            record("e2_mnist", e2_mnist.main(clients=60, rounds=5, seeds=1))
        else:
            record("e2_mnist", e2_mnist.main())
    if "e6" in which:
        from benchmarks import e6_fedopt_ablation
        if args.quick:
            record("e6_fedopt", e6_fedopt_ablation.main(
                clients=150, dim=80, rounds=10, lr_grid=(0.01, 0.1, 0.3)))
        else:
            record("e6_fedopt", e6_fedopt_ablation.main())
    if "e7" in which:
        from benchmarks import e7_engine_throughput
        record("e7_engine", e7_engine_throughput.main(quick=args.quick))
    if "e8" in which:
        # AFTER e7: e7 overwrites BENCH_engine.json wholesale, e8 merges
        from benchmarks import e8_million_clients
        record("e8_million_clients", e8_million_clients.main(quick=args.quick))
    if "e9" in which:
        # also after e7 (merge, don't overwrite) — see e8 comment above
        from benchmarks import e9_compression
        record("e9_compression", e9_compression.main(quick=args.quick))
    if "roofline" in which:
        import os as _os
        from benchmarks import roofline_table
        if _os.path.isdir("results/dryrun_baseline"):
            _os.environ["REPRO_DRYRUN"] = "results/dryrun_baseline"
            import importlib
            importlib.reload(roofline_table)
            roofline_table.main("16x16", label="paper-faithful-baseline")
            roofline_table.main("2x16x16", label="paper-faithful-baseline")
            _os.environ["REPRO_DRYRUN"] = "results/dryrun"
            importlib.reload(roofline_table)
        roofline_table.main("16x16", label="optimized")
        roofline_table.main("2x16x16", label="optimized")
    if emitted:
        print("json results:", ", ".join(sorted(emitted.values())))
    print(f"\nall benchmarks done in {time.time() - t0:.1f}s; CSVs in results/bench/")


if __name__ == "__main__":
    main()
