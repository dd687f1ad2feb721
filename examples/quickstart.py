"""Quickstart: DP-FedEXP vs DP-FedAvg via the session API (DESIGN.md §10).

    PYTHONPATH=src python examples/quickstart.py            # paper-scale CDP run
    PYTHONPATH=src python examples/quickstart.py --quick    # CI smoke (seconds)

Runs the paper's CDP setting (M=1000 clients, tau=20 local steps, 50 rounds)
and prints the distance to the shared optimum plus the adaptive step size.

A run is a ``FederatedSession`` bound to four frozen specs:

    TrainSpec(rounds, tau, eta_l)     what to train
    EngineSpec(chunk_rounds, ...)     how to compile it (default: ONE scan
                                      program for all rounds, cached across
                                      runs of the same session)
    ShardSpec(mesh=make_client_mesh())  partition clients across devices
                                      (DESIGN.md §9; on CPU force host devices
                                      first: XLA_FLAGS=--xla_force_host_
                                      platform_device_count=8)
    CohortSpec(q=0.25)                per-round client sampling with
                                      amplification-aware accounting
                                      (session.privacy_report)

``session.run(key, checkpoint_dir=...)`` makes the run resumable;
``session.resume(dir)`` continues it bit-exactly.  Pass a parameter PYTREE
(e.g. ``repro.models.cnn`` params) instead of a flat vector and the session
ravels/unravels at the boundary — see README.md for the pytree quickstart.

``--telemetry out.jsonl`` streams per-round events (eta, metric, cumulative
privacy ledger, round wall-clock) to a JSONL file WHILE the compiled run
executes — results stay bit-identical (DESIGN.md §15).

``--schedule`` adds a third leg, ``cdp-fedexp-schedule``: the same CDP
FedEXP run under a decaying noise schedule sigma(t) = sigma0 * 0.9**t
(DESIGN.md §17).  Its telemetry stream carries the per-round ``sigma`` the
device actually used, which ``tools/check_telemetry.py --sigma0 S
--sigma-decay 0.9`` pins against the declared schedule in CI.
"""
import argparse
import math
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.core.fedexp import make_algorithm
from repro.data.synthetic import distance_to_opt, linreg_loss, make_synthetic_linreg
from repro.fedsim import CohortSpec, FederatedSession, TrainSpec
from repro.launch.compile_cache import use_compile_cache
from repro.telemetry import JsonlTracker

# grid-searched on this generation (EXPERIMENTS.md): (eta_l, C) per algorithm
HPS = {"dp-fedavg-cdp": (0.3, 3.0), "cdp-fedexp": (0.1, 0.3),
       "cdp-fedexp-schedule": (0.1, 0.3)}

# §17 demo schedule: sigma(t) = sigma0 * SCHEDULE_DECAY**t; CI pins the
# telemetry stream against exactly this decay (check_telemetry --sigma-decay)
SCHEDULE_DECAY = 0.9


def main(quick: bool = False, sampled_q: float | None = None,
         telemetry: str | None = None, schedule: bool = False):
    m, d, rounds, tau = (120, 64, 8, 5) if quick else (1000, 500, 50, 20)
    data = make_synthetic_linreg(jax.random.PRNGKey(0), m, d)
    w0 = jnp.zeros(d)
    eval_fn = distance_to_opt(data.w_star)
    cohort = CohortSpec() if sampled_q is None else CohortSpec(q=sampled_q)
    eval_every = 2 if quick else 10

    names = ["dp-fedavg-cdp", "cdp-fedexp"]
    if schedule:
        names.append("cdp-fedexp-schedule")
    for name in names:
        eta_l, clip = HPS[name]
        kw = dict(clip_norm=clip, sigma=5 * clip / math.sqrt(m),
                  num_clients=m)
        if name == "cdp-fedexp-schedule":
            kw["decay"] = SCHEDULE_DECAY
        alg = make_algorithm(name, **kw)
        session = FederatedSession(
            alg, linreg_loss, w0, data.client_batches(),
            train=TrainSpec(rounds=rounds, tau=tau, eta_l=eta_l,
                            eval_every=eval_every),
            cohort=cohort, eval_fn=eval_fn)
        # one tracker file per algorithm: quickstart.jsonl -> quickstart-<alg>.jsonl
        tracker = None
        if telemetry is not None:
            stem, dot, ext = telemetry.rpartition(".")
            path = f"{stem}-{name}.{ext}" if dot else f"{telemetry}-{name}"
            tracker = JsonlTracker(path)
        result = session.run(jax.random.PRNGKey(42), tracker=tracker)
        dist = float(eval_fn(result.final_w))
        etas = result.eta_history
        report = session.privacy_report(delta=1e-5)
        print(f"{name:16s}  final ||w - w*|| = {dist:8.4f}   "
              f"eta_g: first={float(etas[0]):.2f} last={float(etas[-1]):.2f}   "
              f"eps={report.eps_numerical:.2f}")
        # eval runs on the eval_every cadence; eval_rounds() drops the
        # NaN placeholder rows so only measured rounds print
        trail = "  ".join(f"t={t}: {v:.3f}"
                          for t, v in result.eval_rounds()[-3:])
        print(f"{'':16s}  ||w - w*|| trail: {trail}")

    print("\nDP-FedEXP reaches a closer iterate at the SAME privacy budget —")
    print("the global step size is derived from already-privatized statistics.")
    if sampled_q is not None:
        print(f"(sampled cohorts q={sampled_q}: epsilon accounts for the "
              "subsampled release — conditional-sensitivity inflation plus "
              "GDP amplification, see accounting.cdp_budget)")


if __name__ == "__main__":
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small geometry for CI smoke runs")
    ap.add_argument("--sampled-q", type=float, default=None,
                    help="per-round Bernoulli client sampling rate")
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="stream per-round JSONL telemetry to PATH "
                         "(one file per algorithm; DESIGN.md §15)")
    ap.add_argument("--schedule", action="store_true",
                    help="also run cdp-fedexp under a decaying noise "
                         f"schedule sigma(t) = sigma0 * {SCHEDULE_DECAY}**t "
                         "(DESIGN.md §17)")
    args = ap.parse_args()
    main(quick=args.quick, sampled_q=args.sampled_q, telemetry=args.telemetry,
         schedule=args.schedule)
