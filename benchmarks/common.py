"""Shared helpers for the paper-reproduction benchmarks."""
from __future__ import annotations

import csv
import json
import os
import time

import numpy as np

RESULTS_DIR = os.environ.get("REPRO_RESULTS", "results/bench")


def write_json(name: str, obj) -> str:
    """Dump a benchmark result object to results/bench/<name> (trajectory
    tracking; every benchmark emits one when run.py is passed --json)."""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, name)
    with open(path, "w") as f:
        json.dump(obj, f, indent=2, default=float)
    return path


def write_csv(name: str, header: list[str], rows: list[list]) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, name)
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(rows)
    return path


def print_table(title: str, header: list[str], rows: list[list]) -> None:
    print(f"\n== {title} ==")
    widths = [max(len(str(h)), *(len(_fmt(r[i])) for r in rows)) if rows else len(str(h))
              for i, h in enumerate(header)]
    print("  ".join(str(h).ljust(w) for h, w in zip(header, widths)))
    for r in rows:
        print("  ".join(_fmt(c).ljust(w) for c, w in zip(r, widths)))


def _fmt(x) -> str:
    if isinstance(x, float):
        if x == 0 or (1e-3 < abs(x) < 1e5):
            return f"{x:.4g}"
        return f"{x:.3e}"
    return str(x)


def mean_std(vals: list[float]) -> tuple[float, float]:
    a = np.asarray(vals, np.float64)
    return float(a.mean()), float(a.std())


class Timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.seconds = time.time() - self.t0


def make_dp_algorithm(setting: str, alg: str, *, clip: float, clients: int,
                      dim: int, backend: str = "auto"):
    """Setting -> algorithm factory shared by e1/e2 (the paper's protocol:
    sigma = 5C/sqrt(M) for CDP, 0.7C for LDP Gaussian, eps0=eps1=eps2=2 for
    PrivUnit); ``alg`` is "fedexp" or "fedavg".  ``backend`` picks the
    Gaussian settings' clip/noise/reduce path (``fused_clip_aggregate``)."""
    import math as _math

    from repro.core.fedexp import make_algorithm

    if setting == "cdp":
        name = "cdp-fedexp" if alg == "fedexp" else "dp-fedavg-cdp"
        return make_algorithm(name, clip_norm=clip,
                              sigma=5 * clip / _math.sqrt(clients),
                              num_clients=clients, backend=backend)
    if setting == "ldp-gauss":
        name = "ldp-fedexp-gauss" if alg == "fedexp" else "dp-fedavg-ldp-gauss"
        return make_algorithm(name, clip_norm=clip, sigma=0.7 * clip,
                              backend=backend)
    if setting == "ldp-privunit":
        name = "ldp-fedexp-privunit" if alg == "fedexp" else "dp-fedavg-privunit"
        return make_algorithm(name, clip_norm=clip, eps0=2.0, eps1=2.0,
                              eps2=2.0, dim=dim)
    raise ValueError(f"unknown DP setting {setting!r}")
