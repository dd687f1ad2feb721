#!/usr/bin/env python3
"""DP-FedEXP training on a TPU, end to end, checked against references.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # only the client-sharded engine, 4 chips

One chip trains the paper's E2 setting the way ``benchmarks/e2_mnist.py``
builds it: the generated MNIST substitute split over M = 1000 clients by
Dirichlet(0.3), tau = 10 local steps, ``cdp-fedexp`` on the CDP CNN
(d = 5,046) and ``ldp-fedexp-gauss`` on the LDP CNN (d = 237), a few rounds
each, through ``FederatedSession`` on the scan engine with
``backend="auto"``.  On a TPU that routes clip/noise/reduce through the
compiled ``dp_aggregate`` kernel.  It checks:

  (a) the compiled kernel at each CNN width against a float64 NumPy
      reference of its three sums, in every noise mode ("fused" against the
      noise ``generate_ldp_noise`` draws for the same key), and that noise's
      mean and standard deviation against sigma;
  (b) ``cdp-fedexp`` with ``backend="auto"`` against ``backend="jnp"``:
      eta history and final weights;
  (c) every history is finite, and the round program holds a
      ``tpu_custom_call``, so the kernel is what ran;
  (d) one run streams through a ``JsonlTracker``, and
      ``tools/check_telemetry.py`` accepts the stream.

``--chips 4`` runs only the sharded phase: ``cdp-fedexp`` and
``ldp-fedexp-gauss`` over a 4-chip ``clients`` mesh, one round each at
default precision, against the same session on one chip (for LDP on the
stream engine: the dense LDP release draws its noise from the hardware
PRNG, the sharded and stream releases draw per-client Threefry rows) and
against one chip streamed in blocks of one shard's clients.

Details go to earlier lines.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or when any check fails, the script exits non-zero and
prints no such line.  One process drives the chips; it starts no other.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402  (after the path setup, like the examples)
import jax.numpy as jnp  # noqa: E402

CLIENTS, TAU, ROUNDS = 1000, 10, 3
SETTINGS = ("cdp", "ldp-gauss")        # e2's names: CDP CNN, LDP CNN
ALGORITHM = {"cdp": "cdp-fedexp", "ldp-gauss": "ldp-fedexp-gauss"}

# (a) Against float64, the kernel's f32 sums over 1000 rows are off by a few
# f32 ulps (2**-24 ~ 6e-8) times a modest growth factor.  A product the MXU
# rounds to bf16 (its default for f32 operands) is off by 2**-9 ~ 2e-3.
KERNEL_RTOL = 1e-5
# Sample mean and std of N standard normals: 6 standard errors.
NOISE_SIGMAS = 6.0
# (b) "auto" and "jnp" sum the same clipped f32 rows in different orders.
# Round 1 starts both runs from one w0, so eta differs by f32 rounding only;
# later rounds start from weights that differ by it, and local training
# amplifies that.  Readings on a v5e, E2 seeds 0-2 (relative eta error in
# round 1 / over 3 rounds, final weights as a share of the distance trained):
#   sound, default precision:     <= 1.2e-7 / <= 1.8e-3 / <= 3.0e-3
#   sound, local training at "highest" (seed 0): 9.8e-8 / 5.5e-4 / 2.5e-4
#   kernel column sum and sum_dot multiplying in bf16 (seed 0):
#                                    8.9e-6 / 4.8e-3 / 1.1e-2
# Each bar lies between the largest sound reading and the bf16 one.  This
# script runs seed 0, which reads 9.8e-8 / 1.8e-3 / 1.6e-3 on a v5e.
SESSION_BARS = {"round-1 eta": 1e-6, "eta": 3e-3, "final_w": 5e-3}
# --chips 4, one round at default precision.  Each shard trains its 250
# clients as one vmapped batch, the one-chip session all 1000 at once, and
# the batch shape alone moves local training's rounding: on one v5e, the
# same session streamed in 250-client blocks differs from it by (eta,
# final weights as above) cdp 4.9e-4..1.1e-2 / 3.8e-3..3.0e-2 and ldp
# 0..8.4e-4 / 4.1e-4..4.7e-3 over seeds 0-2, while the scan and stream
# engines on one 1000-client block agree exactly.  So 4 chips are held to
#   - tests/test_sharding.py's bars against one chip streamed in blocks of
#     one shard's clients (same shapes; only the psum order differs), and
#   - SHARD_SESSION_BARS against the one-chip session, 3-4x the largest
#     block-size reading; a shard's moments lost or counted twice would move
#     the round's update by about a quarter.
SHARD_W_TOL = dict(rtol=1e-5, atol=1e-5)
SHARD_ETA_TOL = dict(rtol=1e-4, atol=1e-5)
SHARD_SESSION_BARS = {"eta": 5e-2, "final_w": 1e-1}
SHARD_ROUNDS = 1


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(chips: int):
    """Stop, printing no result, unless JAX sees at least ``chips`` TPUs."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{devices[0].platform!r}); nothing was run")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU chips, "
                 f"JAX sees {len(devices)}")
    return devices


# -- (a) the kernel against float64 -----------------------------------------

def reference_sums(u, clip, noise=None):
    """float64 (sum_i c_i, sum_i ||c_i||^2, sum_i ||clip(u_i)||^2)."""
    u = u.astype(np.float64)
    norms = np.linalg.norm(u, axis=1, keepdims=True)
    clipped = u * np.minimum(1.0, clip / np.maximum(norms, 1e-12))
    released = clipped if noise is None else clipped + noise.astype(np.float64)
    return (released.sum(axis=0), float(np.square(released).sum()),
            float(np.square(clipped).sum()))


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def check_kernel(width: int, *, m: int = CLIENTS, clip: float = 1.0,
                 sigma: float = 0.7, seed: int = 0) -> list[str]:
    """Every noise mode of the compiled kernel at (m, width) vs float64."""
    from repro.kernels.dp_aggregate.ops import dp_aggregate, generate_ldp_noise

    rng = np.random.default_rng(seed)
    # row norms spread over [C/4, 4C], so rows on both sides of the clip
    scale = rng.uniform(0.25, 4.0, (m, 1)) * clip / math.sqrt(width)
    u = (rng.standard_normal((m, width)) * scale).astype(np.float32)
    operand = (sigma * rng.standard_normal((m, width))).astype(np.float32)
    key = jax.random.PRNGKey(seed)
    u_dev, operand_dev = jnp.asarray(u), jnp.asarray(operand)

    t0 = time.perf_counter()
    drawn = np.asarray(generate_ldp_noise(m, width, key, sigma))
    runs = {
        "none": (dp_aggregate(u_dev, clip), None),
        "operand": (dp_aggregate(u_dev, clip, operand_dev), operand),
        "fused": (dp_aggregate(u_dev, clip, noise_key=key, noise_sigma=sigma),
                  drawn),
    }
    jax.block_until_ready([s.cbar for s, _ in runs.values()])
    seconds = time.perf_counter() - t0

    failures = []
    for mode, (stats, noise) in runs.items():
        s, sq, sq_clip = reference_sums(u, clip, noise)
        errs = {"sum": rel_err(stats.cbar * m, s),
                "sum_sq": rel_err(stats.mean_sq * m, sq),
                "sum_sq_clipped": rel_err(stats.mean_sq_clipped * m, sq_clip)}
        log(f"  (a) d={width} {mode:8s} rel err vs float64: "
            + " ".join(f"{k}={v:.3e}" for k, v in errs.items()))
        failures += [f"(a) d={width} {mode} {k} rel err {v:.3e} > {KERNEL_RTOL}"
                     for k, v in errs.items() if not v <= KERNEL_RTOL]

    n = drawn.size
    mean, std = float(drawn.mean()), float(drawn.std())
    mean_bar = NOISE_SIGMAS * sigma / math.sqrt(n)
    std_bar = NOISE_SIGMAS / math.sqrt(2 * n)
    log(f"  (a) d={width} in-kernel noise: mean {mean:.3e} (bar {mean_bar:.1e}),"
        f" std/sigma - 1 = {std / sigma - 1:.3e} (bar {std_bar:.1e}),"
        f" {seconds:.2f} s for the four kernels, compile included")
    if not abs(mean) <= mean_bar:
        failures.append(f"(a) d={width} noise mean {mean} > {mean_bar}")
    if not abs(std / sigma - 1) <= std_bar:
        failures.append(f"(a) d={width} noise std {std} vs sigma {sigma}")
    if len(np.unique(drawn, axis=0)) != m:
        failures.append(f"(a) d={width} in-kernel noise repeats a row")
    return failures


# -- sessions ----------------------------------------------------------------

def make_problems():
    """The E2 problem of each setting: (model, loss, eval_fn, batches)."""
    from benchmarks.e2_mnist import _make_problem
    from repro.data.images import make_image_dataset

    dataset = make_image_dataset(jax.random.PRNGKey(7))
    return {s: _make_problem(s, CLIENTS, 0, dataset=dataset) for s in SETTINGS}


def make_session(problem, setting: str, *, backend: str = "auto",
                 engine: str = "scan", stream=None, mesh=None,
                 rounds: int | None = None):
    from benchmarks.common import make_dp_algorithm
    from benchmarks.e2_mnist import HP
    from repro.fedsim import (EngineSpec, FederatedSession, ShardSpec,
                              StreamSpec, TrainSpec)

    model, loss, eval_fn, batches = problem
    eta_l, clip = HP[setting]["fedexp"]
    alg = make_dp_algorithm(setting, "fedexp", clip=clip, clients=CLIENTS,
                            dim=model.dim, backend=backend)
    return FederatedSession(alg, loss, model.init_flat, batches,
                            train=TrainSpec(rounds=rounds or ROUNDS, tau=TAU,
                                            eta_l=eta_l),
                            engine=EngineSpec(engine=engine),
                            stream=stream or StreamSpec(),
                            shard=ShardSpec(mesh=mesh), eval_fn=eval_fn)


def run_key():
    return jax.random.PRNGKey(2000)     # e2's key for seed 0


def timed_run(session, tracker=None):
    t0 = time.perf_counter()
    result = session.run(run_key(), tracker=tracker)
    jax.block_until_ready((result.final_w, result.eta_history,
                           result.metric_history))
    return result, time.perf_counter() - t0


HISTORIES = ("eta_history", "eta_naive_history", "eta_target_history",
             "metric_history")


def recorded(result, field: str) -> bool:
    """False for a history the step rule does not produce (CDP's has no
    naive eta): the engine fills it with NaN."""
    return not (field in ("eta_naive_history", "eta_target_history")
                and np.all(np.isnan(np.asarray(getattr(result, field)))))


def finite_failures(label: str, result) -> list[str]:
    return [f"(c) {label}: {f} is not finite"
            for f in ("final_w", "last_w") + HISTORIES
            if recorded(result, f)
            and not np.all(np.isfinite(np.asarray(getattr(result, f))))]


def gaps(ref, other, w0) -> dict[str, float]:
    """How far ``other`` ran from ``ref``: relative eta error in round 1 and
    over all rounds, and the largest final-weight difference as a share of
    the distance ``ref`` trained from ``w0``."""
    a = np.asarray(ref.eta_history, np.float64)
    rel = np.abs(np.asarray(other.eta_history, np.float64) - a) / np.abs(a)
    w_ref = np.asarray(ref.final_w, np.float64)
    trained = np.max(np.abs(w_ref - np.asarray(w0, np.float64)))
    w_diff = np.max(np.abs(np.asarray(other.final_w, np.float64) - w_ref))
    return {"round-1 eta": float(rel[0]), "eta": float(np.max(rel)),
            "final_w": float(w_diff / trained)}


def gap_failures(label: str, measured: dict, bars: dict) -> list[str]:
    log(f"  {label}: " + ", ".join(f"{k} {measured[k]:.3e} (bar {bars[k]})"
                                     for k in bars))
    return [f"{label}: {k} {measured[k]:.3e} > {bars[k]}"
            for k in bars if not measured[k] <= bars[k]]


def summarize(label: str, result, seconds: float) -> None:
    eta = np.asarray(result.eta_history)
    acc = np.asarray(result.metric_history)
    log(f"  {label}: {seconds:.2f} s, eta {np.array2string(eta, precision=5)}"
        f", test acc {np.array2string(acc, precision=4)}")


def check_sessions(problems) -> list[str]:
    """(b)-(d) on one chip."""
    from tools.check_telemetry import check_stream
    from repro.telemetry import JsonlTracker

    failures = []
    results = {}
    for setting in SETTINGS:
        session = make_session(problems[setting], setting)
        name = ALGORITHM[setting]
        first, t_first = timed_run(session)
        again, t_again = timed_run(session)
        log(f"  {name} (auto): first run {t_first:.2f} s (compile included),"
            f" second run {t_again:.2f} s for {ROUNDS} rounds")
        summarize(f"{name} (auto)", again, t_again)
        failures += finite_failures(f"{name} (auto)", again)
        if not np.array_equal(np.asarray(first.final_w),
                              np.asarray(again.final_w)):
            failures.append(f"{name}: two runs with one key differ")
        has_kernel = "tpu_custom_call" in session.lower(run_key()).as_text()
        log(f"  (c) {name}: round program holds tpu_custom_call: {has_kernel}")
        if not has_kernel:
            failures.append(f"(c) {name}: no tpu_custom_call in the round "
                            "program; the kernel did not run")
        results[setting] = (session, again)

    # (b) the kernel path against the jnp path
    auto = results["cdp"][1]
    jnp_run, t_jnp = timed_run(make_session(problems["cdp"], "cdp",
                                            backend="jnp"))
    summarize("cdp-fedexp (jnp)", jnp_run, t_jnp)
    failures += finite_failures("cdp-fedexp (jnp)", jnp_run)
    failures += gap_failures(
        "(b) cdp-fedexp auto vs jnp",
        gaps(jnp_run, auto, problems["cdp"][0].init_flat), SESSION_BARS)

    # (d) telemetry through the ordered io_callback inside the scan
    session, ldp = results["ldp-gauss"]
    with tempfile.TemporaryDirectory() as tmp:
        path = Path(tmp) / "telemetry.jsonl"
        tracked, t_tracked = timed_run(session, JsonlTracker(str(path)))
        lines = path.read_text().splitlines()
    errors = check_stream(lines, rounds=ROUNDS, label="telemetry")
    streamed = [json.loads(line)["eta"] for line in lines]
    log(f"  (d) ldp-fedexp-gauss tracked run: {t_tracked:.2f} s (compile "
        f"included), {len(lines)} lines, check_telemetry errors: {errors}")
    failures += [f"(d) {e}" for e in errors]
    if not np.allclose(streamed, np.asarray(tracked.eta_history),
                       rtol=1e-6, atol=0):
        failures.append(f"(d) streamed eta {streamed} != eta_history")
    if not np.array_equal(np.asarray(tracked.final_w), np.asarray(ldp.final_w)):
        failures.append("(d) the tracked run differs from the untracked one")
    return failures


# -- --chips 4 ---------------------------------------------------------------

def compare(label: str, one, sharded) -> list[str]:
    """test_sharding.py's comparison.  Test accuracy counts argmax hits, so
    one flipped sample moves it by 1/2000: the weights it is computed from
    are compared instead."""
    failures = []
    for fields, tol in ((("final_w", "last_w"), SHARD_W_TOL),
                        (("eta_history", "eta_naive_history",
                          "eta_target_history"), SHARD_ETA_TOL)):
        for f in filter(lambda f: recorded(one, f), fields):
            a, b = np.asarray(getattr(one, f)), np.asarray(getattr(sharded, f))
            err = float(np.max(np.abs(a - b) / (tol["atol"]
                                                 + tol["rtol"] * np.abs(a))))
            log(f"  {label} {f}: max |diff| {np.max(np.abs(a - b)):.3e}, "
                f"{err:.3f} of the bar (rtol {tol['rtol']}, atol "
                f"{tol['atol']})")
            if not err <= 1.0:
                failures.append(f"{label} {f}: {err:.3f} of the bar")
    return failures


def check_sharded(problems, chips: int) -> list[str]:
    """Each setting on ``chips`` chips against two one-chip sessions: the
    one users run (cdp: scan; ldp: stream in one block, since the dense LDP
    release draws other noise), and the same clients streamed in blocks of
    one shard's clients, which trains the shapes each shard trains."""
    from repro.fedsim import StreamSpec
    from repro.launch.mesh import make_client_mesh

    mesh = make_client_mesh(chips)
    block = CLIENTS // chips
    failures = []
    for setting in SETTINGS:
        name, problem = ALGORITHM[setting], problems[setting]
        whole = (dict(engine="scan") if setting == "cdp" else
                 dict(engine="stream", stream=StreamSpec(chunk_clients=CLIENTS)))
        runs = {
            "one chip": make_session(problem, setting, rounds=SHARD_ROUNDS,
                                     **whole),
            f"one chip, {block}-client blocks": make_session(
                problem, setting, engine="stream", rounds=SHARD_ROUNDS,
                stream=StreamSpec(chunk_clients=block)),
            f"{chips} chips": make_session(problem, setting, mesh=mesh,
                                           rounds=SHARD_ROUNDS),
        }
        for label, session in runs.items():
            runs[label], seconds = timed_run(session)
            summarize(f"{name} {label}", runs[label], seconds)
            failures += finite_failures(f"{name} {label}", runs[label])
        one, blocks, sharded = runs.values()
        w0 = problem[0].init_flat
        failures += compare(f"{name} {chips} chips vs one chip in "
                            f"{block}-client blocks", blocks, sharded)
        failures += gap_failures(f"{name} {chips} chips vs one chip",
                                 gaps(one, sharded, w0), SHARD_SESSION_BARS)
        log(f"  {name} one chip in {block}-client blocks vs one chip "
            "(block size alone): " + ", ".join(
                f"{k} {v:.3e}" for k, v in gaps(one, blocks, w0).items()))
    return failures


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the client-sharded phase on four chips")
    args = ap.parse_args()

    devices = require_tpu(args.chips)
    from repro.launch.compile_cache import use_compile_cache

    log(f"device: {devices[0].device_kind} x{len(devices)}, jax "
        f"{jax.__version__}, compile cache {use_compile_cache()}")
    t0 = time.perf_counter()
    failures = []
    problems = make_problems()
    if args.chips == 1:
        for setting in SETTINGS:       # d = 5,046 and 237
            failures += check_kernel(problems[setting][0].dim)
        failures += check_sessions(problems)
    else:
        failures += check_sharded(problems, args.chips)
    log(f"total {time.perf_counter() - t0:.1f} s")
    if failures:
        sys.exit("chip_smoke FAILED:\n  " + "\n  ".join(failures))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
