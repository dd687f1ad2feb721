#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 chipbench/run.py --workload e2-cdp-cnn.full --seed 7 --seconds 20 --trace 0

A cell (``BENCHMARK.json``, ``workloads``) names a configuration
(``chipbench/configs/<config>.json``, which names its model family,
``chipbench/families/<family>.py``) and a traffic mix
(``chipbench/traffic/<traffic>.json``).  The run builds the cell's inputs
from ``--seed``, builds the program's ``FederatedSession`` for them, warms
it up with one ``session.run`` (which compiles, or reads the compile cache),
then makes back-to-back ``session.run`` calls with fresh run keys for
``--seconds`` seconds, each timed to ``block_until_ready``: a closed loop,
as a researcher's sweep over run keys.  The window's last call is then
followed by the plain reference (``reference.py``) and compared
(``compare.py``).

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` traces
the window with the JAX profiler and prints its per-layer metrics, each read
by ``chipbench/metrics/<name>.py`` from the reduced trace (``trace.py``) or
from the device time of the program's named scopes and the durations of its
host spans (``scopes.py``, which maps the trace's ops to scopes through the
round program's compiled HLO, fetched after the window).
The last line of stdout is one JSON object; each number that ``correct``
compares is printed beside its limit, last on stderr and last in that
object.  Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

from chipbench import cell as cell_mod  # noqa: E402


class NoChip(SystemExit):
    """Raised when the machine lacks the chips the cell needs."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache(checkout: Path) -> str:
    """JAX's persistent compile cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` already names one."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(checkout / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_tpu(chips: int):
    """The devices, or NoChip unless JAX sees at least ``chips`` TPUs."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"chipbench: JAX finds no accelerator: {e}") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"chipbench: no TPU (JAX platform is "
                     f"{devices[0].platform!r}); nothing was run")
    if len(devices) < chips:
        raise NoChip(f"chipbench: the cell needs {chips} TPU chips, JAX "
                     f"sees {len(devices)}")
    return devices


class CompileCounter:
    """Counts XLA compilations (or compile-cache loads) while armed."""

    def __init__(self):
        import jax.monitoring

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def to_host(result) -> dict:
    """The fields of a ``RunResult`` that the checks read, as NumPy."""
    import jax

    fields = ("final_w", "last_w", "eta_history", "eta_naive_history",
              "eta_target_history", "metric_history")
    return jax.device_get({f: getattr(result, f) for f in fields})


def finite(out: dict) -> bool:
    """Every weight (each leaf of the weight pytrees) and every recorded
    history entry is finite.  CDP has no naive eta: that history is NaN
    throughout by design."""
    import jax
    import numpy as np

    for name, value in out.items():
        a = np.concatenate([np.ravel(v) for v in jax.tree_util.tree_leaves(value)])
        if name == "eta_naive_history" and np.all(np.isnan(a)):
            continue
        if not np.all(np.isfinite(a)):
            return False
    return True


def measure(session, traffic: dict, runs_key, seconds: float, *, tracker_dir,
            counter: CompileCounter, trace_dir: str | None):
    """The closed loop: calls until ``seconds`` have passed."""
    import jax

    from chipbench.inputs import run_key

    def tracker(i):
        if tracker_dir is None:
            return None
        from repro.telemetry import JsonlTracker
        return JsonlTracker(os.path.join(tracker_dir, f"call{i}.jsonl"))

    calls, failed, results, ends = 0, 0, [], []
    if trace_dir is not None:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    counter.armed = True
    t0 = time.perf_counter()
    while True:
        i = calls + 1
        with jax.profiler.TraceAnnotation("chipbench.call"):
            try:
                res = session.run(run_key(runs_key, i), tracker=tracker(i))
                jax.block_until_ready((res.final_w, res.eta_history,
                                       res.metric_history))
            except Exception as e:  # a failed call counts, the loop goes on
                log(f"call {i} raised {type(e).__name__}: {e}")
                res = None
        calls += 1
        ends.append(time.perf_counter())
        if res is None:
            failed += 1
        else:
            results.append((i, res))
        if ends[-1] - t0 >= seconds:
            break
    t1 = ends[-1]
    counter.armed = False
    if trace_dir is not None:
        jax.profiler.stop_trace()
    rounds = traffic["rounds_per_call"]
    outs = []
    for i, res in results:
        out = to_host(res)
        if not finite(out):
            failed += 1
            log(f"call {i} returned non-finite weights or histories")
        else:
            outs.append((i, out))
    log("call seconds: " + " ".join(f"{b - a:.4f}" for a, b in zip([t0] + ends, ends)))
    return {"calls": calls, "failed": failed, "window_s": t1 - t0,
            "rounds": rounds * len(outs), "outs": outs}


def window_scopes(session, runs_key, tracked: bool, profile, *, chips: int,
                  rounds: int) -> tuple[dict, str | None]:
    """The traced window by the program's named scopes and host spans
    (``scopes.reduce``), and the round program's HLO text it was read
    through; the summary goes to stderr.

    The text is that of the compiled round program the window ran: JAX's
    in-process cache hands back its executable, so its instruction names
    are the trace's.  A program whose ``lower`` takes no ``tap`` (one older
    than its named scopes) gives no text, and no scope readings."""
    from chipbench import scopes
    from chipbench.inputs import run_key

    t0 = time.perf_counter()
    try:
        lowered = session.lower(run_key(runs_key, 0), tap=tracked)
        hlo = lowered.compile().as_text()
    except TypeError as e:
        log(f"no round program HLO, so no scope readings: {e}")
        hlo = None
    log(f"round program HLO in {time.perf_counter() - t0:.3f} s")
    reduced = scopes.reduce(profile, hlo, chips=chips)
    for line in scopes.summary(reduced, rounds):
        log(line)
    return reduced, hlo


def memory_peak(devices) -> int | None:
    peaks = [d.memory_stats().get("peak_bytes_in_use") for d in devices
             if d.memory_stats()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def read_metrics(metrics: list[dict], workload: str, ctx: dict) -> dict:
    """Each of ``metrics`` that the cell reports, read by its own module:
    ``metrics/<name>.py``, or for a name split by cells (``mfu.telemetry``)
    the module of the part before the first dot."""
    out = {}
    for m in metrics:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        reader = importlib.import_module(
            f"chipbench.metrics.{m['name'].split('.')[0]}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(workload: str, entry: dict, cfg: dict, traffic: dict, *,
             seed: int, seconds: float, trace_on: bool, devices,
             bench: dict) -> dict:
    """One run of a cell on ``devices``; returns the result object.  It
    reports the ``per_layer`` metrics of ``bench`` (``BENCHMARK.json``)
    when traced, its ``end_to_end`` metrics when not."""
    import jax

    from chipbench import compare, inputs, trace
    from chipbench.peaks import peaks

    counter = CompileCounter()
    log(f"{time.perf_counter() - T_PROCESS:.3f} s: JAX on {devices[0].device_kind}")
    inp = cell_mod.family(cfg).make_inputs(seed, cfg)
    jax.block_until_ready(inp)
    log(f"{time.perf_counter() - T_PROCESS:.3f} s: inputs made")
    scratch = tempfile.mkdtemp(prefix="chipbench-")
    try:
        tracker_dir = scratch if traffic["tracker"] else None
        trace_dir = os.path.join(scratch, "trace") if trace_on else None
        session = cell_mod.make_session(cfg, traffic, inp)
        tracker = None
        if tracker_dir is not None:
            from repro.telemetry import JsonlTracker
            tracker = JsonlTracker(os.path.join(scratch, "call0.jsonl"))
        with jax.profiler.TraceAnnotation("chipbench.warmup"):
            warm = session.run(inputs.run_key(inp["runs_key"], 0),
                               tracker=tracker)
            jax.block_until_ready((warm.final_w, warm.eta_history))
        setup_s = time.perf_counter() - T_PROCESS
        log(f"set-up {setup_s:.3f} s (inputs, session, warm-up call)")

        window = measure(session, traffic, inp["runs_key"], seconds,
                         tracker_dir=tracker_dir, counter=counter,
                         trace_dir=trace_dir)
        log(f"window {window['window_s']:.3f} s: {window['calls']} calls, "
            f"{window['failed']} failed, {window['rounds']} rounds; "
            f"compiles in the window: {counter.count}")
        peak_bytes = memory_peak(devices[:entry["chips"]])

        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": peak_bytes}
        result = {"attempted": window["calls"], "failed": window["failed"]}
        ctx = {"cfg": cfg, "traffic": traffic, "rounds": window["rounds"],
               "window_s": window["window_s"], "setup_s": setup_s,
               "chips": entry["chips"]}
        if trace_on:
            profile = trace.load(trace_dir)
            reduced = trace.reduce(profile, chips=entry["chips"])
            device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            ctx.update(trace=reduced, peaks=peaks(devices[0].device_kind))
            ctx["scopes"], _ = window_scopes(
                session, inp["runs_key"], bool(traffic["tracker"]), profile,
                chips=entry["chips"], rounds=window["rounds"])
            result["metrics"] = read_metrics(bench["per_layer"], workload, ctx)
            result["breakdown"] = reduced["breakdown"]
        else:
            result["metrics"] = read_metrics(bench["end_to_end"], workload, ctx)
        result["device"] = device
        del session, warm

        last = window["outs"][-1] if window["outs"] else (None, None)
        streams = None
        if tracker_dir is not None:
            streams = [(Path(scratch, f"call{i}.jsonl").read_text().splitlines(), o)
                       for i, o in window["outs"]]
        checks = compare.check(
            workload, cfg, inp,
            None if last[0] is None else inputs.run_key(inp["runs_key"], last[0]),
            last[1], streams)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    result["correct"] = compare.passed(checks) and window["failed"] == 0
    result["checks"] = checks
    return result


def main(argv=None) -> dict:
    args = parse_args(argv)
    bench = cell_mod.benchmark()
    entry, cfg, traffic = cell_mod.find(args.workload, bench)
    if entry["chips"] != traffic["chips"]:
        raise SystemExit(f"{args.workload}: BENCHMARK.json asks for "
                         f"{entry['chips']} chips, its traffic for "
                         f"{traffic['chips']}")
    use_compile_cache(cell_mod.CHECKOUT)
    devices = require_tpu(entry["chips"])
    cell_mod.program_path()
    result = run_cell(args.workload, entry, cfg, traffic, seed=args.seed,
                      seconds=args.seconds, trace_on=bool(args.trace),
                      devices=devices, bench=bench)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    try:
        main()
    except NoChip as e:
        print(e, file=sys.stderr)
        sys.exit(3)
