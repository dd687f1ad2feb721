"""Reduction of a JAX profiler trace of the window to the numbers the
per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read with
``jax.profiler.ProfileData`` alone.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation
run, named by its HLO instruction (``%fusion.12 = f32[...] fusion(...)``),
with its start and duration in nanoseconds on the host's clock.  Control
flow (``while``, ``conditional``, ``call``) spans the ops of its body.  The
window is the span of the harness's ``chipbench.call`` annotations on the
host plane.

- busy: the union of the op intervals on a chip, clipped to the window;
- the ``dp_aggregate`` kernel: the Mosaic custom calls
  (``custom_call_target="tpu_custom_call"``) whose HLO instruction bears the
  kernel's stable name, ``%dp_aggregate.N`` (the ``pallas_call``'s
  ``name``, which ``tests/test_tpu_compile.py`` pins).  Another Pallas
  kernel on the round's path, such as a model family's attention or scan
  kernel, is not counted; nor is the kernel of a program older than that
  name (``%_impl.N``), which then reads nothing;
- idle gaps: the stretches of the window with no op on the first chip,
  named by the innermost host annotation that spans their middle.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "chipbench.call"
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")
DP_AGGREGATE = "dp_aggregate"


def load(trace_dir: str):
    """The ``ProfileData`` of the one trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found {files}")
    return ProfileData.from_file(files[0])


def op_name(text: str) -> str:
    """``fusion.12`` from ``%fusion.12 = f32[8] fusion(...)``."""
    m = re.match(r"%?([\w.\-]+)", text)
    return m.group(1) if m else text


def op_kind(name: str) -> str:
    """``fusion`` from ``fusion.12``."""
    return re.sub(r"(\.\d+)+$", "", name)


def is_dp_aggregate(text: str) -> bool:
    """The op is a Mosaic call of the ``dp_aggregate`` kernel."""
    return (op_kind(op_name(text)) == DP_AGGREGATE
            and 'custom_call_target="tpu_custom_call"' in text)


def is_container(text: str) -> bool:
    return op_kind(op_name(text)) in CONTAINERS


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def host_spans(profile) -> list[tuple[float, float, str]]:
    """(start_ns, end_ns, name) of every event on the host's lines."""
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns > 0:
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    return spans


def device_planes(profile) -> list:
    planes = [p for p in profile.planes
              if p.name.startswith("/device:TPU:") and p.name[12:].isdigit()]
    return sorted(planes, key=lambda p: int(p.name[12:]))


def reduce(profile, *, chips: int, top: int = 10) -> dict:
    """Busy and idle time, ``dp_aggregate`` time, and the breakdown."""
    spans = host_spans(profile)
    window = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not window:
        raise RuntimeError(f"no {WINDOW_SPAN!r} annotation in the trace")
    lo, hi = min(s for s, _ in window), max(e for _, e in window)
    planes = device_planes(profile)[:chips]
    if len(planes) < chips:
        raise RuntimeError(f"{len(planes)} device planes in the trace, the "
                           f"cell uses {chips}")
    per_chip = []
    op_time: dict[str, float] = defaultdict(float)
    first_busy = []
    for k, plane in enumerate(planes):
        intervals, kernel_s, kernel_n = [], 0.0, 0
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                if ev.end_ns <= lo or ev.start_ns >= hi:
                    continue
                intervals.append((ev.start_ns, ev.end_ns))
                dur = (min(ev.end_ns, hi) - max(ev.start_ns, lo)) * 1e-9
                if not is_container(ev.name):
                    op_time[op_name(ev.name)] += dur / chips
                if is_dp_aggregate(ev.name):
                    kernel_s, kernel_n = kernel_s + dur, kernel_n + 1
        busy = union(intervals, lo, hi)
        if k == 0:
            first_busy = busy
        per_chip.append({"busy_s": sum(e - s for s, e in busy) * 1e-9,
                         "dp_aggregate_s": kernel_s,
                         "dp_aggregate_events": kernel_n})
    mean = lambda key: sum(c[key] for c in per_chip) / len(per_chip)
    return {"window_s": (hi - lo) * 1e-9, "busy_s": mean("busy_s"),
            "dp_aggregate_s": mean("dp_aggregate_s"),
            "dp_aggregate_events": mean("dp_aggregate_events"),
            "per_chip": per_chip,
            "breakdown": {
                "device_ops": sorted(([n, t] for n, t in op_time.items()),
                                     key=lambda x: -x[1])[:top],
                "idle_gaps": idle_gaps(first_busy, lo, hi, spans, top)}}


def idle_gaps(busy, lo, hi, spans, top: int) -> list[list]:
    """The longest stretches with no op on the chip, each named by the
    innermost host span that covers its middle."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        covering = [(sp_e - sp_s, name) for sp_s, sp_e, name in spans
                    if sp_s <= mid <= sp_e]
        name = min(covering)[1] if covering else "no host span"
        out.append([name, (e - s) * 1e-9])
    return out
