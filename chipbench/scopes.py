"""Device time of the round program by the program's named scopes, and the
durations of its host spans, from the traced window.

The program names its layers (``repro/telemetry/spans.py``): device scopes
``fedsim.*`` (``jax.named_scope``), which reach the compiled HLO as
``metadata={op_name=".../fedsim.release/..."}``, and host spans
``fedsim.*`` / ``telemetry.*`` (``jax.profiler.TraceAnnotation``).  A
trace's ``XLA Ops`` events carry no ``op_name``, so each op is mapped to its
scope through the round program's compiled HLO text, by instruction name:
the first ``fedsim.*`` component of its ``op_name``.  The text is
``session.lower(key, tap=...).compile().as_text()`` after the window: JAX's
in-process cache returns the executable the window ran, so the names match
without a second compile.

- device time of a scope: the summed durations of the non-control-flow ops
  of the round program (``XLA Modules`` events named ``jit_chunk``) that
  map to it, clipped to the ``chipbench.call`` window, averaged over chips;
  ops that map to no scope count under ``unscoped``;
- busy: the union of those ops' intervals, the time the scopes share out;
- host spans: the summed durations and counts of the program's spans that
  start in the window, and the idle gaps of the first chip named by the
  innermost program span over their middle.

A program without scopes or spans (one older than them, or no HLO text)
gives no scope time and no spans; the readers then return None.
"""
from __future__ import annotations

import re
from collections import defaultdict

from chipbench import trace

SCOPE_PREFIX = "fedsim."
SPAN_PREFIXES = ("fedsim.", "telemetry.")
PROGRAM = "jit_chunk"
MODULES_LINE = "XLA Modules"
UNSCOPED = "unscoped"

_INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_scopes(text: str) -> dict[str, str | None]:
    """Instruction name -> the first ``fedsim.*`` component of its
    ``op_name``, or None, for every instruction of the HLO text."""
    out: dict[str, str | None] = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = _OP_NAME.search(line)
        parts = name.group(1).split("/") if name else []
        out[m.group(1)] = next(
            (p for p in parts if p.startswith(SCOPE_PREFIX)), None)
    return out


def _module_intervals(plane) -> list[tuple[float, float]]:
    return sorted((ev.start_ns, ev.end_ns) for line in plane.lines
                  if line.name == MODULES_LINE for ev in line.events
                  if ev.name.startswith(PROGRAM + "("))


def _inside(t: float, intervals) -> bool:
    return any(s <= t < e for s, e in intervals)


def reduce(profile, hlo_text: str | None, *, chips: int, top: int = 5) -> dict:
    """Scope times, busy time, unscoped ops, host spans and idle gaps."""
    spans = trace.host_spans(profile)
    window = [(s, e) for s, e, n in spans if n == trace.WINDOW_SPAN]
    if not window:
        raise RuntimeError(f"no {trace.WINDOW_SPAN!r} annotation in the trace")
    lo, hi = min(s for s, _ in window), max(e for _, e in window)
    names = hlo_scopes(hlo_text) if hlo_text else {}
    planes = trace.device_planes(profile)[:chips]
    if len(planes) < chips:
        raise RuntimeError(f"{len(planes)} device planes in the trace, the "
                           f"cell uses {chips}")
    scope_s: dict[str, float] = defaultdict(float)
    unscoped_ops: dict[str, float] = defaultdict(float)
    busy_s = found_s = op_s = 0.0
    first_busy: list[tuple[float, float]] = []
    for k, plane in enumerate(planes):
        modules = _module_intervals(plane)
        intervals, all_ops = [], []
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            for ev in line.events:
                if ev.end_ns <= lo or ev.start_ns >= hi:
                    continue
                all_ops.append((ev.start_ns, ev.end_ns))
                if trace.is_container(ev.name) or not _inside(ev.start_ns, modules):
                    continue
                intervals.append((ev.start_ns, ev.end_ns))
                dur = (min(ev.end_ns, hi) - max(ev.start_ns, lo)) * 1e-9 / chips
                op = trace.op_name(ev.name)
                scope = names.get(op)
                scope_s[scope or UNSCOPED] += dur
                op_s += dur
                if op in names:
                    found_s += dur
                if scope is None:
                    unscoped_ops[op] += dur
        busy_s += sum(e - s for s, e in trace.union(intervals, lo, hi)) * 1e-9 / chips
        if k == 0:
            first_busy = trace.union(all_ops, lo, hi)
    own = [s for s in spans if s[2].startswith(SPAN_PREFIXES)]
    host_s: dict[str, float] = defaultdict(float)
    host_n: dict[str, int] = defaultdict(int)
    for s, e, name in own:
        if lo <= s < hi:
            host_s[name] += (e - s) * 1e-9
            host_n[name] += 1
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy_s,
            "scope_s": dict(scope_s), "found_share": found_s / op_s if op_s else 0.0,
            "unscoped_top": sorted(([n, t] for n, t in unscoped_ops.items()),
                                   key=lambda x: -x[1])[:top],
            "host_s": dict(host_s), "host_n": dict(host_n),
            "idle_gaps": idle_gaps(first_busy, lo, hi, own, top)}


def idle_gaps(busy, lo, hi, spans, top: int) -> list[list]:
    """The longest stretches with no op on the chip, each named by the
    innermost program span that covers its middle."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = sorted(((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        covering = [(sp_e - sp_s, name) for sp_s, sp_e, name in spans
                    if sp_s <= mid <= sp_e]
        out.append([min(covering)[1] if covering else "no program span",
                    (e - s) * 1e-9])
    return out


def per_round(ctx: dict, kind: str, name: str, scale: float) -> float | None:
    """``scale`` x the time of scope or span ``name`` (``kind`` is
    ``scope_s`` or ``host_s``) over the rounds of the window, or None."""
    reduced = ctx.get("scopes")
    if not reduced or not ctx["rounds"]:
        return None
    t = reduced[kind].get(name)
    return None if not t else scale * t / ctx["rounds"]


def summary(reduced: dict, rounds: int) -> list[str]:
    """Lines for stderr: the unscoped share and its top ops, eval, the host
    spans, and each idle gap's innermost program span."""
    busy = reduced["busy_s"]
    unscoped = reduced["scope_s"].get(UNSCOPED, 0.0)
    per = max(rounds, 1)
    lines = [f"scopes: HLO names found for {100 * reduced['found_share']:.3f}% of "
             f"round-program op time; unscoped {100 * unscoped / busy if busy else 0:.3f}% "
             f"of its busy {busy:.6f} s"]
    lines += [f"scope {n}: {1e3 * t / per:.6f} ms a round"
              for n, t in sorted(reduced["scope_s"].items(), key=lambda x: -x[1])]
    lines += [f"unscoped op {n}: {1e3 * t / per:.6f} ms a round"
              for n, t in reduced["unscoped_top"]]
    lines += [f"host span {n}: {reduced['host_n'][n]} spans, {1e3 * t / per:.6f} ms a round"
              for n, t in sorted(reduced["host_s"].items(), key=lambda x: -x[1])]
    lines += [f"idle gap {1e3 * t:.6f} ms in {n}" for n, t in reduced["idle_gaps"]]
    return lines
