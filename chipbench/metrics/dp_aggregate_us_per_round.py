"""Device time of the ``dp_aggregate`` kernel per round, in microseconds:
the summed durations of its events on each chip (the round program's Mosaic
calls named ``%dp_aggregate.N``, ``trace.is_dp_aggregate``), averaged over
the chips, over the rounds completed in the traced window.  A program whose
kernel bears no such name reads nothing."""
from __future__ import annotations


def read(ctx: dict) -> float | None:
    t = ctx["trace"]
    if not t["dp_aggregate_events"] or not ctx["rounds"]:
        return None
    return 1e6 * t["dp_aggregate_s"] / ctx["rounds"]
