"""Device time of the ``dp_aggregate`` kernel per round, in microseconds:
the summed durations of its events on each chip (the round program's Mosaic
``tpu_custom_call``, ``trace.is_kernel``), averaged over the chips, over the
rounds completed in the traced window."""
from __future__ import annotations


def read(ctx: dict) -> float | None:
    t = ctx["trace"]
    if not t["kernel_events"] or not ctx["rounds"]:
        return None
    return 1e6 * t["kernel_s"] / ctx["rounds"]
