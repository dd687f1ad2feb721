"""Share of the traced window in which no operation ran on the device, in
percent, averaged over the chips used (``trace.reduce``)."""
from __future__ import annotations


def read(ctx: dict) -> float | None:
    t = ctx["trace"]
    if not t["window_s"] or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
