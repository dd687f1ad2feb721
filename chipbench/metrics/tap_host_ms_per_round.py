"""Host time of the telemetry tap a round, in milliseconds: the summed
durations of the program's ``telemetry.emit`` spans (the callback's host
work: the privacy ledger and the tracker's write) that start in the traced
window (``scopes.reduce``), over the rounds completed in it."""
from __future__ import annotations

from chipbench.scopes import per_round


def read(ctx: dict) -> float | None:
    return per_round(ctx, "host_s", "telemetry.emit", 1e3)
