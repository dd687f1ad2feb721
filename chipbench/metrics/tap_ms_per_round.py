"""Device time of the telemetry tap a round, in milliseconds: the round
program's ops in the program's ``fedsim.tap`` scope (``scopes.reduce``):
the payload and the ordered ``io_callback``, which holds the device until
the host has taken the round.  Averaged over the chips, over the rounds
completed in the traced window."""
from __future__ import annotations

from chipbench.scopes import per_round


def read(ctx: dict) -> float | None:
    return per_round(ctx, "scope_s", "fedsim.tap", 1e3)
