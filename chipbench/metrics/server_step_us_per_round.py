"""Device time of the server step a round (the step size and the global
update), in microseconds: the round program's ops in the program's
``fedsim.server_step`` scope (``scopes.reduce``), averaged over the chips,
over the rounds completed in the traced window."""
from __future__ import annotations

from chipbench.scopes import per_round


def read(ctx: dict) -> float | None:
    return per_round(ctx, "scope_s", "fedsim.server_step", 1e6)
