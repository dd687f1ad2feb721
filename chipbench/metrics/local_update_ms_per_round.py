"""Device time of the clients' local training a round, in milliseconds: the
round program's ops in the program's ``fedsim.local_update`` scope
(``scopes.reduce``), averaged over the chips, over the rounds completed in
the traced window."""
from __future__ import annotations

from chipbench.scopes import per_round


def read(ctx: dict) -> float | None:
    return per_round(ctx, "scope_s", "fedsim.local_update", 1e3)
