"""Device time of the release a round (clip, noise and aggregate, with the
``dp_aggregate`` kernel and its pad), in microseconds: the round program's
ops in the program's ``fedsim.release`` scope (``scopes.reduce``), averaged
over the chips, over the rounds completed in the traced window."""
from __future__ import annotations

from chipbench.scopes import per_round


def read(ctx: dict) -> float | None:
    return per_round(ctx, "scope_s", "fedsim.release", 1e6)
