"""Model FLOPs utilisation of the whole round step, in percent.

Model FLOPs per round (``counts.round_flops``: forward and backward of every
local step over every unmasked sample, and the per-round eval) times the
traced window's rounds per second, over the chips used times their peak
bf16 FLOP/s.
"""
from __future__ import annotations

from chipbench.counts import round_flops


def read(ctx: dict) -> float | None:
    if not ctx["rounds"]:
        return None
    rate = ctx["rounds"] / ctx["window_s"]
    return 100.0 * round_flops(ctx["cfg"]) * rate / (
        ctx["chips"] * ctx["peaks"]["bf16_flops"])
