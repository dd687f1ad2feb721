"""Set-up time: process start to the window's start, on the host clock
(JAX start-up, inputs from the seed, the session and its warm-up call)."""
from __future__ import annotations


def read(ctx: dict) -> float | None:
    return ctx["setup_s"]
