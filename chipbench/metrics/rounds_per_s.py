"""Rounds per second: every round that the window's calls completed, over
the host-clock time from the window's start to the end of its last call."""
from __future__ import annotations


def read(ctx: dict) -> float | None:
    if not ctx["rounds"]:
        return None
    return ctx["rounds"] / ctx["window_s"]
