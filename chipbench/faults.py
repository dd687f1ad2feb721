"""Faults planted in the program, to show that ``correct`` refuses them.

Each is a context manager that patches one layer of the program while the
sessions built inside it trace their round program, and restores it after:

- ``unchanged_state``: the server step returns the weights it was given;
- ``half_batch``: the release reduces the first half of the clients' updates
  and takes the mean over those.

Used by the tests (on the CPU, at a small size) and by ``calibrate.py`` (on
the chip, at the cell's size).  The benchmark's own runs never use them.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(obj, name: str, replacement):
    original = getattr(obj, name)
    setattr(obj, name, replacement(original))
    try:
        yield
    finally:
        setattr(obj, name, original)


def unchanged_state():
    from repro.core import compose

    def wrap(apply):
        def step(self, *args):
            _, aux, state = apply(self, *args)
            w = args[1]
            return w, aux, state
        return step

    return _patched(compose.FedEXPStep, "apply", wrap)


def half_batch():
    from repro.core import compose

    def dense(release):
        def half(updates, *args, **kwargs):
            return release(updates[: updates.shape[0] // 2], *args, **kwargs)
        return half

    return _patched(compose, "fused_clip_aggregate", dense)


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch}
