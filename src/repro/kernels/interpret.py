"""The one place a kernel wrapper's ``interpret=None`` default is resolved."""
from __future__ import annotations

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> compiled on a TPU, the Pallas interpreter everywhere else.

    An explicit ``True``/``False`` passes through: tests force the
    interpreter, and a TPU caller never silently falls back to it.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
