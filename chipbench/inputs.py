"""The key streams of ``--seed``, from which every family makes its inputs
(``families/<family>.py``, ``make_inputs``).

The same seed gives the same keys; a seed may be any whole number up to 64
bits.
"""
from __future__ import annotations

import jax


def base_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (PRNGKey alone keeps 32)."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def stream_keys(seed: int) -> dict[str, jax.Array]:
    """Independent key streams of a seed: weights, run keys."""
    weights, runs = jax.random.split(base_key(seed), 2)
    return {"weights": weights, "runs": runs}


def run_key(runs_key: jax.Array, i: int) -> jax.Array:
    """Key of the i-th ``session.run`` call of a process (0 = warm-up)."""
    return jax.random.fold_in(runs_key, i)
