"""Server-algorithm base contract shared by the monolithic classes and the
composable stack.

``ServerAlgorithm`` is the engine-facing interface (DESIGN.md §8/§9): a round
is either one dense call (``apply_round`` / ``apply_round_stateful``) or the
two sharded halves (``local_moments`` -> psum -> ``apply_from_moments``).
This module holds that contract plus the moment-count helpers and the
per-client key derivation — everything both ``repro.core.fedexp`` (the legacy
monolithic algorithms) and ``repro.core.compose`` (the mechanism x
aggregation x step compositions) depend on, so neither imports the other.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.aggregation import RoundMoments, global_client_indices
from repro.telemetry import spans

__all__ = [
    "RoundAux",
    "ServerAlgorithm",
    "client_keys",
    "set_moment_count",
    "clamp_moment_counts",
]


def _map_moments(moments, fix):
    """Apply ``fix`` to every RoundMoments in an algorithm's moments pytree
    (a bare RoundMoments or a (RoundMoments, extras) tuple)."""
    def one(x):
        """Apply ``fix`` when the element is a RoundMoments, else pass through."""
        return fix(x) if isinstance(x, RoundMoments) else x

    if isinstance(moments, tuple):
        return tuple(one(e) for e in moments)
    return one(moments)


def set_moment_count(moments, m_total: int):
    """Swap the traced client count for its statically-known value in every
    RoundMoments of an algorithm's moments pytree.

    Used when the true count is known at trace time (the full cohort size on
    the sharded path, the fixed cohort size on the sampled path): the static
    constant lets XLA fold the 1/M normalizations exactly as the unsampled
    single-device reference does, keeping engines bit-compatible (see
    ``ServerAlgorithm.apply_round_sharded``)."""
    c = jnp.float32(m_total)
    return _map_moments(moments, lambda x: dataclasses.replace(x, count=c))


def clamp_moment_counts(moments, floor: float = 1.0):
    """Clamp every RoundMoments count to >= ``floor``.

    Bernoulli cohort sampling can draw an empty round; with all sums already
    zero, a clamped count turns the 0/0 mean into a zero update (the round is
    a no-op) instead of NaN-poisoning the carry.  Weighted-aggregation
    counts are weight SUMS (legitimately < 1), so the engine clamps those
    with a tiny ``floor`` that only guards the empty round — clamping to 1
    would silently rescale every light-cohort mean."""
    return _map_moments(
        moments,
        lambda x: dataclasses.replace(x, count=jnp.maximum(x.count, floor)))


def client_keys(key: jax.Array, m: int, start: int | jax.Array = 0) -> jax.Array:
    """(m,) per-client PRNG keys: row i is ``fold_in(key, start + i)``.

    Keyed by GLOBAL client index so a client shard derives exactly its own
    clients' keys (pass ``start = shard_index * m_local``) and the sharded
    release reproduces the single-device randomization bit-for-bit.  A (m,)
    vector ``start`` names the global index of each row directly (the
    sparse-gather path, DESIGN.md §14).
    """
    idx = global_client_indices(start, m)
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)


@dataclasses.dataclass
class RoundAux:
    """Diagnostics for one round (logged by fedsim / benchmarks).

    Every field is a fixed-shape scalar array: diagnostics an algorithm does
    not produce are NaN, NOT None, so one round is scan-compatible (the
    engine stacks these across rounds without Python-level branching).
    """

    eta_g: jax.Array
    eta_naive: jax.Array | None = None   # Eq. (3), for the Fig. 2 ablation
    eta_target: jax.Array | None = None  # Eq. (5), oracle diagnostic
    update_norm: jax.Array | None = None

    def __post_init__(self):
        for f in ("eta_naive", "eta_target", "update_norm"):
            if getattr(self, f) is None:
                setattr(self, f, jnp.float32(jnp.nan))


class ServerAlgorithm:
    """Base class; subclasses set `name` and implement apply_round.

    Stateless algorithms implement ``apply_round``; stateful servers (the
    FedOpt family — server Adam/momentum over pseudo-gradients) override
    ``init_state`` / ``apply_round_stateful``, which the training loop
    threads through its carry. Default wrappers keep the two interchangeable.

    Sharded-round protocol (DESIGN.md §9).  A round is also expressible as
    two halves the client-sharded engine splits across the ``clients`` mesh
    axis:

        local_moments(key, w, deltas, mask, start, state)  -> pytree of SUMS
        apply_from_moments(key, w, global_moments, state)  -> (w', aux, state)

    ``local_moments`` runs per-device on that shard's (m_local, d) slice of
    the cohort (``start`` = global index of its first client, ``mask``
    zero-weights padding rows) and returns only partial sums; the engine
    ``psum``s them and every device applies the identical server update —
    noise is drawn AFTER the reduction from the replicated round key, so DP
    semantics match the single-device path exactly.
    """

    name: str = "base"
    is_private: bool = True
    # set_moment_count / fixed-size-count substitution is valid: the count of
    # a RoundMoments really is the number of participating clients.  The
    # weighted-aggregation compositions (count = sum of client weights) set
    # this False and the engine leaves their counts traced (DESIGN.md §11).
    supports_static_count: bool = True

    def apply_round(self, key: jax.Array, w: jax.Array, raw_deltas: jax.Array):
        """One dense server round: ``(key, w, (M, d) raw deltas) -> (w_next, RoundAux)``."""
        raise NotImplementedError

    def comm_floats(self, d: int) -> int:
        """Floats of per-round reduced state (the communication model,
        DESIGN.md §16): what one client uploads and the round collective
        moves — ``sum_c`` plus the three scalar moments by default.
        Compressed compositions override this with their O(k) /
        O(width·depth) payload; the telemetry tap reports
        ``4 * comm_floats(d)`` as ``bytes_per_round``."""
        return d + 3

    def init_state(self, w: jax.Array):
        """Initial optimizer/clip carry for a run starting from ``w``."""
        return ()

    def apply_round_stateful(self, key, w, raw_deltas, state):
        """Stateful dense round: ``apply_round`` threading the optimizer/clip carry."""
        w_next, aux = self.apply_round(key, w, raw_deltas)
        return w_next, aux, state

    def local_moments(self, key, w, deltas, mask, start, state):
        """Shard-local partial sums (a psum-able pytree; SUMS, never means)."""
        raise NotImplementedError(f"{self.name} has no sharded-round support")

    def apply_from_moments(self, key, w, moments, state):
        """Server update from globally-reduced moments; replicated math."""
        raise NotImplementedError(f"{self.name} has no sharded-round support")

    def apply_round_sharded(self, key, w, deltas, mask, state, axis_name,
                            m_total: int | None = None):
        """One round on a client shard (call inside ``shard_map``).

        ``m_total`` is the STATIC true client count when the caller knows it
        (the engine always does — it built the padding mask).  Replacing the
        psummed mask-sum with the static constant lets XLA fold the 1/M
        normalizations exactly as the single-device reference's static
        ``sum / m`` does, keeping the two engines bit-compatible instead of
        one ULP apart."""
        start = jax.lax.axis_index(axis_name) * deltas.shape[0]
        moments = self.local_moments(key, w, deltas, mask, start, state)
        with jax.named_scope(spans.PSUM):
            moments = jax.lax.psum(moments, axis_name)
        if m_total is not None and self.supports_static_count:
            moments = set_moment_count(moments, m_total)
        return self.apply_from_moments(key, w, moments, state)
