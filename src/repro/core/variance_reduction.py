"""Control-variate server algorithms (DP-SCAFFOLD on the engine stack, §17).

SCAFFOLD (Karimireddy et al. 2020) removes client drift with control
variates: client i steps with ``g - c_i + c`` and refreshes its variate via
option-II ``c_i+ = c_i - c + (w - y_i)/(tau * eta_l)``.  Under client-level
DP the client releases TWO vectors per round — the model update ``dy`` and
the variate update ``dc`` — each clipped and noised at std ``sigma*sqrt(2)``
(scaled by the variate scale for ``dc``), so the per-round GDP budget
composes to exactly a single release at std ``sigma`` (Noble et al. 2022;
the "noise doubling" the paper's §5 points at).

``DPScaffoldServer`` is that baseline as an engine-facing
``ServerAlgorithm``: the per-client variates live in the server carry
(``ScaffoldState``), the LocalTrainer receives each round's variate rows
through the ``uses_local_context`` hook (``fedsim/server.py::_local_caller``
appends ``local_context(state, start, m_local)`` to the trainer call), and
the two releases ride the standard dense/moments round halves — so the
legacy ``run_dp_scaffold`` Python loop's algorithm now composes with cohort
sampling, streaming, sparse gather, sharding and fault injection.

Bit-compatibility contract (tests/test_schedules.py):

* the DENSE path (scan/eager engines, full participation) replicates the
  legacy ``run_dp_scaffold`` round verbatim — same key splits, same
  ``jnp.mean`` reductions, same central (d,) draws — so ``central=True``
  runs match the retired loop bit-for-bit at any sigma;
* the MOMENTS path (stream/gather/sharded engines) writes sums (``v @ rows``
  — psum-able, mask-weighted) and re-keys local-mode noise per GLOBAL client
  index (``materialize_ldp_noise``), so engines agree at the stack's usual
  cross-engine tolerance; at sigma=0 both paths are bit-identical and the
  local-mode legacy pin holds exactly.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core import accounting
from repro.core.aggregation import (
    RoundMoments,
    global_client_indices,
    materialize_ldp_noise,
    sum_dot,
)
from repro.core.algorithm import RoundAux, ServerAlgorithm
from repro.core.clipping import clip_batch

__all__ = ["ScaffoldState", "DPScaffoldServer"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ScaffoldState:
    """Server carry of a control-variate run: the global variate ``c`` (d,)
    and the per-client variate table ``c_is`` (num_clients, d).

    Rides the engines' existing scan/stream/checkpoint carry exactly like an
    optimizer state — resume, §13 rollback and the divergence watchdog all
    snapshot/restore it with the model vector, no engine changes.
    """

    c: jax.Array
    c_is: jax.Array


@dataclasses.dataclass(frozen=True)
class DPScaffoldServer(ServerAlgorithm):
    """DP-SCAFFOLD (Noble, Bellet, Dieuleveut, AISTATS 2022) as a stateful
    engine algorithm: two clipped+noised releases per round over a
    control-variate local trainer (``LocalSpec(control_variates=True)``).

    ``central=True`` noises the two means server-side at
    ``sigma*sqrt(2)/sqrt(num_clients)`` (CDP); ``central=False`` noises each
    client's releases at ``sigma*sqrt(2)`` before aggregation (LDP).  The
    eta_g is pinned to 1 — SCAFFOLD has no extrapolation rule; that contrast
    IS the paper's baseline comparison.
    """

    clip_norm: float
    sigma: float                 # baseline noise scale (as for DP-FedAvg)
    central: bool                # True: CDP noise on the means
    num_clients: int
    tau: int
    eta_l: float

    name = "dp-scaffold"
    uses_local_context = True    # _local_caller appends (c_i rows, c)

    def __post_init__(self):
        if self.clip_norm <= 0:
            raise ValueError(f"clip_norm must be positive, got {self.clip_norm}")
        if self.sigma < 0:
            raise ValueError(f"sigma must be >= 0, got {self.sigma}")
        if self.num_clients < 1:
            raise ValueError(f"num_clients must be >= 1, got {self.num_clients}")
        if self.tau < 1:
            raise ValueError(f"tau must be >= 1, got {self.tau}")
        if self.eta_l <= 0:
            raise ValueError(f"eta_l must be positive, got {self.eta_l}")

    @property
    def variate_scale(self) -> float:
        """Option-II refresh scale 1/(tau * eta_l): dc = -c - vs * dy."""
        return 1.0 / (self.tau * self.eta_l)

    def comm_floats(self, d: int) -> int:
        """Two (d,) releases ride every round reduction (the §16 model
        counts the variate-update vector next to the usual payload)."""
        return 2 * d + 3

    def init_state(self, w):
        """Zero variates: the legacy loop's exact starting carry."""
        d = w.shape[-1]
        return ScaffoldState(c=jnp.zeros_like(w),
                             c_is=jnp.zeros((self.num_clients, d), w.dtype))

    # -- LocalTrainer context (fedsim/server.py::_local_caller) -------------

    def local_context(self, state, start, m_local: int):
        """This shard/chunk's variate rows + the global variate: ``(c_i, c)``.

        ``start`` follows the engines' global-index contract: a static 0
        (dense full cohort — returns the table itself, bit-identical), a
        traced scalar (shard/chunk slices; the table is zero-padded by
        ``m_local`` rows so fully-padded tail chunks clamp onto inert zero
        rows), or a (m_local,) gather-slot vector (§14; padding slots point
        at client 0 and are mask-zeroed downstream).
        """
        c_is = state.c_is
        m = c_is.shape[0]
        if getattr(start, "ndim", 0) == 1:
            rows = jnp.take(c_is, jnp.minimum(start, m - 1), axis=0)
            return rows, state.c
        if isinstance(start, int) and start == 0 and m_local == m:
            return c_is, state.c
        padded = jnp.concatenate(
            [c_is, jnp.zeros((m_local,) + c_is.shape[1:], c_is.dtype)])
        rows = jax.lax.dynamic_slice_in_dim(padded, start, m_local)
        return rows, state.c

    def _dc(self, deltas, c_i, c):
        """Variate updates from the raw dy rows — the legacy loop's exact
        op order ``(c_i - c - vs*dy) - c_i`` (NOT the algebraic ``-c -
        vs*dy``: fp non-associativity makes those differ bitwise, and the
        dense path is pinned bit-for-bit against the retired loop)."""
        c_i_new = c_i - c - deltas * self.variate_scale
        return c_i_new - c_i

    # -- dense round (scan/eager engines; legacy-verbatim) ------------------

    def apply_round(self, key, w, raw_deltas):
        """One dense server round: ``(key, w, (M, d) raw deltas) -> (w_next, RoundAux)``."""
        raise TypeError(f"{self.name} is stateful; use apply_round_stateful")

    def apply_round_stateful(self, key, w, raw_deltas, state):
        """Full-participation dense round, replicating the retired
        ``run_dp_scaffold`` body verbatim (same splits, same ``jnp.mean``):
        the bit-for-bit legacy pin.  Local-mode noise is the per-client
        keyed stream (``materialize_ldp_noise``) rather than the loop's one
        monolithic (M, d) draw — identical at sigma=0, where the local pin
        is asserted, and engine-reproducible at sigma>0."""
        m, d = raw_deltas.shape
        vs = self.variate_scale
        dc = self._dc(raw_deltas, state.c_is, state.c)
        dy_clip = clip_batch(raw_deltas, self.clip_norm)
        dc_clip = clip_batch(dc, self.clip_norm * vs)
        k_dy, k_dc = jax.random.split(key)
        if self.central:
            std = self.sigma * math.sqrt(2.0) / math.sqrt(self.num_clients)
            dy_bar = jnp.mean(dy_clip, axis=0) \
                + std * jax.random.normal(k_dy, (d,))
            dc_bar = jnp.mean(dc_clip, axis=0) \
                + std * vs * jax.random.normal(k_dc, (d,))
        else:
            std = self.sigma * math.sqrt(2.0)
            dy_bar = jnp.mean(
                dy_clip + materialize_ldp_noise(k_dy, m, d, std,
                                                raw_deltas.dtype, start=0),
                axis=0)
            dc_bar = jnp.mean(
                dc_clip + materialize_ldp_noise(k_dc, m, d, std * vs,
                                                raw_deltas.dtype, start=0),
                axis=0)
        state_next = ScaffoldState(c=state.c + dc_bar,
                                   c_is=state.c_is + dc_clip)
        return w + dy_bar, RoundAux(eta_g=jnp.float32(1.0)), state_next

    # -- sharded/streamed round halves (DESIGN.md §9/§12/§14) ---------------

    def local_moments(self, key, w, deltas, mask, start, state):
        """Partial SUMS of both releases over the masked rows at global
        ``start``: the dy release rides the standard ``RoundMoments``; the
        dc release sum and the per-client variate-table delta (a scattered
        (num_clients, d) add — additive across shards/chunks, so it psums)
        ride the extras dict."""
        m_local, d = deltas.shape
        vs = self.variate_scale
        if mask is None:
            mask = jnp.ones((m_local,), jnp.float32)
        gidx = global_client_indices(start, m_local)
        c_i = jnp.take(state.c_is, jnp.minimum(gidx, self.num_clients - 1),
                       axis=0)
        # gate BEFORE clipping: a masked row's dc would otherwise be the
        # nonzero -c (its deltas are zeroed, its c_i is a pad/garbage row)
        gate = mask[:, None] > 0
        dc = jnp.where(gate, self._dc(deltas, c_i, state.c), 0.0)
        dy_clip = clip_batch(deltas, self.clip_norm)
        dc_clip = clip_batch(dc, self.clip_norm * vs)
        rel_dy, rel_dc = dy_clip, dc_clip
        if not self.central and self.sigma > 0:
            k_dy, k_dc = jax.random.split(key)
            std = self.sigma * math.sqrt(2.0)
            rel_dy = dy_clip + materialize_ldp_noise(
                k_dy, m_local, d, std, deltas.dtype, start=start)
            rel_dc = dc_clip + materialize_ldp_noise(
                k_dc, m_local, d, std * vs, deltas.dtype, start=start)
        mom = RoundMoments(
            sum_c=sum_dot(mask, rel_dy),
            sum_sq=sum_dot(mask, jnp.sum(jnp.square(rel_dy), axis=-1)),
            sum_sq_clipped=sum_dot(mask, jnp.sum(jnp.square(dy_clip), axis=-1)),
            count=jnp.sum(mask))
        cis_add = jnp.zeros((self.num_clients, d), deltas.dtype) \
            .at[gidx].add(dc_clip * mask[:, None], mode="drop")
        return mom, {"sum_dc": sum_dot(mask, rel_dc), "cis_add": cis_add}

    def apply_from_moments(self, key, w, moments, state):
        """Replicated server update from the psummed two-release moments;
        central noise is drawn AFTER the reduction from the replicated round
        key (the same ``split`` the dense path performs), so sharded and
        single-device central runs add identical (d,) draws."""
        mom, extras = moments
        d = w.shape[-1]
        dy_bar = mom.sum_c / mom.count
        dc_bar = extras["sum_dc"] / mom.count
        if self.central:
            k_dy, k_dc = jax.random.split(key)
            # static num_clients, as the legacy loop (and the fixed-sigma
            # CentralGaussian): the Prop.-style accounting is stated for it
            std = self.sigma * math.sqrt(2.0) / math.sqrt(self.num_clients)
            dy_bar = dy_bar + std * jax.random.normal(k_dy, (d,))
            dc_bar = dc_bar + std * self.variate_scale \
                * jax.random.normal(k_dc, (d,))
        state_next = ScaffoldState(c=state.c + dc_bar,
                                   c_is=state.c_is + extras["cis_add"])
        return w + dy_bar, RoundAux(eta_g=jnp.float32(1.0)), state_next

    # -- accounting ---------------------------------------------------------

    def budget(self, delta: float, *, rounds: int, dim: int | None = None,
               sampling_q: float = 1.0) -> accounting.PrivacyReport:
        """Two per-round releases at std sigma*sqrt(2) (dy) and
        sigma*sqrt(2)*vs against sensitivity 2C*vs (dc) each carry GDP
        mu/sqrt(2) of the single-release mechanism; they compose to exactly
        the single-release budget at std sigma, so the report delegates to
        the standard curves (the scale cancels from the dc release's
        sensitivity/noise ratio)."""
        if self.sigma <= 0:
            raise ValueError(f"{self.name} with sigma=0 is not private")
        if self.central:
            rep = accounting.cdp_budget(self.clip_norm, self.sigma,
                                        self.num_clients, rounds, delta,
                                        sampling_q=sampling_q)
            return dataclasses.replace(
                rep, setting="CDP (Gaussian, SCAFFOLD two-release)")
        rep = accounting.ldp_gaussian_budget(self.clip_norm, self.sigma, delta)
        return dataclasses.replace(
            rep, setting="LDP (Gaussian, SCAFFOLD two-release)")
