"""Federated round engine: compiled scan chunks, sharding, cohort sampling.

Runs T rounds of: broadcast -> vmapped local training (Algorithm 3) ->
clip/randomize/aggregate + adaptive step size (Algorithms 1/2) -> global
update.

This module owns the ENGINE MACHINERY — the round-step builders, the scan
bodies, and the compile caches.  The public entry point is
``repro.fedsim.session.FederatedSession`` (DESIGN.md §10), which composes
these builders from declarative specs; ``run_federated`` /
``run_federated_batched`` below are thin deprecated shims over a session and
keep their historical behavior bit-for-bit.

Engine (DESIGN.md §8).  The default scan engine compiles the whole round
loop as ``jax.lax.scan`` programs: T rounds run as ceil(T/chunk_rounds) XLA
dispatches (one, by default), per-round PRNG keys are ``fold_in``-derived
inside the scan, the eta/metric/naive/target histories come back as stacked
scan outputs, and the trailing ``avg_last`` iterates ride in the scan carry
so the §5 iterate average needs no host-side tail.  The carry is donated on
accelerators, and the compiled chunk program is cached across calls keyed on
the (frozen, hashable) algorithm + spec configuration.

Client sharding (DESIGN.md §9): a 1-D ``clients`` mesh wraps the same scan
program in ``shard_map``; each device holds a (M/n_shards, d) cohort slice
and only the O(d) aggregation moments cross devices via one ``psum`` per
round.  Cohorts with M % n_shards != 0 are padded with zero-weight clients
(``pad_cohort``) that every moment masks out.

Cohort sampling (DESIGN.md §10): a ``CohortSpec`` with q<1 or a fixed size
draws a per-round participation mask INSIDE the scan body (static shapes —
sampled rounds stay one compiled program per chunk) and routes the round
through the same masked-moment machinery sharding uses: non-participants'
updates are zero-weighted at the source and every reduction is mask-weighted,
so the release is mathematically the sampled-cohort release.  The sampling
mask is derived from the replicated round key, so sharded and single-device
sampled runs see the identical cohort.

Streaming cohorts (DESIGN.md §12): ``engine="stream"`` iterates each round's
cohort in ``StreamSpec.chunk_clients``-sized chunks via an INNER ``lax.scan``
nested in the round scan: every chunk runs local training + the per-client
release on its (c, d) block and only the additive ``RoundMoments`` (plus the
PrivUnit / adaptive-clip extras, all SUMS) accumulate in the inner carry, so
peak update-matrix memory is O(chunk_clients * d) instead of O(M * d).  All
per-client randomness keys by GLOBAL client index, so the streamed release
draws exactly the dense engine's randomization; the chunk-boundary
re-association of the sums is the only difference (rtol 1e-5, bit-exact when
one chunk covers the cohort).  Composes with sampling (the full mask is
derived from the replicated round key and sliced per chunk) and with §9
sharding (each shard streams its own cohort slice; one O(d) psum per round,
after the inner scan).

Compressed communication (DESIGN.md §16): a compressed ``Aggregation`` layer
(rand-k / count-sketch) shrinks ``RoundMoments.sum_c`` from (d,) to the
compressed width at the source — inside ``algorithm.local_moments`` — and
every engine path here inherits it with NO structural change, because each
one only ever ADDS moments: the sharded psum is pytree-shaped by the local
moments, the stream inner-scan carry is zero-initialized from
``jax.eval_shape`` of the chunk program, the gather engine reduces the same
moments over slots, and the count-resolution helpers
(``set_moment_count`` / ``clamp_moment_counts`` / ``sanitize_moments``) are
field-targeted tree_maps that never look at ``sum_c``'s shape.  The per-round
collective is therefore O(k) / O(width·depth) on all four paths.  The shared
per-round compression plan derives from the replicated round key
(COMPRESS_TAG), so shard/chunk partial sums are summands of one linear map.

Following §5 of the paper, the returned final model is the average of the
last two iterates ("to mitigate the oscillating behaviour of DP-FedEXP").
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.experimental import io_callback
from jax.sharding import PartitionSpec as P

from repro.core.fedexp import ServerAlgorithm, clamp_moment_counts, set_moment_count
from repro.fedsim.faults import apply_faults, fault_masks, gather_fault_rows, resolve_steps, sanitize_moments
from repro.fedsim.local import gather_rows, gather_slots, mask_rows
from repro.fedsim.specs import CohortSpec, FaultSpec, StreamSpec
from repro.models.sharding import client_axis_rules, logical_to_pspec
from repro.telemetry import spans

__all__ = ["RunResult", "run_federated", "run_federated_batched"]


@dataclasses.dataclass
class RunResult:
    """Outputs of a federated run: final/last weights + per-round histories."""
    final_w: Any                  # average of the last `avg_last` iterates
    last_w: Any                   # pytree-shaped when the session got a pytree
    eta_history: jax.Array        # (T,)
    metric_history: jax.Array     # (T,) eval metric per round (nan if no
    #                               eval_fn or the round is off cadence)
    eta_naive_history: jax.Array | None = None
    eta_target_history: jax.Array | None = None
    fault_round: int | None = None  # watchdog: first diverged round (§13)

    def eval_rounds(self) -> list[tuple[int, float]]:
        """(round, metric) pairs for the rounds the eval cadence actually
        evaluated — the NaN sentinels that pad ``metric_history`` off the
        ``eval_every`` grid (and past a watchdog trip) are dropped, so
        consumers never NaN-filter by hand.  Batched results (leading seed
        axis) have no single eval trace; index the history yourself there.
        """
        hist = jax.device_get(self.metric_history)
        if hist.ndim != 1:
            raise ValueError(
                "eval_rounds() needs a single-run (T,) metric history; this "
                f"result's is {hist.shape} — a run_batched result carries a "
                "leading seed axis, slice it per seed instead")
        import math
        return [(t, float(v)) for t, v in enumerate(hist)
                if math.isfinite(float(v))]


def _eval_metric(eval_fn, eval_every: int, w_next, t):
    """Per-round metric honoring the eval cadence.

    eval_every == 1 keeps the historical unconditional call (bit-identical
    program); a larger cadence guards the eval behind ``lax.cond`` so skipped
    rounds cost nothing and record NaN (fixed-shape histories).
    """
    if eval_fn is None:
        return jnp.float32(jnp.nan)
    with jax.named_scope(spans.EVAL):
        if eval_every == 1:
            return eval_fn(w_next)
        return jax.lax.cond((t + 1) % eval_every == 0,
                            lambda w: jnp.asarray(eval_fn(w), jnp.float32),
                            lambda w: jnp.float32(jnp.nan), w_next)


def _resolve_sampled_count(moments, cohort: CohortSpec, algorithm):
    """Fix the moments' client count for a sampled round.

    Fixed-size cohorts have a statically known count — substituting it lets
    XLA fold the 1/|S_t| normalizations identically on every engine (the same
    trick as ``m_total`` on the sharded path).  Bernoulli counts are traced
    and can be zero on an unlucky round; clamping to >= 1 turns the empty
    round into a zero update instead of NaN poison.  Algorithms whose count
    is not a client count (weighted aggregation: count = sum of weights)
    opt out of the static substitution via ``supports_static_count``.
    """
    if getattr(algorithm, "supports_static_count", True):
        if cohort.size is not None:
            return set_moment_count(moments, cohort.size)
        return clamp_moment_counts(moments)
    # weighted aggregation: the count is a weight sum, legitimately < 1 —
    # only guard the 0/0 of an empty Bernoulli round
    return clamp_moment_counts(moments, floor=1e-12)


def _resolve_realized_count(moments, algorithm):
    """Count resolution for fault-active rounds (DESIGN.md §13).

    Under injected faults the realized participation is traced and strictly
    below the nominal cohort, so the static-count substitution of
    ``_resolve_sampled_count`` never applies — always clamp instead, so an
    all-failed round resolves as a zero update, never NaN.
    """
    if getattr(algorithm, "supports_static_count", True):
        return clamp_moment_counts(moments)
    return clamp_moment_counts(moments, floor=1e-12)


def _round_kwargs(algorithm, t):
    """Round-index kwargs for the algorithm's round calls (DESIGN.md §17).

    Round-indexed algorithms (a genuinely varying ``NoiseSchedule``) receive
    ``t=t`` so the mechanism can resolve sigma(t); every other algorithm —
    including the legacy monoliths, whose round methods have no ``t``
    parameter at all — keeps its exact historical call, so fixed-noise
    programs are untouched bit-for-bit.
    """
    if getattr(algorithm, "needs_round_index", False):
        return {"t": t}
    return {}


def _local_caller(local_fn, fault: FaultSpec | None, tau: int,
                  algorithm=None):
    """Adapter calling the LocalTrainer with or without per-client steps
    and/or per-client server context.

    When the fault model cuts stragglers short, the session built the
    ``with_steps`` LocalTrainer variant (arity +1) and every engine resolves
    the per-client step counts from the straggler draw.  When the algorithm
    declares ``uses_local_context`` (DP-SCAFFOLD control variates, §17), the
    trainer takes one more trailing argument — the algorithm's per-client
    context rows sliced from the carry at the round's global start.  With
    neither active, the historical closure is called untouched
    (bit-identical program).
    """
    straggling = fault is not None and fault.straggler > 0.0
    with_ctx = algorithm is not None and getattr(
        algorithm, "uses_local_context", False)

    def call(w, batches, eta_l, round_key, start, straggler_rows=None,
             opt_state=None):
        with jax.named_scope(spans.LOCAL_UPDATE):
            args = (w, batches, eta_l, round_key, start)
            if straggling:
                args += (resolve_steps(fault, straggler_rows, tau),)
            if with_ctx:
                m_local = jax.tree_util.tree_leaves(batches)[0].shape[0]
                args += (algorithm.local_context(opt_state, start, m_local),)
            return local_fn(*args)

    return call


def _pad_slice(v, m_pad: int, start, m_local: int):
    """Zero-pad a full-cohort fault vector to the padded grid and slice this
    shard/chunk's rows — the §9/§10 full-mask-then-slice pattern.  Zero is
    the inert pad for every fault class (dead / on-time / uncorrupted); pad
    rows are masked out regardless."""
    if v is None:
        return None
    if m_pad > v.shape[0]:
        v = jnp.concatenate(
            [v, jnp.zeros((m_pad - v.shape[0],), v.dtype)])
    return jax.lax.dynamic_slice(v, (start,), (m_local,))


def _round_step(algorithm, local_fn, eval_fn, eval_every: int = 1,
                cohort: CohortSpec | None = None,
                fault: FaultSpec | None = None, tau: int = 1):
    """One server round; identical computation for scan and eager engines.

    ``local_fn`` is the LocalTrainer closure built by
    ``repro.fedsim.local.build_cohort_local_fn`` — full-batch GD (the
    historical path, bit-for-bit) or a LocalSpec trainer.  With no (active)
    cohort spec this is the full-participation round; a sampling spec
    reroutes the round through the masked-moment protocol: all M clients
    still compute local updates (static shapes), the participation mask
    zero-weights non-participants, and the algorithm consumes mask-weighted
    moments exactly as on a client shard.

    An injecting ``FaultSpec`` reroutes even full-participation rounds
    through the same masked protocol: the round's fault draws turn failed
    clients into zero-weight rows (``apply_faults``) and the REALIZED count
    flows through the clamped resolution (DESIGN.md §13).

    ``CohortSpec(gather=True)`` (DESIGN.md §14) replaces the all-M masked
    round with the sparse fast path: the participation mask is packed into a
    static (cap,) slot table, client batches (and fault rows) are gathered by
    slot, local training runs on the gathered block only, and the moments are
    keyed by the slots' GLOBAL indices — the identical release in O(q·M·d)
    work.

    Compressed aggregation layers (§16) ride both branches untouched: the
    dense branch routes compressed compositions through the moment protocol
    (``apply_round_stateful`` does internally), and the masked branch's
    moments simply carry a compressed-width ``sum_c``.
    """
    sampled = cohort is not None and cohort.is_sampled
    gathering = sampled and cohort.gather
    injecting = fault is not None and fault.injects
    local = _local_caller(local_fn, fault, tau, algorithm)

    def step(w, opt_state, round_key, t, client_batches, eta_l):
        """One server round inside the compiled scan body."""
        tkw = _round_kwargs(algorithm, t)
        if not sampled and not injecting:
            deltas = local(w, client_batches, eta_l, round_key, 0,
                           None, opt_state)
            w_next, aux, opt_state = algorithm.apply_round_stateful(
                round_key, w, deltas, opt_state, **tkw)
        else:
            m = jax.tree_util.tree_leaves(client_batches)[0].shape[0]
            mask = (cohort.round_mask(round_key, m) if sampled
                    else jnp.ones((m,), jnp.float32))
            if gathering:
                slots, mask, _ = gather_slots(mask, cohort.resolved_cap(m))
                client_batches = gather_rows(client_batches, slots)
                start = slots
            else:
                start = 0
            if injecting:
                alive, straggler, corrupt = fault_masks(fault, round_key, m)
                if gathering:
                    alive, straggler, corrupt = gather_fault_rows(
                        slots, alive, straggler, corrupt)
                deltas = local(w, client_batches, eta_l, round_key, start,
                               straggler, opt_state)
                deltas, mask = apply_faults(deltas, mask, alive, corrupt)
            else:
                deltas = mask_rows(
                    local(w, client_batches, eta_l, round_key, start,
                          None, opt_state), mask)
            moments = algorithm.local_moments(round_key, w, deltas, mask,
                                              start, opt_state, **tkw)
            if injecting:
                moments = sanitize_moments(moments)
                moments = _resolve_realized_count(moments, algorithm)
            else:
                moments = _resolve_sampled_count(moments, cohort, algorithm)
            w_next, aux, opt_state = algorithm.apply_from_moments(
                round_key, w, moments, opt_state, **tkw)
        metric = _eval_metric(eval_fn, eval_every, w_next, t)
        outs = (aux.eta_g, metric, aux.eta_naive, aux.eta_target)
        return w_next, opt_state, outs

    return step


def _sharded_round_step(algorithm, local_fn, eval_fn, axis, m_true,
                        m_pad: int | None = None, eval_every: int = 1,
                        cohort: CohortSpec | None = None,
                        fault: FaultSpec | None = None, tau: int = 1):
    """One round on a client shard; runs inside ``shard_map`` over ``axis``.

    Same round semantics as ``_round_step``, but local training and the
    clip/randomize reductions see only this device's cohort slice, and the
    algorithm's partial moments are psummed before the replicated server
    update.  ``m_true`` is the static pre-padding client count.  Local
    training receives the shard's GLOBAL start index, so spec trainers
    shuffle exactly as the single-device engine.  With cohort sampling,
    every device derives the FULL participation mask from the replicated
    round key and slices its own rows, so the sampled cohort is identical to
    the single-device engine's.  Fault draws follow the same full-cohort-
    then-slice pattern (DESIGN.md §13), so a faulty sharded run degrades
    exactly as its single-device reference.

    With ``CohortSpec(gather=True)`` (§14) each shard packs ITS slice of the
    participation mask into a per-shard slot table (static cap bounded by the
    shard's client count) and trains only the gathered rows; the moments key
    by ``shard_start + slot`` — the same global indices the dense engines
    use — and cross shards in the identical single psum.

    With a compressed aggregation layer (§16) the psummed ``sum_c`` is the
    compressed partial sum — every shard builds the identical plan from the
    replicated round key, so the psum is a sum of one linear map's outputs
    and the per-round collective drops from O(d) to the compressed width.
    """
    sampled = cohort is not None and cohort.is_sampled
    gathering = sampled and cohort.gather
    injecting = fault is not None and fault.injects
    local = _local_caller(local_fn, fault, tau, algorithm)

    def step(w, opt_state, round_key, t, batches_and_mask, eta_l):
        """One server round inside the compiled scan body."""
        tkw = _round_kwargs(algorithm, t)
        local_batches, pad_mask = batches_and_mask
        m_local = pad_mask.shape[0]
        start = jax.lax.axis_index(axis) * m_local
        if not sampled and not injecting:
            deltas = mask_rows(
                local(w, local_batches, eta_l, round_key, start,
                      None, opt_state), pad_mask)
            w_next, aux, opt_state = algorithm.apply_round_sharded(
                round_key, w, deltas, pad_mask, opt_state, axis,
                m_total=m_true, **tkw)
        else:
            if sampled:
                full = cohort.round_mask(round_key, m_true)
                full = jnp.concatenate(
                    [full, jnp.zeros((m_pad - m_true,), jnp.float32)])
                mask = jax.lax.dynamic_slice(full, (start,),
                                             (m_local,)) * pad_mask
            else:
                mask = pad_mask
            if gathering:
                slots, mask, _ = gather_slots(mask,
                                              cohort.resolved_cap(m_local))
                local_batches = gather_rows(local_batches, slots)
                start = start + slots   # (cap,) vector of GLOBAL indices
            if injecting:
                alive, straggler, corrupt = (
                    _pad_slice(v, m_pad, jax.lax.axis_index(axis) * m_local,
                               m_local)
                    for v in fault_masks(fault, round_key, m_true))
                if gathering:
                    alive, straggler, corrupt = gather_fault_rows(
                        slots, alive, straggler, corrupt)
                deltas = local(w, local_batches, eta_l, round_key, start,
                               straggler, opt_state)
                deltas, mask = apply_faults(deltas, mask, alive, corrupt)
            else:
                deltas = mask_rows(
                    local(w, local_batches, eta_l, round_key, start,
                          None, opt_state), mask)
            moments = algorithm.local_moments(round_key, w, deltas, mask,
                                              start, opt_state, **tkw)
            with jax.named_scope(spans.PSUM):
                moments = jax.lax.psum(moments, axis)
            if injecting:
                moments = sanitize_moments(moments)
                moments = _resolve_realized_count(moments, algorithm)
            else:
                moments = _resolve_sampled_count(moments, cohort, algorithm)
            w_next, aux, opt_state = algorithm.apply_from_moments(
                round_key, w, moments, opt_state, **tkw)
        metric = _eval_metric(eval_fn, eval_every, w_next, t)
        outs = (aux.eta_g, metric, aux.eta_naive, aux.eta_target)
        return w_next, opt_state, outs

    return step


def _stream_round_step(algorithm, local_fn, eval_fn,
                       m_true: int, m_pad: int, eval_every: int = 1,
                       cohort: CohortSpec | None = None, axis: str | None = None,
                       fault: FaultSpec | None = None, tau: int = 1):
    """One server round streamed over client chunks (DESIGN.md §12).

    The cohort arrives pre-chunked: every client-batch leaf is
    (n_chunks, chunk_clients, ...) and the weight mask (n_chunks,
    chunk_clients), zero on the rows that pad M up to the chunk grid.  An
    inner ``lax.scan`` walks the chunks; chunk j computes its clients' local
    updates and ``algorithm.local_moments`` on global client indices
    [start + j*c, start + (j+1)*c) and adds the resulting moments pytree
    (SUMS, plus any additive extras — the PrivUnit Σŝ, the adaptive-clip
    below-threshold bit count) into a zero-initialized running carry.  Only
    that O(d) carry and one (c, d) update block are ever live, which is the
    engine's whole point: peak update memory is chunk-sized, not
    cohort-sized.

    ``axis`` is the §9 ``clients`` mesh axis when each SHARD streams its
    slice (``m_pad`` stays the GLOBAL padded cohort so every device derives
    the identical full sampling mask); the accumulated shard moments cross
    devices in the same single post-scan psum the dense sharded engine
    performs.  Count resolution matches the engine the stream replaces:
    sampled rounds go through ``_resolve_sampled_count``, full-participation
    rounds substitute the static true client count (``set_moment_count``)
    exactly as ``apply_round_sharded`` does.

    With a compressed aggregation layer (§16) the inner-scan carry is
    compressed-width (its zero init comes from ``jax.eval_shape`` of the
    chunk moments), so the streamed accumulation and the post-scan psum move
    O(k) floats — every chunk compresses with the identical round-key plan.
    """
    sampled = cohort is not None and cohort.is_sampled
    injecting = fault is not None and fault.injects
    local_call = _local_caller(local_fn, fault, tau, algorithm)

    def step(w, opt_state, round_key, t, batches_and_mask, eta_l):
        """One server round inside the compiled scan body."""
        tkw = _round_kwargs(algorithm, t)
        chunk_batches, chunk_mask = batches_and_mask
        n_chunks, c = chunk_mask.shape
        if axis is None:
            shard_start = 0
        else:
            shard_start = jax.lax.axis_index(axis) * (n_chunks * c)
        if sampled:
            # full participation mask from the replicated round key — the
            # SAME draw as the dense/sharded engines — padded with zeros and
            # sliced to this shard's rows, then laid on the chunk grid
            full = cohort.round_mask(round_key, m_true)
            full = jnp.concatenate(
                [full, jnp.zeros((m_pad - m_true,), jnp.float32)])
            local = jax.lax.dynamic_slice(full, (shard_start,), (n_chunks * c,))
            chunk_mask = chunk_mask * local.reshape(n_chunks, c)
        if injecting:
            # fault draws: same full-cohort-then-slice pattern as the
            # sampling mask, laid on this shard's chunk grid so they can
            # ride the inner scan's xs (inactive classes materialize their
            # inert value — the grid rides the scan either way)
            alive_f, strag_f, corr_f = fault_masks(fault, round_key, m_true)
            grid_len = n_chunks * c

            def grid(v, default: float):
                if v is None:
                    v = jnp.full((m_true,), default, jnp.float32)
                v = jnp.concatenate(
                    [v, jnp.zeros((m_pad - m_true,), jnp.float32)])
                v = jax.lax.dynamic_slice(v, (shard_start,), (grid_len,))
                return v.reshape(n_chunks, c)

            fault_grid = (grid(alive_f, 1.0), grid(strag_f, 0.0),
                          grid(corr_f, 0.0))
        else:
            fault_grid = ()

        def chunk_moments(j, batches_j, mask_j, fault_j):
            """Local training + release moments for chunk ``j`` of the cohort."""
            start = shard_start + j * c
            if injecting:
                alive_j, strag_j, corr_j = fault_j
                deltas = local_call(w, batches_j, eta_l, round_key, start,
                                    strag_j, opt_state)
                deltas, mask_j = apply_faults(deltas, mask_j, alive_j, corr_j)
            else:
                deltas = mask_rows(
                    local_call(w, batches_j, eta_l, round_key, start,
                               None, opt_state), mask_j)
            return algorithm.local_moments(round_key, w, deltas, mask_j,
                                           start, opt_state, **tkw)

        # zero-initialize the running moments from the chunk computation's
        # abstract shape (no FLOPs traced): every field is an additive SUM,
        # so zeros is the correct identity for the accumulation
        row_sds = jax.ShapeDtypeStruct((c,), jnp.float32)
        shapes = jax.eval_shape(
            chunk_moments, jax.ShapeDtypeStruct((), jnp.int32),
            jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                chunk_batches),
            jax.ShapeDtypeStruct((c,), chunk_mask.dtype),
            (row_sds,) * 3 if injecting else ())
        acc0 = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes)

        def body(acc, xs):
            """Scan body: accumulate one chunk's additive moments into the carry."""
            j, batches_j, mask_j, fault_j = xs
            mom = chunk_moments(j, batches_j, mask_j, fault_j)
            return jax.tree_util.tree_map(jnp.add, acc, mom), None

        js = jnp.arange(n_chunks, dtype=jnp.int32)
        moments, _ = jax.lax.scan(
            body, acc0, (js, chunk_batches, chunk_mask, fault_grid))
        if axis is not None:
            with jax.named_scope(spans.PSUM):
                moments = jax.lax.psum(moments, axis)
        if injecting:
            moments = sanitize_moments(moments)
            moments = _resolve_realized_count(moments, algorithm)
        elif sampled:
            moments = _resolve_sampled_count(moments, cohort, algorithm)
        elif getattr(algorithm, "supports_static_count", True):
            # full participation: the accumulated count is exactly m_true;
            # substituting the static constant folds the 1/M normalizations
            # as the dense engine does (same trick as apply_round_sharded)
            moments = set_moment_count(moments, m_true)
        else:
            # weighted aggregation: the count is a weight sum — keep the
            # accumulated traced value, only guard an (impossible here)
            # zero count
            moments = clamp_moment_counts(moments, floor=1e-12)
        w_next, aux, opt_state = algorithm.apply_from_moments(
            round_key, w, moments, opt_state, **tkw)
        metric = _eval_metric(eval_fn, eval_every, w_next, t)
        outs = (aux.eta_g, metric, aux.eta_naive, aux.eta_target)
        return w_next, opt_state, outs

    return step


def _build_stream_chunk_fn(algorithm: ServerAlgorithm, local_fn, eval_fn,
                           donate: bool, unroll: int, stream: StreamSpec,
                           m_true: int, m_pad: int,
                           eval_every: int, cohort: CohortSpec | None,
                           fault: FaultSpec | None, tau: int,
                           tap: bool = False):
    step_round = _stream_round_step(algorithm, local_fn, eval_fn,
                                    m_true, m_pad, eval_every, cohort,
                                    fault=fault, tau=tau)
    tap_ctx = ((m_true, cohort, fault, None, _tap_clip_fn(algorithm),
                _tap_sigma_fn(algorithm))
               if tap else None)

    def chunk(carry, key, ts, chunk_batches, chunk_mask, eta_l):
        """Compiled scan over one chunk of rounds."""
        keys = _fold_round_keys(key, ts)
        body = _scan_body(step_round, (chunk_batches, chunk_mask), eta_l,
                          fault, tap_ctx)
        return jax.lax.scan(body, carry, (keys, ts), unroll=min(unroll, len(ts)))

    return jax.jit(chunk, donate_argnums=(0,) if donate else ())


_cached_stream_chunk_fn = functools.lru_cache(maxsize=32)(_build_stream_chunk_fn)


def _stream_chunk_fn(algorithm: ServerAlgorithm, local_fn, eval_fn,
                     donate: bool, unroll: int, stream: StreamSpec,
                     m_true: int, m_pad: int, eval_every: int = 1,
                     cohort: CohortSpec | None = None,
                     fault: FaultSpec | None = None, tau: int = 1,
                     tap: bool = False):
    """Compiled streaming scan chunk, cached like ``_scan_chunk_fn`` (the
    StreamSpec and padded-cohort geometry join the key; same
    unhashable-algorithm fallback)."""
    try:
        return _cached_stream_chunk_fn(algorithm, local_fn, eval_fn, donate,
                                       unroll, stream, m_true, m_pad,
                                       eval_every, cohort, fault, tau, tap)
    except TypeError:
        return _build_stream_chunk_fn(algorithm, local_fn, eval_fn, donate,
                                      unroll, stream, m_true, m_pad,
                                      eval_every, cohort, fault, tau, tap)


def _build_sharded_stream_chunk_fn(algorithm: ServerAlgorithm, local_fn,
                                   eval_fn, donate: bool, unroll: int,
                                   stream: StreamSpec, mesh, axis: str,
                                   batch_treedef, leaf_ndims,
                                   n_chunks: int, m_true: int, m_pad: int,
                                   eval_every: int, cohort: CohortSpec | None,
                                   fault: FaultSpec | None, tau: int,
                                   tap: bool = False):
    """Each shard streams its own slice of the chunk grid (DESIGN.md §12).

    The pre-chunked leaves are (n_chunks_total, c, ...) with chunks laid out
    so contiguous chunk blocks are contiguous client blocks; sharding the
    leading CHUNK axis over the ``clients`` mesh therefore hands each device
    the same client rows the dense sharded engine would, and the inner
    scan's shard-local moments cross devices in one psum per round.
    """
    step_round = _stream_round_step(algorithm, local_fn, eval_fn,
                                    m_true, m_pad, eval_every, cohort,
                                    axis=axis, fault=fault, tau=tau)
    rules = client_axis_rules(mesh, axis=axis)
    specs = [logical_to_pspec(("clients",) + (None,) * (nd - 1), rules)
             for nd in leaf_ndims]
    batch_specs = jax.tree_util.tree_unflatten(batch_treedef, specs)
    mask_spec = logical_to_pspec(("clients", None), rules,
                                 dims=(n_chunks, stream.chunk_clients))
    tap_ctx = ((m_true, cohort, fault, axis, _tap_clip_fn(algorithm),
                _tap_sigma_fn(algorithm))
               if tap else None)

    def chunk(carry, key, ts, chunk_batches, chunk_mask, eta_l):
        """Compiled scan over one chunk of rounds."""
        keys = _fold_round_keys(key, ts)
        body = _scan_body(step_round, (chunk_batches, chunk_mask), eta_l,
                          fault, tap_ctx)
        return jax.lax.scan(body, carry, (keys, ts), unroll=min(unroll, len(ts)))

    sharded = jax.shard_map(
        chunk, mesh=mesh,
        in_specs=(P(), P(), P(), batch_specs, mask_spec, P()),
        out_specs=P(),
        check_vma=False)  # psum-then-replicated-update, as the dense engine
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


_cached_sharded_stream_chunk_fn = (
    functools.lru_cache(maxsize=32)(_build_sharded_stream_chunk_fn))


def _sharded_stream_chunk_fn(algorithm, local_fn, eval_fn, donate, unroll,
                             stream, mesh, axis, batch_treedef, leaf_ndims,
                             n_chunks, m_true, m_pad, eval_every: int = 1,
                             cohort: CohortSpec | None = None,
                             fault: FaultSpec | None = None, tau: int = 1,
                             tap: bool = False):
    """Compiled sharded+streamed scan chunk, cached like ``_scan_chunk_fn``."""
    try:
        return _cached_sharded_stream_chunk_fn(
            algorithm, local_fn, eval_fn, donate, unroll, stream, mesh, axis,
            batch_treedef, leaf_ndims, n_chunks, m_true, m_pad, eval_every,
            cohort, fault, tau, tap)
    except TypeError:
        return _build_sharded_stream_chunk_fn(
            algorithm, local_fn, eval_fn, donate, unroll, stream, mesh, axis,
            batch_treedef, leaf_ndims, n_chunks, m_true, m_pad, eval_every,
            cohort, fault, tau, tap)


def _gather_stream_round_step(algorithm, local_fn, eval_fn,
                              m_true: int, m_pad: int, chunk_clients: int,
                              eval_every: int = 1,
                              cohort: CohortSpec | None = None,
                              axis: str | None = None,
                              fault: FaultSpec | None = None, tau: int = 1):
    """One sampled round streamed over the GATHERED cohort (DESIGN.md §14).

    The sparse × streaming composition: the cohort arrives UN-chunked (each
    shard holds its (m_local, ...) slice plus the padding mask), the round
    packs the participation mask into a static slot table as the dense-gather
    engines do, and the §12 inner scan then walks the slot table — not the
    cohort — in ``chunk_clients``-sized chunks, gathering each chunk's client
    rows by slot right before its local training.  Peak update memory stays
    O(chunk·d) AND the round's work is O(cap·d) instead of O(M·d): the inner
    scan runs ceil(cap / c) steps, not ceil(M / c).

    Moments key by the slots' GLOBAL indices (``shard_start + slot``), fault
    rows gather through the same slots, and count resolution matches the
    dense sampled engines — so gather × stream × shard × fault all reproduce
    the dense sampled release at rtol 1e-5.

    Compressed aggregation layers (§16) compose transparently: each gathered
    chunk's moments carry a compressed-width ``sum_c`` (same round-key plan
    on every chunk and shard), so a q-sampled round's collective is O(k)
    while its local-training work stays O(cap·d).
    """
    injecting = fault is not None and fault.injects
    local_call = _local_caller(local_fn, fault, tau, algorithm)

    def step(w, opt_state, round_key, t, batches_and_mask, eta_l):
        """One server round inside the compiled scan body."""
        tkw = _round_kwargs(algorithm, t)
        local_batches, pad_mask = batches_and_mask
        m_local = pad_mask.shape[0]
        shard_start = (0 if axis is None
                       else jax.lax.axis_index(axis) * m_local)
        full = cohort.round_mask(round_key, m_true)
        full = jnp.concatenate(
            [full, jnp.zeros((m_pad - m_true,), jnp.float32)])
        mask = jax.lax.dynamic_slice(full, (shard_start,),
                                     (m_local,)) * pad_mask
        # static slot grid: cap rounded up to the chunk size, so the slot
        # table reshapes onto the (n_chunks, c) inner-scan grid exactly as
        # chunk_cohort lays out the dense stream's clients
        cap = cohort.resolved_cap(m_local)
        c = min(chunk_clients, cap)
        n_chunks = -(-cap // c)
        slots, slot_mask, _ = gather_slots(mask, n_chunks * c)
        slot_grid = slots.reshape(n_chunks, c)
        mask_grid = slot_mask.reshape(n_chunks, c)
        if injecting:
            alive_f, strag_f, corr_f = (
                _pad_slice(v, m_pad, shard_start, m_local)
                for v in fault_masks(fault, round_key, m_true))
            alive_f, strag_f, corr_f = gather_fault_rows(
                slots, alive_f, strag_f, corr_f)

            def fgrid(v, default: float):
                if v is None:
                    v = jnp.full((slots.shape[0],), default, jnp.float32)
                return v.reshape(n_chunks, c)

            fault_grid = (fgrid(alive_f, 1.0), fgrid(strag_f, 0.0),
                          fgrid(corr_f, 0.0))
        else:
            fault_grid = ()

        def chunk_moments(slots_j, mask_j, fault_j):
            """Gather + local training + release moments for one slot chunk."""
            batches_j = gather_rows(local_batches, slots_j)
            gidx = shard_start + slots_j
            if injecting:
                alive_j, strag_j, corr_j = fault_j
                deltas = local_call(w, batches_j, eta_l, round_key, gidx,
                                    strag_j, opt_state)
                deltas, mask_j = apply_faults(deltas, mask_j, alive_j, corr_j)
            else:
                deltas = mask_rows(
                    local_call(w, batches_j, eta_l, round_key, gidx,
                               None, opt_state), mask_j)
            return algorithm.local_moments(round_key, w, deltas, mask_j,
                                           gidx, opt_state, **tkw)

        row_sds = jax.ShapeDtypeStruct((c,), jnp.float32)
        shapes = jax.eval_shape(
            chunk_moments, jax.ShapeDtypeStruct((c,), jnp.int32),
            jax.ShapeDtypeStruct((c,), jnp.float32),
            (row_sds,) * 3 if injecting else ())
        acc0 = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes)

        def body(acc, xs):
            """Scan body: accumulate one chunk's additive moments into the carry."""
            slots_j, mask_j, fault_j = xs
            mom = chunk_moments(slots_j, mask_j, fault_j)
            return jax.tree_util.tree_map(jnp.add, acc, mom), None

        moments, _ = jax.lax.scan(body, acc0,
                                  (slot_grid, mask_grid, fault_grid))
        if axis is not None:
            with jax.named_scope(spans.PSUM):
                moments = jax.lax.psum(moments, axis)
        if injecting:
            moments = sanitize_moments(moments)
            moments = _resolve_realized_count(moments, algorithm)
        else:
            moments = _resolve_sampled_count(moments, cohort, algorithm)
        w_next, aux, opt_state = algorithm.apply_from_moments(
            round_key, w, moments, opt_state, **tkw)
        metric = _eval_metric(eval_fn, eval_every, w_next, t)
        outs = (aux.eta_g, metric, aux.eta_naive, aux.eta_target)
        return w_next, opt_state, outs

    return step


def _build_gather_stream_chunk_fn(algorithm: ServerAlgorithm, local_fn,
                                  eval_fn, donate: bool, unroll: int,
                                  chunk_clients: int, m_true: int, m_pad: int,
                                  eval_every: int, cohort: CohortSpec | None,
                                  fault: FaultSpec | None, tau: int,
                                  tap: bool = False):
    step_round = _gather_stream_round_step(algorithm, local_fn, eval_fn,
                                           m_true, m_pad, chunk_clients,
                                           eval_every, cohort,
                                           fault=fault, tau=tau)
    tap_ctx = ((m_true, cohort, fault, None, _tap_clip_fn(algorithm),
                _tap_sigma_fn(algorithm))
               if tap else None)

    def chunk(carry, key, ts, local_batches, pad_mask, eta_l):
        """Compiled scan over one chunk of rounds."""
        keys = _fold_round_keys(key, ts)
        body = _scan_body(step_round, (local_batches, pad_mask), eta_l, fault,
                          tap_ctx)
        return jax.lax.scan(body, carry, (keys, ts), unroll=min(unroll, len(ts)))

    return jax.jit(chunk, donate_argnums=(0,) if donate else ())


_cached_gather_stream_chunk_fn = (
    functools.lru_cache(maxsize=32)(_build_gather_stream_chunk_fn))


def _gather_stream_chunk_fn(algorithm: ServerAlgorithm, local_fn, eval_fn,
                            donate: bool, unroll: int, chunk_clients: int,
                            m_true: int, m_pad: int, eval_every: int = 1,
                            cohort: CohortSpec | None = None,
                            fault: FaultSpec | None = None, tau: int = 1,
                            tap: bool = False):
    """Compiled gather-stream scan chunk, cached like ``_scan_chunk_fn``."""
    try:
        return _cached_gather_stream_chunk_fn(
            algorithm, local_fn, eval_fn, donate, unroll, chunk_clients,
            m_true, m_pad, eval_every, cohort, fault, tau, tap)
    except TypeError:
        return _build_gather_stream_chunk_fn(
            algorithm, local_fn, eval_fn, donate, unroll, chunk_clients,
            m_true, m_pad, eval_every, cohort, fault, tau, tap)


def _build_sharded_gather_stream_chunk_fn(algorithm: ServerAlgorithm,
                                          local_fn, eval_fn, donate: bool,
                                          unroll: int, chunk_clients: int,
                                          mesh, axis: str, batch_treedef,
                                          leaf_ndims, mask_len: int,
                                          m_true: int,
                                          eval_every: int,
                                          cohort: CohortSpec | None,
                                          fault: FaultSpec | None, tau: int,
                                          tap: bool = False):
    """Each shard gather-streams its own cohort slice (§9 × §14): the
    UN-chunked client leaves shard over the ``clients`` mesh exactly as the
    dense sharded engine's, each device packs its slice's slot table, and
    the accumulated shard moments cross devices in one psum per round."""
    step_round = _gather_stream_round_step(algorithm, local_fn, eval_fn,
                                           m_true, mask_len, chunk_clients,
                                           eval_every, cohort, axis=axis,
                                           fault=fault, tau=tau)
    rules = client_axis_rules(mesh, axis=axis)
    batch_specs, mask_spec = _client_batch_specs(batch_treedef, leaf_ndims,
                                                 mask_len, rules)
    tap_ctx = ((m_true, cohort, fault, axis, _tap_clip_fn(algorithm),
                _tap_sigma_fn(algorithm))
               if tap else None)

    def chunk(carry, key, ts, local_batches, pad_mask, eta_l):
        """Compiled scan over one chunk of rounds."""
        keys = _fold_round_keys(key, ts)
        body = _scan_body(step_round, (local_batches, pad_mask), eta_l, fault,
                          tap_ctx)
        return jax.lax.scan(body, carry, (keys, ts), unroll=min(unroll, len(ts)))

    sharded = jax.shard_map(
        chunk, mesh=mesh,
        in_specs=(P(), P(), P(), batch_specs, mask_spec, P()),
        out_specs=P(),
        check_vma=False)  # psum-then-replicated-update, as the dense engine
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


_cached_sharded_gather_stream_chunk_fn = (
    functools.lru_cache(maxsize=32)(_build_sharded_gather_stream_chunk_fn))


def _sharded_gather_stream_chunk_fn(algorithm, local_fn, eval_fn, donate,
                                    unroll, chunk_clients, mesh, axis,
                                    batch_treedef, leaf_ndims, mask_len,
                                    m_true, eval_every: int = 1,
                                    cohort: CohortSpec | None = None,
                                    fault: FaultSpec | None = None,
                                    tau: int = 1, tap: bool = False):
    """Compiled sharded gather-stream chunk, cached like ``_scan_chunk_fn``."""
    try:
        return _cached_sharded_gather_stream_chunk_fn(
            algorithm, local_fn, eval_fn, donate, unroll, chunk_clients, mesh,
            axis, batch_treedef, leaf_ndims, mask_len, m_true, eval_every,
            cohort, fault, tau, tap)
    except TypeError:
        return _build_sharded_gather_stream_chunk_fn(
            algorithm, local_fn, eval_fn, donate, unroll, chunk_clients, mesh,
            axis, batch_treedef, leaf_ndims, mask_len, m_true, eval_every,
            cohort, fault, tau, tap)


def _build_host_moments_fn(algorithm: ServerAlgorithm, local_fn, data):
    """Per-chunk moments program of the host-resident driver (DESIGN.md §14).

    One compiled function per session, applied to every staged chunk of every
    round: local training + release moments for the chunk's rows, keyed by
    the chunk's GLOBAL client indices (a (c,) vector — slot indices on the
    gather path, ``j*c + arange(c)`` on the dense path; both are exactly the
    indices the device-resident stream engine derives, so the host-staged
    release is the identical computation).  ``data`` (the frozen DataSpec) is
    part of the compile-cache key, as for every other spec.
    """
    del data  # cache key only: the compiled program is data-location blind
    local = _local_caller(local_fn, None, 1, algorithm)

    def chunk_moments(w, opt_state, round_key, batches_j, mask_j, gidx_j,
                      eta_l, t):
        """Local training + release moments for one host-staged chunk."""
        deltas = mask_rows(
            local(w, batches_j, eta_l, round_key, gidx_j, None, opt_state),
            mask_j)
        return algorithm.local_moments(round_key, w, deltas, mask_j,
                                       gidx_j, opt_state,
                                       **_round_kwargs(algorithm, t))

    return jax.jit(chunk_moments)


_cached_host_moments_fn = functools.lru_cache(maxsize=32)(_build_host_moments_fn)


def _host_moments_fn(algorithm: ServerAlgorithm, local_fn, data):
    """Compiled host-driver chunk program, cached like ``_scan_chunk_fn``."""
    try:
        return _cached_host_moments_fn(algorithm, local_fn, data)
    except TypeError:
        return _build_host_moments_fn(algorithm, local_fn, data)


def _build_host_finalize_fn(algorithm: ServerAlgorithm, eval_fn,
                            eval_every: int, cohort: CohortSpec | None,
                            m_true: int):
    """Per-round tail of the host-resident driver: count resolution +
    server update + eval + iterate-tail roll — exactly the post-inner-scan
    logic of ``_stream_round_step`` and the tail semantics of ``_scan_body``,
    so a host-staged run reproduces the device-resident stream engine."""
    sampled = cohort is not None and cohort.is_sampled

    def finalize(w, opt_state, tail, round_key, t, moments):
        """Resolve counts, apply the server update, roll the iterate tail."""
        if sampled:
            moments = _resolve_sampled_count(moments, cohort, algorithm)
        elif getattr(algorithm, "supports_static_count", True):
            moments = set_moment_count(moments, m_true)
        else:
            moments = clamp_moment_counts(moments, floor=1e-12)
        w_next, aux, opt_state = algorithm.apply_from_moments(
            round_key, w, moments, opt_state,
            **_round_kwargs(algorithm, t))
        metric = _eval_metric(eval_fn, eval_every, w_next, t)
        tail = jnp.concatenate([tail[1:], w_next[None]], axis=0)
        outs = (aux.eta_g, metric, aux.eta_naive, aux.eta_target)
        return w_next, opt_state, tail, outs

    return jax.jit(finalize)


_cached_host_finalize_fn = (
    functools.lru_cache(maxsize=32)(_build_host_finalize_fn))


def _host_finalize_fn(algorithm: ServerAlgorithm, eval_fn,
                      eval_every: int = 1, cohort: CohortSpec | None = None,
                      m_true: int = 1):
    """Compiled host-driver round finalizer, cached like ``_scan_chunk_fn``."""
    try:
        return _cached_host_finalize_fn(algorithm, eval_fn, eval_every,
                                        cohort, m_true)
    except TypeError:
        return _build_host_finalize_fn(algorithm, eval_fn, eval_every,
                                       cohort, m_true)


@jax.jit
def _host_add_moments(acc, mom):
    """Accumulate one chunk's additive moments (the inner-scan ``jnp.add``)."""
    return jax.tree_util.tree_map(jnp.add, acc, mom)


def _client_batch_specs(treedef, leaf_ndims, mask_len, rules):
    """PartitionSpecs for the (padded) client-batch pytree + mask, derived
    through the logical-axis layer: every leaf is ("clients", None, ...)."""
    specs = [logical_to_pspec(("clients",) + (None,) * (nd - 1), rules)
             for nd in leaf_ndims]
    mask_spec = logical_to_pspec(("clients",), rules, dims=(mask_len,))
    return jax.tree_util.tree_unflatten(treedef, specs), mask_spec


def _fold_round_keys(key, ts):
    """Per-round keys, derived identically by every engine."""
    return jax.vmap(lambda t: jax.random.fold_in(key, t))(ts)


# ---------------------------------------------------------------------------
# Engine tap (DESIGN.md §15): per-round diagnostics streamed to the host
# ---------------------------------------------------------------------------

def _tap_clip_fn(algorithm):
    """Best-effort clip threshold C for the telemetry payload.

    Resolution order mirrors where composed vs legacy algorithms keep the
    threshold: the GlobalStep's ``clip_override`` (adaptive clipping carries
    it in opt_state), a bare ``opt_state.clip`` (the legacy adaptive-clip
    monolith), then the static ``clip_norm`` on the algorithm or its
    mechanism.  NaN when the algorithm has no clipping at all — the host
    omits the field.  Runs at TRACE time inside the tap, never on the
    non-tap program.
    """

    def clip_of(opt_state):
        # an error-feedback compressed composition (§16) wraps the step's
        # carry in a CompressionCarry; the clip threshold lives on .inner
        opt_state = getattr(opt_state, "inner", opt_state)
        step = getattr(algorithm, "step", None)
        if step is not None:
            try:
                c = step.clip_override(opt_state)
                if c is not None:
                    return jnp.float32(c)
            except Exception:
                pass
        c = getattr(opt_state, "clip", None)
        if c is not None:
            return jnp.float32(c)
        for holder in (algorithm, getattr(algorithm, "mechanism", None)):
            c = getattr(holder, "clip_norm", None)
            if c is not None:
                return jnp.float32(c)
        return jnp.float32(jnp.nan)

    return clip_of


def _tap_sigma_fn(algorithm):
    """Best-effort per-round noise std sigma(t) for the telemetry payload
    (DESIGN.md §15/§17).

    A round-indexed NoiseSchedule emits its traced sigma(t); a fixed-sigma
    algorithm (monolith or composition — ``sigma`` forwards through the
    composed ``__getattr__``, a constant schedule forwards to its inner
    mechanism) emits the constant; NaN when the release has no shared noise
    std at all (NoPrivacy, PrivUnit's pure-DP release, heterogeneous
    per-client sigmas) — the host omits the field.  Trace-time only, like
    ``_tap_clip_fn``.
    """
    mech = getattr(algorithm, "mechanism", None)
    if mech is not None and getattr(mech, "is_round_indexed", False):
        return lambda t: jnp.float32(mech._sigma_at(t))
    sigma = getattr(algorithm, "sigma", None)
    if isinstance(sigma, (int, float)):
        return lambda t: jnp.float32(sigma)
    return lambda t: jnp.float32(jnp.nan)


def _tap_emit(tap_ctx, round_key, t, opt_state, outs, fault_t):
    """Emit one round's diagnostics to the host tracker (DESIGN.md §15).

    Only ever traced when a tracker is attached (``tap=True`` builders) —
    the default program contains no callback at all.  All diagnostics
    derive from REPLICATED draws (the cohort mask and fault vectors come
    from the replicated round key), so the tap needs nothing from the
    engines' per-shard internals; duplicating the mask draw here costs one
    extra O(M) bernoulli on tap runs only and keeps the emission math
    read-only — the engine's own computation is untouched, which is what
    makes tap-on results bit-identical to tap-off.

    Ordering (§15): non-sharded engines emit ``ordered=True`` (the scan
    delivers rounds in order); ``shard_map`` engines emit ``ordered=False``
    — EVERY shard fires the callback, so the payload carries ``axis_index``
    and the host drops shard != 0 and reorders by round index.  Ordered
    callbacks inside shard_map are not used: they are unreliable on this
    jax version (see DESIGN.md §15).
    """
    from repro.telemetry import tap as _tap

    with jax.named_scope(spans.TAP):
        m_true, cohort, fault, axis, clip_fn, sigma_fn = tap_ctx
        eta, metric, naive, target = outs
        sampled = cohort is not None and cohort.is_sampled
        participants = (jnp.sum(cohort.round_mask(round_key, m_true))
                        if sampled else jnp.float32(m_true))
        if fault is not None and fault.injects:
            alive, strag, corr = fault_masks(fault, round_key, m_true)
            ones = jnp.ones((m_true,), jnp.float32)
            zeros = jnp.zeros((m_true,), jnp.float32)
            alive = ones if alive is None else alive
            strag = zeros if strag is None else strag
            corr = zeros if corr is None else corr
            mask = (cohort.round_mask(round_key, m_true) if sampled else ones)
            realized = jnp.sum(mask * alive * (1.0 - corr))
            dropped = jnp.sum(mask * (1.0 - alive))
            stragglers = jnp.sum(mask * alive * strag)
            corrupt = jnp.sum(mask * alive * corr)
        else:
            realized = participants
            dropped = stragglers = corrupt = jnp.float32(0.0)
        payload = jnp.stack([
            jnp.float32(eta), jnp.float32(naive), jnp.float32(target),
            jnp.float32(metric), clip_fn(opt_state), participants, realized,
            dropped, stragglers, corrupt, jnp.float32(fault_t), sigma_fn(t)])
        shard = jnp.int32(0) if axis is None else jax.lax.axis_index(axis)
        io_callback(_tap.device_emit, None, t, shard, payload,
                    ordered=(axis is None))


def _scan_body(step_round, client_batches, eta_l,
               fault: FaultSpec | None = None, tap_ctx=None):
    """The one scan body every engine compiles — the tail-carry and key
    semantics the bit-exactness tests pin down.  xs is (round_keys, ts): the
    round index rides along for eval cadence and diagnostics.

    With an armed watchdog (``FaultSpec(watchdog=True)``, DESIGN.md §13) the
    carry grows a fourth element ``fault_t`` (int32, -1 while healthy): after
    each round the body checks the global model for non-finite coordinates
    and the step size for NaN / explosion past ``eta_max``; a tripped round
    is NOT committed (the carry rolls back to the pre-round state, so
    recovery resumes from the last healthy iterate), ``fault_t`` records the
    faulting GLOBAL round index, and every remaining round in the chunk is
    frozen behind ``lax.cond`` — no local training, NaN histories.

    ``tap_ctx`` (DESIGN.md §15) arms the telemetry tap: one ``_tap_emit``
    per round, placed AFTER the round's watchdog/rollback resolution so the
    emitted fault state is the committed one.  The emission only reads —
    every carry value flows through it untouched — so tap-on results stay
    bit-identical to tap-off.
    """
    watchdog = fault is not None and fault.watchdog

    def body(carry, key_t):
        """Round-scan body: one server round, w_next appended to the iterate tail."""
        round_key, t = key_t
        if not watchdog:
            w, opt_state, tail = carry
            w_next, opt_next, outs = step_round(
                w, opt_state, round_key, t, client_batches, eta_l)
            if tap_ctx is not None:
                _tap_emit(tap_ctx, round_key, t, opt_state, outs,
                          jnp.int32(-1))
            tail = jnp.concatenate([tail[1:], w_next[None]], axis=0)
            return (w_next, opt_next, tail), outs

        w, opt_state, tail, fault_t = carry
        tripped = fault_t >= 0

        def frozen(operand):
            """Post-trip round: carry passes through, histories record NaN."""
            w, opt_state, tail = operand
            nanf = jnp.float32(jnp.nan)
            return w, opt_state, tail, (nanf, nanf, nanf, nanf)

        def live(operand):
            """Healthy round: the exact computation the unwatched body runs."""
            w, opt_state, tail = operand
            w_next, opt_next, outs = step_round(
                w, opt_state, round_key, t, client_batches, eta_l)
            tail_next = jnp.concatenate([tail[1:], w_next[None]], axis=0)
            return w_next, opt_next, tail_next, outs

        w_next, opt_next, tail_next, outs = jax.lax.cond(
            tripped, frozen, live, (w, opt_state, tail))
        eta = outs[0]
        healthy = (jnp.all(jnp.isfinite(w_next))
                   & jnp.isfinite(eta)
                   & (eta <= jnp.float32(fault.eta_max)))
        bad = jnp.logical_and(~tripped, ~healthy)
        # the faulting round's update is NOT committed — roll this round's
        # carry back so recovery resumes from the last healthy iterate
        w_next = jnp.where(bad, w, w_next)
        opt_next = jax.tree_util.tree_map(
            lambda a, b: jnp.where(bad, a, b), opt_state, opt_next)
        tail_next = jnp.where(bad, tail, tail_next)
        fault_t = jnp.where(bad, t, fault_t)
        if tap_ctx is not None:
            # post-resolution emission: the host sees the committed fault
            # state — the tripping round reports fault_t == t (it executed,
            # so it charges the ledger); frozen rounds report t > fault_t
            _tap_emit(tap_ctx, round_key, t, opt_state, outs, fault_t)
        return (w_next, opt_next, tail_next, fault_t), outs

    return body


def _build_scan_chunk_fn(algorithm: ServerAlgorithm, local_fn, eval_fn,
                         donate: bool, unroll: int,
                         eval_every: int, cohort: CohortSpec | None,
                         fault: FaultSpec | None, tau: int,
                         tap: bool = False):
    step_round = _round_step(algorithm, local_fn, eval_fn, eval_every, cohort,
                             fault, tau)

    def chunk(carry, key, ts, client_batches, eta_l):
        """Compiled scan over one chunk of rounds."""
        keys = _fold_round_keys(key, ts)
        tap_ctx = None
        if tap:
            m = jax.tree_util.tree_leaves(client_batches)[0].shape[0]
            tap_ctx = (m, cohort, fault, None, _tap_clip_fn(algorithm),
                       _tap_sigma_fn(algorithm))
        body = _scan_body(step_round, client_batches, eta_l, fault, tap_ctx)
        return jax.lax.scan(body, carry, (keys, ts), unroll=min(unroll, len(ts)))

    return jax.jit(chunk, donate_argnums=(0,) if donate else ())


_cached_scan_chunk_fn = functools.lru_cache(maxsize=32)(_build_scan_chunk_fn)


def _scan_chunk_fn(algorithm: ServerAlgorithm, local_fn, eval_fn,
                   donate: bool, unroll: int, eval_every: int = 1,
                   cohort: CohortSpec | None = None,
                   fault: FaultSpec | None = None, tau: int = 1,
                   tap: bool = False):
    """Compiled scan over a chunk of rounds, cached by configuration.

    The cache key is (algorithm config, local-trainer/eval *identity*,
    donation, unroll, eval cadence, cohort spec, §15 tap on/off — the ONLY
    telemetry bit that may enter any cache key); round count, eta_l, and all
    array shapes are traced, so any two calls with equal configuration share
    one compiled program per chunk length.  For the cache to hit, callers
    must hold onto their local/eval closures — a fresh closure per call
    retraces (exactly the legacy cost, no worse); ``FederatedSession`` builds
    its ``local_fn`` once (binding loss_fn, LocalSpec and tau) and owns it,
    so repeated ``run`` calls on one session always hit.  ``unroll`` packs
    that many rounds per loop trip — XLA:CPU penalizes ops inside while-loop
    bodies, and a small unroll claws most of it back for ~proportional
    compile time (results are bit-identical).

    Algorithms with unhashable fields (arrays, user-defined non-frozen
    dataclasses) can't be cache keys; they get an uncached build — again the
    legacy per-call-retrace cost, never an error.
    """
    try:
        return _cached_scan_chunk_fn(algorithm, local_fn, eval_fn,
                                     donate, unroll, eval_every, cohort,
                                     fault, tau, tap)
    except TypeError:
        return _build_scan_chunk_fn(algorithm, local_fn, eval_fn,
                                    donate, unroll, eval_every, cohort,
                                    fault, tau, tap)


def _build_sharded_chunk_fn(algorithm: ServerAlgorithm, local_fn, eval_fn,
                            donate: bool, unroll: int,
                            mesh, axis: str, batch_treedef, leaf_ndims,
                            mask_len: int, m_true: int,
                            eval_every: int, cohort: CohortSpec | None,
                            fault: FaultSpec | None, tau: int,
                            tap: bool = False):
    step_round = _sharded_round_step(algorithm, local_fn, eval_fn, axis,
                                     m_true, mask_len, eval_every, cohort,
                                     fault, tau)
    rules = client_axis_rules(mesh, axis=axis)
    batch_specs, mask_spec = _client_batch_specs(batch_treedef, leaf_ndims,
                                                 mask_len, rules)
    tap_ctx = ((m_true, cohort, fault, axis, _tap_clip_fn(algorithm),
                _tap_sigma_fn(algorithm))
               if tap else None)

    def chunk(carry, key, ts, local_batches, mask, eta_l):
        """Compiled scan over one chunk of rounds."""
        keys = _fold_round_keys(key, ts)
        body = _scan_body(step_round, (local_batches, mask), eta_l, fault,
                          tap_ctx)
        return jax.lax.scan(body, carry, (keys, ts), unroll=min(unroll, len(ts)))

    sharded = jax.shard_map(
        chunk, mesh=mesh,
        in_specs=(P(), P(), P(), batch_specs, mask_spec, P()),
        out_specs=P(),
        check_vma=False)  # psum-then-replicated-update; vma checker can't see it
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


_cached_sharded_chunk_fn = functools.lru_cache(maxsize=32)(_build_sharded_chunk_fn)


def _sharded_chunk_fn(algorithm, local_fn, eval_fn, donate, unroll,
                      mesh, axis, batch_treedef, leaf_ndims, mask_len, m_true,
                      eval_every: int = 1, cohort: CohortSpec | None = None,
                      fault: FaultSpec | None = None, tau: int = 1,
                      tap: bool = False):
    """Compiled shard_mapped scan chunk, cached like `_scan_chunk_fn` (the
    mesh, client-batch treedef and leaf ranks join the key; same unhashable-
    algorithm fallback)."""
    try:
        return _cached_sharded_chunk_fn(algorithm, local_fn, eval_fn,
                                        donate, unroll, mesh, axis,
                                        batch_treedef, leaf_ndims, mask_len,
                                        m_true, eval_every, cohort,
                                        fault, tau, tap)
    except TypeError:
        return _build_sharded_chunk_fn(algorithm, local_fn, eval_fn,
                                       donate, unroll, mesh, axis,
                                       batch_treedef, leaf_ndims, mask_len,
                                       m_true, eval_every, cohort,
                                       fault, tau, tap)


def _build_batched_run_fn(algorithm: ServerAlgorithm, local_fn, eval_fn,
                          tail_n: int, batched_w0: bool,
                          batched_data: bool, eval_every: int,
                          cohort: CohortSpec | None):
    step_round = _round_step(algorithm, local_fn, eval_fn, eval_every, cohort)

    def run_one(w0, key, client_batches, eta_l, ts):
        """Full single-seed run: scan all rounds and average the iterate tail."""
        keys = _fold_round_keys(key, ts)
        carry = (w0, algorithm.init_state(w0),
                 jnp.zeros((tail_n,) + w0.shape, w0.dtype))
        body = _scan_body(step_round, client_batches, eta_l)
        (w, _, tail), outs = jax.lax.scan(body, carry, (keys, ts))
        return (jnp.mean(tail, axis=0), w) + outs

    in_axes = (0 if batched_w0 else None, 0, 0 if batched_data else None,
               None, None)
    return jax.jit(jax.vmap(run_one, in_axes=in_axes))


_cached_batched_run_fn = functools.lru_cache(maxsize=32)(_build_batched_run_fn)


def _build_sharded_batched_run_fn(algorithm: ServerAlgorithm, local_fn, eval_fn,
                                  tail_n: int, batched_w0: bool,
                                  batched_data: bool, mesh, axis: str,
                                  batch_treedef, leaf_ndims, mask_len: int,
                                  m_true: int, eval_every: int,
                                  cohort: CohortSpec | None):
    """Seeds vmapped INSIDE shard_map: every device runs all S seeds over its
    own client slice, so one program serves the whole sweep sharded."""
    step_round = _sharded_round_step(algorithm, local_fn, eval_fn, axis,
                                     m_true, mask_len, eval_every, cohort)
    rules = client_axis_rules(mesh, axis=axis)
    # with batched_data the seed axis leads and `clients` moves to axis 1
    names = [(None, "clients") if batched_data else ("clients",)] * len(leaf_ndims)
    specs = [logical_to_pspec(tuple(n) + (None,) * (nd - len(n)), rules)
             for n, nd in zip(names, leaf_ndims)]
    batch_specs = jax.tree_util.tree_unflatten(batch_treedef, specs)
    mask_spec = logical_to_pspec(("clients",), rules, dims=(mask_len,))

    def run_one(w0, key, local_batches, mask, eta_l, ts):
        """Full single-seed run: scan all rounds and average the iterate tail."""
        keys = _fold_round_keys(key, ts)
        carry = (w0, algorithm.init_state(w0),
                 jnp.zeros((tail_n,) + w0.shape, w0.dtype))
        body = _scan_body(step_round, (local_batches, mask), eta_l)
        (w, _, tail), outs = jax.lax.scan(body, carry, (keys, ts))
        return (jnp.mean(tail, axis=0), w) + outs

    def batched(w0, keys, local_batches, mask, eta_l, ts):
        """Vmap ``run_one`` over the seed axis inside the shard."""
        in_axes = (0 if batched_w0 else None, 0, 0 if batched_data else None,
                   None, None, None)
        return jax.vmap(run_one, in_axes=in_axes)(
            w0, keys, local_batches, mask, eta_l, ts)

    sharded = jax.shard_map(
        batched, mesh=mesh,
        in_specs=(P(), P(), batch_specs, mask_spec, P(), P()),
        out_specs=P(),
        check_vma=False)
    return jax.jit(sharded)


_cached_sharded_batched_run_fn = (
    functools.lru_cache(maxsize=32)(_build_sharded_batched_run_fn))


def _batched_run_fn(algorithm: ServerAlgorithm, local_fn, eval_fn,
                    tail_n: int, batched_w0: bool, batched_data: bool,
                    eval_every: int = 1, cohort: CohortSpec | None = None):
    """vmapped-over-seeds full run (single scan, no chunking); cached with
    the same hashability fallback as `_scan_chunk_fn`."""
    try:
        return _cached_batched_run_fn(algorithm, local_fn, eval_fn,
                                      tail_n, batched_w0, batched_data,
                                      eval_every, cohort)
    except TypeError:
        return _build_batched_run_fn(algorithm, local_fn, eval_fn,
                                     tail_n, batched_w0, batched_data,
                                     eval_every, cohort)


def _sharded_batched_fn(algorithm, local_fn, eval_fn, tail_n, batched_w0,
                        batched_data, mesh, axis, batch_treedef, leaf_ndims,
                        mask_len, m_true, eval_every: int = 1,
                        cohort: CohortSpec | None = None):
    try:
        return _cached_sharded_batched_run_fn(
            algorithm, local_fn, eval_fn, tail_n, batched_w0, batched_data,
            mesh, axis, batch_treedef, leaf_ndims, mask_len, m_true,
            eval_every, cohort)
    except TypeError:
        return _build_sharded_batched_run_fn(
            algorithm, local_fn, eval_fn, tail_n, batched_w0, batched_data,
            mesh, axis, batch_treedef, leaf_ndims, mask_len, m_true,
            eval_every, cohort)


def _run_eager(algorithm, local_fn, w0, client_batches, *, rounds, eta_l,
               key, eval_fn, avg_last, eval_every: int = 1,
               cohort: CohortSpec | None = None,
               fault: FaultSpec | None = None, tau: int = 1,
               tap: bool = False):
    """Legacy engine: one jitted XLA program per round, dispatched from a
    Python loop (re-traced per call — kept as the e7 throughput baseline).

    The divergence watchdog runs HOST-side here (the loop is already on the
    host): a tripped round is not committed, the remaining rounds are
    skipped with NaN histories, and ``RunResult.fault_round`` records the
    faulting round — the same semantics the compiled scan's in-carry
    watchdog produces (DESIGN.md §13).

    The §15 tap emits from inside the jitted round (ordered io_callback —
    one program per round, dispatched in order).  The host-side watchdog
    runs AFTER the emission, so the tripping round is reported (it executed)
    and skipped rounds are simply never emitted — no frozen-round events,
    unlike the in-scan watchdog whose frozen rounds still flow through the
    scan body.
    """
    step_round = _round_step(algorithm, local_fn, eval_fn, eval_every, cohort,
                             fault, tau)
    watchdog = fault is not None and fault.watchdog
    tap_ctx = None
    if tap:
        m = jax.tree_util.tree_leaves(client_batches)[0].shape[0]
        tap_ctx = (m, cohort, fault, None, _tap_clip_fn(algorithm),
                       _tap_sigma_fn(algorithm))

    def one_round(w, opt_state, round_key, t):
        """One jitted round dispatched from the Python loop."""
        out = step_round(w, opt_state, round_key, t, client_batches, eta_l)
        if tap_ctx is not None:
            _tap_emit(tap_ctx, round_key, t, opt_state, out[2], jnp.int32(-1))
        return out

    round_jit = jax.jit(one_round)

    w = w0
    opt_state = algorithm.init_state(w0)
    tail: list[jax.Array] = []
    etas, metrics, naives, targets = [], [], [], []
    fault_round = None
    for t in range(rounds):
        w_next, opt_next, (eta, metric, naive, target) = round_jit(
            w, opt_state, jax.random.fold_in(key, t), jnp.int32(t))
        etas.append(eta)
        metrics.append(metric)
        naives.append(naive)
        targets.append(target)
        if watchdog:
            eta_host = float(jax.device_get(eta))
            healthy = (bool(jax.device_get(jnp.all(jnp.isfinite(w_next))))
                       and eta_host == eta_host  # not NaN
                       and eta_host <= fault.eta_max)
            if not healthy:
                fault_round = t
                nanf = jnp.float32(jnp.nan)
                for _ in range(rounds - t - 1):
                    etas.append(nanf)
                    metrics.append(nanf)
                    naives.append(nanf)
                    targets.append(nanf)
                break
        w, opt_state = w_next, opt_next
        tail.append(w)
        if len(tail) > avg_last:
            tail.pop(0)

    if not tail:  # watchdog tripped on round 0: w0 is the last healthy iterate
        tail = [w]
    final_w = jnp.mean(jnp.stack(tail), axis=0)
    return RunResult(
        final_w=final_w,
        last_w=w,
        eta_history=jnp.stack(etas),
        metric_history=jnp.stack(metrics),
        eta_naive_history=jnp.stack(naives),
        eta_target_history=jnp.stack(targets),
        fault_round=fault_round,
    )


# ---------------------------------------------------------------------------
# Deprecated kwargs-style entry points (shims over FederatedSession)
# ---------------------------------------------------------------------------

_deprecation_warned = False


def _warn_deprecated(name: str) -> None:
    """One DeprecationWarning per process — the shims stay quiet afterwards."""
    global _deprecation_warned
    if not _deprecation_warned:
        _deprecation_warned = True
        warnings.warn(
            f"{name} is deprecated; build a repro.fedsim.FederatedSession "
            "with TrainSpec/EngineSpec/ShardSpec/CohortSpec instead "
            "(DESIGN.md §10). The shim delegates to a session and keeps "
            "historical behavior bit-for-bit.",
            DeprecationWarning, stacklevel=3)


def run_federated(
    algorithm: ServerAlgorithm,
    loss_fn: Callable,
    w0: jax.Array,
    client_batches,
    *,
    rounds: int,
    tau: int,
    eta_l: float,
    key: jax.Array,
    eval_fn: Callable | None = None,
    avg_last: int = 2,
    engine: str = "scan",
    chunk_rounds: int | None = None,
    scan_unroll: int = 2,
    mesh=None,
    client_axis: str = "clients",
) -> RunResult:
    """DEPRECATED shim: run T federated rounds via a one-shot session.

    Equivalent to ``FederatedSession(algorithm, loss_fn, w0, client_batches,
    train=TrainSpec(...), engine=EngineSpec(...), shard=ShardSpec(...)).run(key)``
    — same engines, same compile caches, same results bit-for-bit.  New code
    should build the session directly (it also adds cohort sampling, eval
    cadence, pytree models, and checkpoint/resume).
    """
    _warn_deprecated("run_federated")
    from repro.fedsim.session import FederatedSession
    from repro.fedsim.specs import EngineSpec, ShardSpec, TrainSpec

    session = FederatedSession(
        algorithm, loss_fn, w0, client_batches,
        train=TrainSpec(rounds=rounds, tau=tau, eta_l=eta_l,
                        avg_last=max(1, int(avg_last))),
        engine=EngineSpec(engine=engine,
                          chunk_rounds=int(chunk_rounds) if chunk_rounds else None,
                          scan_unroll=max(1, int(scan_unroll))),
        shard=ShardSpec(mesh=mesh, client_axis=client_axis),
        eval_fn=eval_fn)
    return session.run(key)


def run_federated_batched(
    algorithm: ServerAlgorithm,
    loss_fn: Callable,
    w0: jax.Array,
    client_batches,
    *,
    rounds: int,
    tau: int,
    eta_l: float,
    keys: jax.Array,
    eval_fn: Callable | None = None,
    avg_last: int = 2,
    batched_w0: bool = False,
    batched_data: bool = False,
    mesh=None,
    client_axis: str = "clients",
) -> RunResult:
    """DEPRECATED shim: S-seed batched run via ``FederatedSession.run_batched``.

    ``keys`` is (S,)-stacked PRNG keys; set ``batched_w0`` / ``batched_data``
    when w0 / client_batches carry a matching leading seed axis.  Every
    RunResult field gains a leading (S,) axis.
    """
    _warn_deprecated("run_federated_batched")
    from repro.fedsim.session import FederatedSession
    from repro.fedsim.specs import ShardSpec, TrainSpec

    session = FederatedSession(
        algorithm, loss_fn, w0, client_batches,
        train=TrainSpec(rounds=rounds, tau=tau, eta_l=eta_l,
                        avg_last=max(1, int(avg_last))),
        shard=ShardSpec(mesh=mesh, client_axis=client_axis),
        eval_fn=eval_fn)
    return session.run_batched(keys, batched_w0=batched_w0,
                               batched_data=batched_data)
