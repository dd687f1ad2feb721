"""Client-side local training (Algorithm 3) — vectorized over the cohort.

Each client runs local gradient steps on its own dataset starting from the
broadcast global model and returns the raw local update
``Delta~_i = w_i^{(t-1,tau)} - w^{(t-1)}``.  The whole cohort is a single
``vmap`` so M=1000 clients execute as one batched XLA program.

The LocalTrainer layer (DESIGN.md §11).  ``local_update`` is the historical
full-batch GD of Algorithm 3; ``local_update_spec`` is the pytree-native
spec-driven trainer behind ``LocalSpec`` — minibatch SGD with local epochs,
a FedProx proximal term, and client momentum.  The spec trainers are written
entirely with ``jax.tree_util`` maps, so they train ANY parameter pytree
(the ``models/`` zoo plugs in directly) as well as the engine's flat
vectors; gradients are taken on whatever structure the loss sees and only
the resulting update is raveled at the clip/aggregate boundary.
``build_cohort_local_fn`` binds (loss, LocalSpec, tau) into the one
``local_fn(w, batches, eta_l, round_key, start)`` closure the round engine
compiles — the default spec routes through ``cohort_updates`` unchanged,
bit-for-bit.

Client sharding (DESIGN.md §9): when the engine partitions the cohort across
a ``clients`` mesh axis, each device vmaps only its (M/n_shards, d) slice.
``pad_cohort`` rounds M up to a multiple of the shard count by repeating row 0
(real data, so the padded rows' local training stays numerically tame for any
loss) and returns a {1., 0.} weight mask; every aggregation moment is
mask-weighted, so padded clients contribute exactly zero to the round.
``masked_cohort_updates`` additionally zeroes the padded rows' updates right
at the source, before they can reach a reduction.  Spec trainers key their
minibatch shuffles by GLOBAL client index (``start`` offset), so a shard
draws exactly the batches the single-device engine would.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.aggregation import global_client_indices
from repro.fedsim.specs import LOCAL_TRAIN_TAG, LocalSpec

__all__ = [
    "local_update",
    "local_update_spec",
    "local_update_scaffold",
    "cohort_updates",
    "cohort_updates_spec",
    "cohort_updates_scaffold",
    "build_cohort_local_fn",
    "masked_cohort_updates",
    "mask_rows",
    "pad_cohort",
    "chunk_cohort",
    "gather_slots",
    "gather_rows",
]


def local_update(loss_fn: Callable, w0: jax.Array, client_batch, tau: int,
                 eta_l: float, steps: jax.Array | None = None) -> jax.Array:
    """tau steps of (full-batch) GD on one client's data; returns the update.

    ``steps`` (optional traced int32 scalar) is the straggler cutoff
    (DESIGN.md §13): the client commits only its first ``steps`` of the
    ``tau`` local steps — the partial update a deadline-missing device
    uploads.  Shapes stay static (all tau steps are traced; later ones are
    where-frozen), and ``steps=None`` is the historical path, bit-for-bit.
    """

    def step(w, _):
        """One full-batch gradient-descent step on this client's data."""
        g = jax.grad(loss_fn)(w, client_batch)
        return w - eta_l * g, None

    # Unrolling trivial tau removes the inner while-loop, which otherwise
    # blocks XLA from fusing the local steps with the server-side reductions
    # when the whole round lives inside the scan engine's loop body; larger
    # tau keeps the loop — unrolling it multiplies compile time for heavy
    # per-step graphs (e.g. CNN grads) with no measured runtime win.
    if steps is None:
        w_tau, _ = jax.lax.scan(step, w0, None, length=tau,
                                unroll=tau if tau <= 2 else 1)
        return w_tau - w0

    def gated(w, i):
        """Step i, committed only while i < steps (straggler cutoff)."""
        w_new, _ = step(w, None)
        return jnp.where(i < steps, w_new, w), None

    w_tau, _ = jax.lax.scan(gated, w0, jnp.arange(tau, dtype=jnp.int32),
                            unroll=tau if tau <= 2 else 1)
    return w_tau - w0


def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def local_update_spec(loss_fn: Callable, w0, client_batch, key: jax.Array,
                      spec: LocalSpec, tau: int, eta_l,
                      steps: jax.Array | None = None):
    """Spec-driven local training for ONE client; returns the update pytree.

    ``w0`` may be any parameter pytree (a flat (d,) vector is the one-leaf
    case) — every update is a ``tree_map``, and gradients are taken on the
    structure ``loss_fn`` consumes.  Static shapes throughout: the step
    count, minibatch size and epoch layout are trace-time constants, so one
    compiled program serves every round.

    Semantics (see ``LocalSpec``): with ``batch_size`` set, step ``s`` of
    epoch ``e`` trains on rows ``perm_e[s*b : (s+1)*b]`` of a per-epoch
    shuffle drawn from ``fold_in(key, e)``; otherwise ``tau`` full-batch
    steps.  FedProx adds ``prox_mu * (w - w0)`` to each gradient; client
    momentum accumulates a velocity that starts at zero every round.
    """
    grad_fn = jax.grad(loss_fn)

    def gd_step(carry, batch):
        """One local gradient step (FedProx pull and momentum per the spec)."""
        w, v = carry
        g = grad_fn(w, batch)
        if spec.prox_mu:
            g = _tmap(lambda gg, ww, w0l: gg + spec.prox_mu * (ww - w0l), g, w, w0)
        if spec.momentum:
            v = _tmap(lambda vv, gg: spec.momentum * vv + gg, v, g)
            d = v
        else:
            d = g
        w = _tmap(lambda ww, dd: ww - eta_l * dd, w, d)
        return (w, v), None

    def gate(i, new, old):
        """Commit a (w, v) carry update only while i < steps (§13 cutoff)."""
        return _tmap(lambda a, b: jnp.where(i < steps, a, b), new, old)

    carry0 = (w0, _tmap(jnp.zeros_like, w0))
    if spec.batch_size is None:
        if steps is None:
            (w_tau, _), _ = jax.lax.scan(lambda c, _: gd_step(c, client_batch),
                                         carry0, None, length=tau,
                                         unroll=tau if tau <= 2 else 1)
        else:
            (w_tau, _), _ = jax.lax.scan(
                lambda c, i: (gate(i, gd_step(c, client_batch)[0], c), None),
                carry0, jnp.arange(tau, dtype=jnp.int32),
                unroll=tau if tau <= 2 else 1)
        return _tmap(lambda a, c: a - c, w_tau, w0)

    leaves, treedef = jax.tree_util.tree_flatten(client_batch)
    if not leaves or leaves[0].ndim < 1:
        raise ValueError("LocalSpec(batch_size=...) needs client batches "
                         "with a leading per-sample axis")
    n = leaves[0].shape[0]
    b = min(spec.batch_size, n)
    n_batches = max(1, n // b)

    # ALL PRNG work and ALL minibatch gathers happen up front: one shuffle
    # per epoch (vmapped), then one (steps, b, ...) gather per leaf, and the
    # training scan consumes the pre-gathered minibatches as plain xs.  This
    # keeps fold_in/permutation/gather out of the grad-bearing scan body —
    # one O(n log n) shuffle per epoch instead of per minibatch
    # (tests/test_local.py pins sharded == single-device for this path).
    # Cost: epochs extra copies of each client's sample set.
    perms = jax.vmap(lambda e: jax.random.permutation(
        jax.random.fold_in(key, e), n))(jnp.arange(spec.epochs, dtype=jnp.int32))
    idxs = perms[:, : n_batches * b].reshape(spec.epochs * n_batches, b)
    # only leaves carrying the per-sample axis are sliced; scalars and
    # differently-shaped leaves (per-client constants) ride along whole
    sliceable = [x.ndim >= 1 and x.shape[0] == n for x in leaves]
    xs = [jnp.take(x, idxs, axis=0)
          for x, ok in zip(leaves, sliceable) if ok]

    def batch_step(carry, mb_leaves):
        """One minibatch step over the pre-gathered minibatch leaves."""
        mb = list(mb_leaves)
        merged = [mb.pop(0) if ok else x for x, ok in zip(leaves, sliceable)]
        return gd_step(carry, jax.tree_util.tree_unflatten(treedef, merged))

    if steps is None:
        (w_tau, _), _ = jax.lax.scan(batch_step, carry0, tuple(xs))
    else:
        n_steps = spec.epochs * n_batches
        (w_tau, _), _ = jax.lax.scan(
            lambda c, x: (gate(x[1], batch_step(c, x[0])[0], c), None),
            carry0, (tuple(xs), jnp.arange(n_steps, dtype=jnp.int32)))
    return _tmap(lambda a, c: a - c, w_tau, w0)


def cohort_updates(loss_fn: Callable, w: jax.Array, client_batches, tau: int,
                   eta_l: float, steps: jax.Array | None = None) -> jax.Array:
    """(M, d) matrix of raw local updates for the full cohort (vmapped).

    ``steps`` (optional (M,) int32) is the per-client straggler cutoff
    (§13); None is the historical all-tau path, bit-for-bit.
    """
    if steps is None:
        fn = lambda batch: local_update(loss_fn, w, batch, tau, eta_l)
        return jax.vmap(fn)(client_batches)
    fn = lambda batch, s: local_update(loss_fn, w, batch, tau, eta_l, steps=s)
    return jax.vmap(fn)(client_batches, steps)


def local_update_scaffold(loss_fn: Callable, w0: jax.Array, client_batch,
                          c_i: jax.Array, c: jax.Array, tau: int, eta_l: float,
                          steps: jax.Array | None = None) -> jax.Array:
    """tau SCAFFOLD control-variate steps on one client (DESIGN.md §17).

    Each step moves by the drift-corrected direction ``g - c_i + c`` — the
    exact op order (and rolled ``length=tau`` scan) of the retired
    ``run_dp_scaffold`` local solve, so the migrated dense round is pinned
    bit-for-bit against it.  ``steps`` is the §13 straggler cutoff, gated
    exactly as ``local_update``.
    """

    def step(y, _):
        """One control-variate-corrected gradient step."""
        g = jax.grad(loss_fn)(y, client_batch)
        return y - eta_l * (g - c_i + c), None

    if steps is None:
        y_tau, _ = jax.lax.scan(step, w0, None, length=tau)
        return y_tau - w0

    def gated(y, i):
        """Step i, committed only while i < steps (straggler cutoff)."""
        y_new, _ = step(y, None)
        return jnp.where(i < steps, y_new, y), None

    y_tau, _ = jax.lax.scan(gated, w0, jnp.arange(tau, dtype=jnp.int32))
    return y_tau - w0


def cohort_updates_scaffold(loss_fn: Callable, w: jax.Array, client_batches,
                            tau: int, eta_l: float, ctx,
                            steps: jax.Array | None = None) -> jax.Array:
    """(m, d) control-variate cohort updates; ``ctx`` is the algorithm's
    per-round local context ``(c_i rows, global c)`` sliced by the engine
    (``DPScaffoldServer.local_context``), vmapped alongside the batches."""
    c_is, c = ctx
    if steps is None:
        fn = lambda batch, ci: local_update_scaffold(loss_fn, w, batch, ci, c,
                                                     tau, eta_l)
        return jax.vmap(fn)(client_batches, c_is)
    fn = lambda batch, ci, s: local_update_scaffold(loss_fn, w, batch, ci, c,
                                                    tau, eta_l, steps=s)
    return jax.vmap(fn)(client_batches, c_is, steps)


def cohort_updates_spec(loss_fn: Callable, w, client_batches, spec: LocalSpec,
                        tau: int, eta_l, round_key: jax.Array,
                        start: int | jax.Array = 0,
                        steps: jax.Array | None = None):
    """Spec-driven cohort updates, vmapped with per-client local PRNG keys.

    Client ``i`` of the shard draws its minibatch shuffles from
    ``fold_in(fold_in(round_key, LOCAL_TRAIN_TAG), start + i)`` — keyed by
    GLOBAL index so sharded and single-device engines shuffle identically.
    A (m,) vector ``start`` names each row's global index directly (the
    sparse-gather path, DESIGN.md §14).  ``steps`` (optional (M,) int32) is
    the per-client straggler cutoff (§13).
    """
    m = jax.tree_util.tree_leaves(client_batches)[0].shape[0]
    base = jax.random.fold_in(round_key, LOCAL_TRAIN_TAG)
    idx = global_client_indices(start, m)
    keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(idx)
    if steps is None:
        fn = lambda batch, k: local_update_spec(loss_fn, w, batch, k, spec, tau, eta_l)
        return jax.vmap(fn)(client_batches, keys)
    fn = lambda batch, k, s: local_update_spec(loss_fn, w, batch, k, spec,
                                               tau, eta_l, steps=s)
    return jax.vmap(fn)(client_batches, keys, steps)


def _build_cohort_local_fn(loss_fn: Callable, spec: LocalSpec | None, tau: int,
                           with_steps: bool = False):
    if spec is not None and spec.control_variates:
        # SCAFFOLD trainer (§17): one extra trailing arg — the algorithm's
        # per-client context (c_i rows, c), appended by _local_caller when
        # the algorithm declares uses_local_context
        if with_steps:
            def local_fn(w, client_batches, eta_l, round_key, start, steps,
                         ctx):
                """Control-variate closure with straggler cutoffs (§13/§17)."""
                return cohort_updates_scaffold(loss_fn, w, client_batches,
                                               tau, eta_l, ctx, steps=steps)
            return local_fn

        def local_fn(w, client_batches, eta_l, round_key, start, ctx):
            """The engine's control-variate local-training closure (§17)."""
            return cohort_updates_scaffold(loss_fn, w, client_batches, tau,
                                           eta_l, ctx)
        return local_fn

    if with_steps:
        if spec is None or spec.is_default:
            def local_fn(w, client_batches, eta_l, round_key, start, steps):
                """Local-training closure with per-client straggler cutoffs (§13)."""
                return cohort_updates(loss_fn, w, client_batches, tau, eta_l,
                                      steps=steps)
            return local_fn

        def local_fn(w, client_batches, eta_l, round_key, start, steps):
            """Local-training closure with per-client straggler cutoffs (§13)."""
            return cohort_updates_spec(loss_fn, w, client_batches, spec, tau,
                                       eta_l, round_key, start, steps=steps)
        return local_fn

    if spec is None or spec.is_default:
        def local_fn(w, client_batches, eta_l, round_key, start):
            """The engine's local-training closure: cohort deltas for one round."""
            return cohort_updates(loss_fn, w, client_batches, tau, eta_l)
        return local_fn

    def local_fn(w, client_batches, eta_l, round_key, start):
        """The engine's local-training closure: cohort deltas for one round."""
        return cohort_updates_spec(loss_fn, w, client_batches, spec, tau,
                                   eta_l, round_key, start)
    return local_fn


_cached_cohort_local_fn = functools.lru_cache(maxsize=64)(_build_cohort_local_fn)


def build_cohort_local_fn(loss_fn: Callable, spec: LocalSpec | None, tau: int,
                          with_steps: bool = False):
    """Bind (loss, LocalSpec, tau) into the engine's local-training closure:

        local_fn(w, client_batches, eta_l, round_key, start) -> (M, d) deltas

    The default spec returns the historical ``cohort_updates`` computation —
    the identical jaxpr, so pre-LocalSpec sessions stay bit-for-bit.  The
    closure's identity keys the engine's compile cache, so it is MEMOIZED on
    (loss_fn identity, spec, tau): two sessions sharing a loss closure and
    equal specs receive the same ``local_fn`` object and keep sharing one
    compiled chunk program, exactly as the pre-LocalSpec engine keyed on
    ``loss_fn`` directly.  An unhashable loss falls back to an uncached
    build (a per-session retrace — the cost the engine's builder fallback
    already documents, never an error).

    ``with_steps=True`` (straggler faults, §13) returns the variant closure

        local_fn(w, client_batches, eta_l, round_key, start, steps)

    taking a per-client (m,) int32 step-count vector; it keys the memo
    separately, so fault-free sessions keep sharing the historical closure.
    """
    try:
        return _cached_cohort_local_fn(loss_fn, spec, tau, with_steps)
    except TypeError:
        return _build_cohort_local_fn(loss_fn, spec, tau, with_steps)


def mask_rows(deltas: jax.Array, mask: jax.Array) -> jax.Array:
    """Zero the masked-out rows of a delta matrix AT THE SOURCE.

    The where (not a multiply) means a non-finite update from a padding or
    non-sampled client's dummy batch cannot leak into the round's moments
    as 0 * nan.
    """
    return jnp.where(mask[:, None] > 0, deltas, 0.0)


def masked_cohort_updates(loss_fn: Callable, w: jax.Array, client_batches,
                          tau: int, eta_l: float, mask: jax.Array) -> jax.Array:
    """``cohort_updates`` with padding rows forced to zero (see mask_rows)."""
    deltas = cohort_updates(loss_fn, w, client_batches, tau, eta_l)
    return mask_rows(deltas, mask)


def pad_cohort(client_batches, n_shards: int, *, axis: int = 0):
    """Pad every client-batch leaf to M % n_shards == 0; returns (batches, mask).

    Padding repeats client 0's data (finite, in-distribution) rather than
    zeros so arbitrary user losses don't see degenerate inputs; the returned
    float mask is 0. on padded rows and the moment reductions weight by it,
    which keeps the padded clients out of Σc_i, Σ||c_i||², the client count,
    and the adaptive-clip bit sum alike.  ``axis`` is the client axis of the
    leaves (1 in the batched engine, where a seed axis leads).
    """
    leaves = jax.tree_util.tree_leaves(client_batches)
    if not leaves:
        raise ValueError("client_batches has no array leaves to shard")
    m = leaves[0].shape[axis]
    pad = (-m) % n_shards
    mask = jnp.concatenate([jnp.ones((m,), jnp.float32),
                            jnp.zeros((pad,), jnp.float32)])
    if pad == 0:
        return client_batches, mask

    def pad_leaf(x):
        """Append ``pad`` copies of row 0 along the client axis of one leaf."""
        first = jax.lax.slice_in_dim(x, 0, 1, axis=axis)
        shape = x.shape[:axis] + (pad,) + x.shape[axis + 1:]
        return jnp.concatenate([x, jnp.broadcast_to(first, shape)], axis=axis)

    return jax.tree_util.tree_map(pad_leaf, client_batches), mask


def chunk_cohort(client_batches, chunk_clients: int, *, n_shards: int = 1):
    """Lay the cohort on the streaming engine's chunk grid (DESIGN.md §12).

    Pads M to a multiple of ``chunk_clients * n_shards`` (zero-weight
    clients, exactly as ``pad_cohort``) and reshapes every client-batch leaf
    from (m_pad, ...) to (n_chunks, chunk_clients, ...); the weight mask
    comes back as (n_chunks, chunk_clients).  Chunk j holds the clients with
    global indices [j*c, (j+1)*c), so contiguous chunk blocks are contiguous
    client blocks — under §9 sharding the leading CHUNK axis shards over the
    ``clients`` mesh and every device receives the same client rows the
    dense sharded engine would.

    Args:
      client_batches: pytree of per-client leaves, client axis leading.
      chunk_clients: clients per chunk (``StreamSpec.chunk_clients``).
      n_shards: client-mesh size the chunk grid must also divide by.

    Returns:
      ``(chunk_batches, chunk_mask)`` — the reshaped pytree and the float
      {1., 0.} weight mask on the same grid.
    """
    if chunk_clients < 1:
        raise ValueError(f"chunk_clients must be >= 1, got {chunk_clients}")
    batches, mask = pad_cohort(client_batches, chunk_clients * n_shards)
    n_chunks = mask.shape[0] // chunk_clients

    def to_grid(x):
        """Reshape one padded leaf onto the (n_chunks, chunk_clients, ...) grid."""
        return x.reshape((n_chunks, chunk_clients) + x.shape[1:])

    return (jax.tree_util.tree_map(to_grid, batches),
            mask.reshape(n_chunks, chunk_clients))


def gather_slots(mask: jax.Array, cap: int):
    """Pack a sparse participation mask into a dense slot table (§14).

    Given the (m,) per-round mask (0. = non-participant), returns

        slots:       (cap,) int32 — slot j holds the global index of the
                     j-th participant (in index order); padding slots hold 0
        slot_mask:   (cap,) float32 — the participant's mask value in its
                     slot (1., or the multiplicity weight), 0. on padding
        overflow:    scalar float32 — how many participants did NOT fit in
                     ``cap`` slots (0. when the cap held)

    Pure jax with static shapes (mask → positions via cumsum, one scatter
    with ``mode="drop"``), so it runs inside the scan body.  Padding slots
    point at client 0 — REAL data, so padded rows' local training stays
    finite for any loss — and carry a zero ``slot_mask``, which the §9/§13
    masked-moment protocol already guarantees keeps them out of every sum.
    Participants beyond ``cap`` are dropped from the round (their scatter
    target falls off the table); ``overflow`` lets callers surface that.
    """
    m = mask.shape[0]
    on = mask > 0
    pos = jnp.cumsum(on.astype(jnp.int32)) - 1          # participant rank
    target = jnp.where(on & (pos < cap), pos, cap)      # cap = off-table
    slots = jnp.full((cap,), m, jnp.int32).at[target].set(
        jnp.arange(m, dtype=jnp.int32), mode="drop")
    valid = slots < m
    slots = jnp.where(valid, slots, 0)
    slot_mask = jnp.where(valid, jnp.take(mask, slots, axis=0), 0.0)
    overflow = jnp.maximum(jnp.sum(on.astype(jnp.float32)) - float(cap), 0.0)
    return slots, slot_mask.astype(jnp.float32), overflow


def gather_rows(tree, slots: jax.Array, *, axis: int = 0):
    """Gather the slot rows out of every leaf of a per-client pytree.

    ``jnp.take`` along the client axis — the §14 pre-gather that shrinks a
    (m, ...) cohort block to the (cap, ...) sampled block before local
    training runs.  Slot indices are always in-range (``gather_slots`` clamps
    padding to client 0), so no gather-mode games are needed.
    """
    return jax.tree_util.tree_map(
        lambda x: jnp.take(x, slots, axis=axis), tree)
