"""FederatedSession: the spec-driven, resumable simulation entry point.

DESIGN.md §10.  A session binds (algorithm, loss_fn, model, client data) to
four frozen specs and owns the compiled chunk program:

    session = FederatedSession(
        algorithm, loss_fn, params, client_batches,
        train=TrainSpec(rounds=50, tau=20, eta_l=0.1),
        local=LocalSpec(batch_size=8),      # minibatch local SGD (§11)
        cohort=CohortSpec(q=0.25),          # per-round Poisson sampling
        eval_fn=eval_fn)
    result = session.run(jax.random.PRNGKey(0))

Three properties the kwargs-style API could not offer:

* **Pytree-native models.**  ``params`` may be any parameter pytree (the
  ``models/`` zoo plugs in directly); the session ravels it once via
  ``fedsim.flat.flatten_model``, wraps the loss/eval closures, and unravels
  ``RunResult.final_w`` / ``last_w`` back to the caller's structure.  Flat
  (d,) vectors pass through untouched — zero overhead, bit-identical.

* **Per-round client sampling.**  ``CohortSpec`` draws the participation
  mask inside the scan body (static shapes, one compiled program per chunk)
  and routes the round through the masked-moment protocol; the sampling rate
  feeds ``core.accounting`` for amplification-aware epsilon reporting
  (``privacy_report``).

* **Resumable runs.**  ``run(key, checkpoint_dir=...)`` threads the round
  counter, RNG key, model, optimizer/clip state, and histories through
  ``repro.checkpoint``; ``resume(checkpoint_dir)`` continues to
  ``train.rounds`` and returns the same RunResult an uninterrupted run
  produces — bit-exactly, because per-round keys are ``fold_in(key, t)`` by
  GLOBAL round index and the carry round-trips losslessly.

The session holds its loss/eval closures for its lifetime, so the engine's
cross-call compile cache (keyed on closure identity + hashable specs) hits on
every ``run``/``resume``/``run_batched`` after the first.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.core import accounting
from repro.core.fedexp import ServerAlgorithm
from repro.fedsim import server as _srv
from repro.fedsim.data import ClientDataSource, as_data_source
from repro.fedsim.flat import flatten_model
from repro.fedsim.local import (
    build_cohort_local_fn,
    chunk_cohort,
    gather_slots,
    pad_cohort,
)
from repro.fedsim.server import RunResult
from repro.fedsim.specs import (
    CohortSpec,
    DataSpec,
    EngineSpec,
    FaultSpec,
    LocalSpec,
    ShardSpec,
    StreamSpec,
    TelemetrySpec,
    TrainSpec,
)
from repro.telemetry import NullTracker, Tracker, spans
from repro.telemetry import tap as _tap_mod

__all__ = ["FederatedSession", "RecoveryPolicy"]


@dataclasses.dataclass(frozen=True)
class RecoveryPolicy:
    """Auto-recovery for watchdog-tripped runs (DESIGN.md §13).

    ``run(key, checkpoint_dir=..., on_divergence=RecoveryPolicy(...))`` rolls
    a tripped run back to the newest intact checkpoint, sleeps
    ``backoff * attempt`` seconds (0 disables), and re-runs — at most
    ``max_retries`` times, after which the fault is surfaced in
    ``RunResult.fault_round`` instead.  Every rolled-back round was still
    EXECUTED against client data, so retried rounds join the privacy
    composition (``FederatedSession.privacy_report``).
    """
    max_retries: int = 3
    backoff: float = 0.0

    def __post_init__(self):
        if self.max_retries < 1:
            raise ValueError(
                f"max_retries must be >= 1, got {self.max_retries} "
                "(omit on_divergence to disable recovery)")
        if self.backoff < 0.0:
            raise ValueError(f"backoff must be >= 0, got {self.backoff}")


def _is_flat_params(w0) -> bool:
    """True when w0 is already a bare array (the historical flat contract).

    A bare array of ANY rank passes through unwrapped — run_batched's
    ``batched_w0`` stacks seeds on axis 0 of a flat (S, d) array, which must
    not be mistaken for a pytree model.  Anything with tree structure (dict,
    tuple, dataclass of arrays) is a model pytree and gets raveled.
    """
    leaves = jax.tree_util.tree_leaves(w0)
    return len(leaves) == 1 and leaves[0] is w0


class FederatedSession:
    """A reusable, compiled federated run bound to declarative specs."""

    def __init__(self, algorithm: ServerAlgorithm, loss_fn: Callable,
                 w0: Any, client_batches, *, train: TrainSpec,
                 local: LocalSpec = LocalSpec(),
                 engine: EngineSpec = EngineSpec(),
                 shard: ShardSpec = ShardSpec(),
                 cohort: CohortSpec = CohortSpec(),
                 stream: StreamSpec = StreamSpec(),
                 fault: FaultSpec = FaultSpec(),
                 data: DataSpec | None = None,
                 telemetry: TelemetrySpec = TelemetrySpec(),
                 eval_fn: Callable | None = None,
                 num_clients: int | None = None):
        """Bind (algorithm, loss, model, client data) to declarative specs.

        Args:
          algorithm: a ``ServerAlgorithm`` (typically ``make_algorithm(...)``
            or a ``compose_algorithm(...)`` composition).
          loss_fn: per-client loss ``loss_fn(params, client_batch) -> scalar``
            on the caller's parameter structure.
          w0: initial model — any parameter pytree, or a flat (d,) vector
            (passes through unwrapped).
          client_batches: pytree of per-client data; every leaf carries the
            client axis leading (axis 1 for ``run_batched(batched_data=True)``).
            Also accepts a ``ClientDataSource`` (DESIGN.md §14): an
            ``ArraySource`` unwraps to the historical device-resident engine
            bit-for-bit; host/npz/synthetic sources stream chunk-staged data
            through ``engine="stream"``, bounding M by host storage.
          train: what to train (rounds, tau, eta_l, averaging, eval cadence).
          local: how clients train locally (DESIGN.md §11).
          engine: how the round loop compiles — scan / eager / stream (§8, §12).
          shard: optional ``clients`` mesh the cohort shards over (§9).
          cohort: per-round client sampling (§10).
          stream: client-chunk grid of the streaming engine (§12); only
            consulted when ``engine="stream"`` (a non-default spec under any
            other engine raises, rather than being silently ignored).
          fault: deterministic fault injection + divergence watchdog (§13);
            the default (no faults, watchdog off) is normalized away and
            reproduces the fault-free program bit-for-bit.
          data: where the client data lives + prefetch depth (§14).  Derived
            from ``client_batches`` when omitted (the eighth spec — joins the
            compile-cache key); passing one whose ``kind`` contradicts the
            actual input raises rather than silently mis-staging.
          telemetry: how the run is observed (§15): ledger δ and profiler
            window.  Deliberately NOT part of any compile-cache key — only
            the presence of a non-null ``run(tracker=...)`` flips the
            single on/off tap flag the engines compile against.
          eval_fn: optional metric closure ``eval_fn(params) -> scalar``.
          num_clients: explicit cohort size, required only when the client
            axis is not leaf axis 0 (``run_batched(batched_data=True)``).
        """
        self.algorithm = algorithm
        self.train = train
        self.local = local
        self.engine = engine
        self.shard = shard
        self.stream = stream
        self.telemetry = telemetry
        if engine.engine != "stream" and stream != StreamSpec():
            raise ValueError(
                "a non-default StreamSpec requires engine='stream' "
                "(EngineSpec(engine='stream')); it would be silently "
                f"ignored under engine={engine.engine!r}")
        # normalize full participation to None so unsampled sessions share
        # compile-cache entries with pre-cohort callers (and with each other
        # regardless of how "no sampling" was spelled)
        self.cohort = cohort if cohort.is_sampled else None
        # same normalization for the fault model: FaultSpec() is structurally
        # the fault-free engine — identical compile-cache key, identical
        # program, bit-exact with pre-fault sessions (DESIGN.md §13)
        self.fault = fault if fault.is_active else None
        # privacy compositions consumed by rolled-back rounds (recovery);
        # privacy_report folds these into the round count
        self._rounds_retried = 0
        # run()/resume() calls so far: the ``call`` of their profiler spans
        self._calls = 0
        # test hook: callable (carry, attempt) -> carry applied before the
        # first chunk of each recovery attempt — lets tests inject a
        # TRANSIENT divergence (poison attempt 0 only) so the retried run is
        # bit-exact with an unkilled reference
        self._inject_divergence = None
        # unified data entry (§14): a ClientDataSource of kind "device"
        # unwraps to the historical device-resident path (bit-for-bit); other
        # kinds stay behind the source and stream host-staged chunks
        source = as_data_source(client_batches)
        if source is not None and source.kind == "device":
            client_batches, source = source.batches, None
        self._source = source
        kind = "device" if source is None else source.kind
        if data is None:
            data = DataSpec(kind=kind)
        elif data.kind != kind:
            raise ValueError(
                f"DataSpec(kind={data.kind!r}) contradicts the client data "
                f"actually passed ({kind!r}); drop data= (the kind is "
                "derived) or pass the matching ClientDataSource")
        self.data = data
        if source is not None:
            if engine.engine != "stream":
                raise ValueError(
                    f"a {kind!r} ClientDataSource requires engine='stream' "
                    "(the scan/eager engines assume device-resident "
                    "batches); pass EngineSpec(engine='stream') or stage the "
                    "data yourself and pass device arrays")
            if shard.mesh is not None:
                raise ValueError(
                    "host-resident sources stream on a single device (chunk "
                    "staging does not compose with the clients mesh yet); "
                    "drop ShardSpec or pass device-resident batches")
            if self.fault is not None:
                raise ValueError(
                    "fault injection requires device-resident batches (the "
                    "fault engines draw per-client faults inside the "
                    "compiled round); drop FaultSpec or pass device arrays")
        self.client_batches = client_batches
        # leaf axis 0 is the client axis EXCEPT for run_batched(batched_data=
        # True), where a seed axis leads — pass num_clients= explicitly there
        # (run_batched re-derives it for its own masks either way)
        if source is not None:
            self.num_clients = source.num_clients
        else:
            self.num_clients = (num_clients if num_clients is not None else
                                jax.tree_util.tree_leaves(
                                    client_batches)[0].shape[0])

        if _is_flat_params(w0):
            self._w0 = jnp.asarray(w0)
            self._unravel = None
            self.loss_fn = loss_fn
            self.eval_fn = eval_fn
        else:
            flat, unravel = flatten_model(w0)
            self._w0 = flat
            self._unravel = unravel
            # the session OWNS these wrappers: their identity is the compile-
            # cache key, so they must live exactly as long as the session
            self.loss_fn = lambda wf, batch: loss_fn(unravel(wf), batch)
            self.eval_fn = (None if eval_fn is None
                            else (lambda wf: eval_fn(unravel(wf))))
        if engine.engine == "stream" and self.stream.is_auto:
            # resolve chunk_clients="auto" from the live device budget (the
            # docs/scaling.md sizing rule, mirroring auto_shard_count); the
            # resolved value is recorded on self.stream so benchmarks can
            # name it in their config identity
            from repro.launch.mesh import auto_chunk_clients
            n_shards = (1 if shard.mesh is None
                        else shard.mesh.shape[shard.client_axis])
            self.stream = StreamSpec(chunk_clients=auto_chunk_clients(
                self.dim, self._client_bytes(), n_shards=n_shards))
        # the LocalTrainer closure (DESIGN.md §11): binds loss, LocalSpec and
        # tau once — its identity keys the engine's compile cache, and the
        # default spec reproduces the pre-LocalSpec program bit-for-bit.
        # Straggler cutoffs need the with_steps variant (arity +1, §13).
        # Context-consuming algorithms (DP-SCAFFOLD, §17) and the
        # control-variate trainer come as a pair: the engine appends the
        # algorithm's per-client context to the trainer call, so a mismatch
        # would surface as an opaque arity error deep in the compiled round.
        wants_ctx = bool(getattr(self.algorithm, "uses_local_context", False))
        has_cv = self.local is not None and getattr(
            self.local, "control_variates", False)
        if wants_ctx != has_cv:
            if wants_ctx:
                raise ValueError(
                    f"{self.algorithm.name!r} trains with per-client control "
                    "variates; pass local=LocalSpec(control_variates=True) "
                    "so the LocalTrainer consumes the (c_i, c) context")
            raise ValueError(
                "LocalSpec(control_variates=True) needs a control-variate "
                f"algorithm (e.g. make_algorithm('dp-scaffold', ...)); "
                f"{self.algorithm.name!r} supplies no local context")
        with_steps = self.fault is not None and self.fault.straggler > 0.0
        self._local_fn = build_cohort_local_fn(self.loss_fn, self.local,
                                               int(train.tau),
                                               with_steps=with_steps)

    # -- helpers -----------------------------------------------------------

    def _validate_cohort(self, m: int) -> None:
        if self.cohort is not None and self.cohort.size is not None \
                and not self.cohort.replace and self.cohort.size > m:
            raise ValueError(
                f"CohortSpec.size={self.cohort.size} exceeds the "
                f"{m}-client cohort (without replacement)")
        agg = getattr(self.algorithm, "aggregation", None)
        if agg is not None and getattr(agg, "is_weighted", False) \
                and len(agg.weights) != m:
            raise ValueError(
                f"WeightedAggregation carries {len(agg.weights)} weights for "
                f"a {m}-client cohort; weights are indexed by global client "
                "index and must match exactly (a short tuple would silently "
                "zero-weight the tail clients)")
        alg_m = getattr(self.algorithm, "num_clients", None)
        if getattr(self.algorithm, "uses_local_context", False) \
                and alg_m is not None and alg_m != m:
            raise ValueError(
                f"{self.algorithm.name!r} carries a {alg_m}-client variate "
                f"table for a {m}-client cohort; num_clients indexes the "
                "per-client state by global client index and must match")

    @property
    def dim(self) -> int:
        """Flat model dimension d (after any pytree ravel)."""
        return self._w0.shape[-1]

    def _client_bytes(self) -> int:
        """Approximate bytes of ONE client's data (the auto-chunk sizing
        term): one fetched row for a source, total-bytes / M for arrays."""
        if self._source is not None:
            rows = self._source.fetch(np.zeros((1,), np.int64))
            return int(sum(np.asarray(x).nbytes
                           for x in jax.tree_util.tree_leaves(rows)))
        total = sum(x.nbytes
                    for x in jax.tree_util.tree_leaves(self.client_batches))
        return int(total // max(1, self.num_clients))

    def _tail_n(self) -> int:
        return max(1, min(self.train.avg_last, self.train.rounds))

    def _donate(self) -> bool:
        if self.engine.donate is not None:
            return self.engine.donate
        return jax.default_backend() in ("tpu", "gpu")

    def _restore_params(self, w):
        return w if self._unravel is None else self._unravel(w)

    @property
    def _watchdog(self) -> bool:
        return self.fault is not None and self.fault.watchdog

    def _restore_batched(self, w):
        return w if self._unravel is None else jax.vmap(self._unravel)(w)

    def _chunk_callable(self, donate: bool, tap: bool = False):
        """The compiled chunk program + the extra positional args it takes.

        ``tap`` is the §15 on/off engine-tap flag — the ONLY telemetry bit
        that reaches the builders (and hence the compile-cache keys).
        """
        t, e, s = self.train, self.engine, self.shard
        if e.engine == "stream":
            n_shards = 1 if s.mesh is None else s.mesh.shape[s.client_axis]
            # cap the chunk at the cohort size: chunk >= M is the one-chunk
            # degenerate grid either way, and normalizing the spec keeps a
            # small cohort from being padded up to a large default chunk
            # (and lets all such sessions share one compiled program)
            stream = StreamSpec(chunk_clients=min(self.stream.chunk_clients,
                                                  max(1, self.num_clients)))
            if self._source is not None:
                # host-resident driver (§14): chunk-staged fetch + prefetch,
                # one compiled chunk program — the source rides the batches
                # slot of the fn(carry, key, ts, batches, eta_l) contract
                return (self._host_chunk_callable(stream.chunk_clients,
                                                  tap=tap),
                        self._source, ())
            if self.cohort is not None and self.cohort.gather:
                # gather-stream (§14): the cohort stays UN-chunked; the
                # round packs its slot table and the inner scan walks slots
                batches, mask = pad_cohort(self.client_batches, n_shards)
                m_pad = mask.shape[0]
                if s.mesh is None:
                    fn = _srv._gather_stream_chunk_fn(
                        self.algorithm, self._local_fn, self.eval_fn, donate,
                        e.scan_unroll, stream.chunk_clients,
                        self.num_clients, m_pad, t.eval_every, self.cohort,
                        self.fault, int(t.tau), tap)
                    return fn, batches, (mask,)
                leaves, treedef = jax.tree_util.tree_flatten(batches)
                fn = _srv._sharded_gather_stream_chunk_fn(
                    self.algorithm, self._local_fn, self.eval_fn, donate,
                    e.scan_unroll, stream.chunk_clients, s.mesh,
                    s.client_axis, treedef, tuple(x.ndim for x in leaves),
                    m_pad, self.num_clients, t.eval_every, self.cohort,
                    self.fault, int(t.tau), tap)
                return fn, batches, (mask,)
            batches, mask = chunk_cohort(self.client_batches,
                                         stream.chunk_clients,
                                         n_shards=n_shards)
            n_chunks = mask.shape[0]
            m_pad = n_chunks * stream.chunk_clients
            if s.mesh is None:
                fn = _srv._stream_chunk_fn(
                    self.algorithm, self._local_fn, self.eval_fn, donate,
                    e.scan_unroll, stream, self.num_clients, m_pad,
                    t.eval_every, self.cohort, self.fault, int(t.tau), tap)
                return fn, batches, (mask,)
            leaves, treedef = jax.tree_util.tree_flatten(batches)
            fn = _srv._sharded_stream_chunk_fn(
                self.algorithm, self._local_fn, self.eval_fn, donate,
                e.scan_unroll, stream, s.mesh, s.client_axis, treedef,
                tuple(x.ndim for x in leaves), n_chunks, self.num_clients,
                m_pad, t.eval_every, self.cohort, self.fault, int(t.tau), tap)
            return fn, batches, (mask,)
        if s.mesh is not None:
            m_true = self.num_clients
            batches, mask = pad_cohort(self.client_batches,
                                       s.mesh.shape[s.client_axis])
            leaves, treedef = jax.tree_util.tree_flatten(batches)
            fn = _srv._sharded_chunk_fn(
                self.algorithm, self._local_fn, self.eval_fn, donate,
                e.scan_unroll, s.mesh, s.client_axis, treedef,
                tuple(x.ndim for x in leaves), mask.shape[0], m_true,
                t.eval_every, self.cohort, self.fault, int(t.tau), tap)
            return fn, batches, (mask,)
        fn = _srv._scan_chunk_fn(self.algorithm, self._local_fn, self.eval_fn,
                                 donate, e.scan_unroll,
                                 t.eval_every, self.cohort, self.fault,
                                 int(t.tau), tap)
        return fn, self.client_batches, ()

    def _host_chunk_callable(self, chunk_clients: int, tap: bool = False):
        """The host-resident stream driver (DESIGN.md §14).

        Returns a callable with the engine contract ``fn(carry, key, ts,
        batches, eta_l)`` — so ``_run_scan``'s chunking, checkpointing, and
        resume machinery drive it unchanged — that loops rounds in Python:
        per round it derives the round key and participation mask eagerly
        (the same pure-jax draws the compiled engines trace), plans the
        chunk grid, and pumps ``source.fetch`` + ``jax.device_put`` through
        a ``DataSpec.prefetch``-deep staging deque so the next chunk's
        host→device transfer overlaps the current chunk's compiled moments
        program.  Chunks accumulate in the device-resident stream engine's
        exact order and arithmetic, so host-staged results are bit-exact
        with device-resident ones.

        With ``tap`` the driver emits each round's §15 telemetry payload
        directly from the Python loop (no io_callback needed — the loop IS
        on the host), through the same ``TapSession.emit`` funnel the
        compiled engines reach, so sinks cannot tell the paths apart.  The
        host path never injects faults (the session forbids the combination),
        so the fault slots are inert.
        """
        m = self.num_clients
        cohort = self.cohort
        gathering = cohort is not None and cohort.gather
        if gathering:
            cap = cohort.resolved_cap(m)
            c = min(chunk_clients, cap)
            n_chunks = -(-cap // c)
        else:
            c = chunk_clients
            n_chunks = -(-m // c)
        grid = n_chunks * c
        depth = max(1, self.data.prefetch)
        source = self._source
        moments_fn = _srv._host_moments_fn(self.algorithm, self._local_fn,
                                           self.data)
        finalize = _srv._host_finalize_fn(self.algorithm, self.eval_fn,
                                          self.train.eval_every, cohort, m)
        if not gathering:
            # dense grid: chunk j is global rows [j*c, (j+1)*c); rows past M
            # fetch client 0 (pad_cohort's repeat-row-0 pad, zero-masked) but
            # keep their padded-grid GLOBAL index for key-fold parity
            dense_gidx = [jnp.arange(j * c, (j + 1) * c, dtype=jnp.int32)
                          for j in range(n_chunks)]
            dense_idx = [np.where(g < m, g, 0)
                         for g in (np.arange(j * c, (j + 1) * c)
                                   for j in range(n_chunks))]

        clip_fn = _srv._tap_clip_fn(self.algorithm) if tap else None
        sigma_fn = _srv._tap_sigma_fn(self.algorithm) if tap else None

        def run_rounds(carry, key, ts, src, eta_l):
            """Python round loop with prefetch-staged chunk programs."""
            del src  # the engine contract's batches slot; == self._source
            w, opt_state, tail = carry
            cols = ([], [], [], [])
            for t_host in np.asarray(ts):
                with jax.profiler.TraceAnnotation(
                        spans.DISPATCH, call=self._calls,
                        rounds=f"{t_host}:{t_host + 1}"):
                    t = jnp.int32(int(t_host))
                    rk = jax.random.fold_in(key, t)
                    if gathering:
                        round_mask = cohort.round_mask(rk, m)
                        slots, slot_mask, _ = gather_slots(round_mask, grid)
                        slots_np = np.asarray(jax.device_get(slots))
                        sgrid = slots.reshape(n_chunks, c)
                        mgrid = slot_mask.reshape(n_chunks, c)
                        plan = ((slots_np[j * c:(j + 1) * c], mgrid[j], sgrid[j])
                                for j in range(n_chunks))
                    else:
                        round_mask = (cohort.round_mask(rk, m)
                                      if cohort is not None
                                      else jnp.ones((m,), jnp.float32))
                        full = jnp.concatenate(
                            [round_mask, jnp.zeros((grid - m,), jnp.float32)])
                        mgrid = full.reshape(n_chunks, c)
                        plan = ((dense_idx[j], mgrid[j], dense_gidx[j])
                                for j in range(n_chunks))

                    buf = collections.deque()

                    def stage(plan=plan, buf=buf):
                        """Fetch + device_put the next planned chunk, if any."""
                        p = next(plan, None)
                        if p is None:
                            return
                        idx_np, mask_j, gidx_j = p
                        buf.append((jax.device_put(source.fetch(idx_np)),
                                    mask_j, gidx_j))

                    for _ in range(depth):
                        stage()
                    moments = None
                    while buf:
                        batches_j, mask_j, gidx_j = buf.popleft()
                        mom = moments_fn(w, opt_state, rk, batches_j, mask_j,
                                         gidx_j, eta_l, t)
                        # refill AFTER dispatch: the next fetch/transfer overlaps
                        # the asynchronously executing chunk program
                        stage()
                        moments = (mom if moments is None
                                   else _srv._host_add_moments(moments, mom))
                    clip_val = clip_fn(opt_state) if tap else None
                    w, opt_state, tail, outs = finalize(w, opt_state, tail,
                                                        rk, t, moments)
                    for col, v in zip(cols, outs):
                        col.append(v)
                    if tap:
                        sess = _tap_mod.active()
                        if sess is not None:
                            eta, metric, naive, target = outs
                            part = jnp.sum(round_mask)
                            payload = np.asarray(jax.device_get(jnp.stack([
                                jnp.float32(eta), jnp.float32(naive),
                                jnp.float32(target), jnp.float32(metric),
                                jnp.float32(clip_val), part, part,
                                jnp.float32(0.0), jnp.float32(0.0),
                                jnp.float32(0.0), jnp.float32(-1.0),
                                sigma_fn(t)])))
                            sess.emit(int(t_host), 0, payload)
            hist = tuple(jnp.stack(col) if col
                         else jnp.zeros((0,), jnp.float32) for col in cols)
            return (w, opt_state, tail), hist

        return run_rounds

    @staticmethod
    def _chunk_bounds(start: int, rounds: int, chunk_rounds: int | None,
                      checkpoint_every: int | None = None,
                      profile: tuple[int, int] | None = None):
        """[start, rounds) split at the chunk grid (anchored at ``start``,
        matching the historical one-shot behavior) union the checkpoint grid
        (anchored at round 0, so checkpoints land on stable global rounds)
        union the §15 profiler-window edges (so ``TelemetrySpec.
        profile_rounds=(a, b)`` traces exactly rounds [a, b) — the trace
        starts/stops at chunk boundaries)."""
        stops = set()
        chunk = (rounds - start) if not chunk_rounds else max(1, int(chunk_rounds))
        stops.update(range(start + chunk, rounds, chunk))
        if checkpoint_every:
            stops.update(b for b in range(checkpoint_every, rounds,
                                          checkpoint_every) if b > start)
        if profile is not None:
            stops.update(edge for edge in (profile[0], min(profile[1], rounds))
                         if start < edge < rounds)
        stops.add(rounds)
        edges = [start] + sorted(stops)
        return list(zip(edges[:-1], edges[1:]))

    # -- checkpoint plumbing ----------------------------------------------

    def _save(self, directory: str, step: int, key, carry, hist) -> str:
        key_arr, typed = _key_data(key)
        return ckpt.save_checkpoint(
            directory, step, {"carry": carry, "hist": hist},
            extra={"key": [int(x) for x in key_arr.reshape(-1)],
                   "key_typed": typed,
                   "algorithm": self.algorithm.name,
                   "rounds_total": self.train.rounds})

    def _carry_template(self):
        """Zero carry matching this session's structure (+ watchdog slot)."""
        w = jnp.asarray(self._w0)
        carry = (w, self.algorithm.init_state(w),
                 jnp.zeros((self._tail_n(),) + w.shape, w.dtype))
        if self._watchdog:
            carry = carry + (jnp.int32(-1),)
        return carry

    def _load(self, directory: str, *, retries: int = 0, backoff: float = 0.0):
        """Newest INTACT checkpoint (corrupt ones are skipped — §13), with
        optional transient-I/O retries; raises FileNotFoundError when the
        directory holds no checkpoints at all."""

        def template(step):
            return {
                "carry": self._carry_template(),
                "hist": tuple(jnp.zeros((step,), jnp.float32)
                              for _ in range(4)),
            }

        step, payload, meta = ckpt.load_latest_intact(
            directory, template, retries=retries, backoff=backoff)
        carry = jax.tree_util.tree_map(jnp.asarray, payload["carry"])
        hist = tuple(jnp.asarray(h) for h in payload["hist"])
        key = _key_restore(meta["key"], meta.get("key_typed", False))
        if meta.get("algorithm") not in (None, self.algorithm.name):
            raise ValueError(
                f"checkpoint was written by algorithm {meta['algorithm']!r}, "
                f"this session runs {self.algorithm.name!r}")
        return step, key, carry, hist

    # -- telemetry plumbing (§15) -----------------------------------------

    @staticmethod
    def _tap_on(tracker) -> bool:
        """The one telemetry bit that reaches the engines: a NullTracker (or
        no tracker) compiles the tap OUT entirely — the historical program."""
        return tracker is not None and not isinstance(tracker, NullTracker)

    def _ledger_fn(self):
        """Per-round cumulative privacy callable for ledger events, or None.

        Probing once at round count 1 classifies the session: non-private
        algorithms raise and get no ledger (the run proceeds untracked
        rather than erroring — observability must never kill a run).
        """
        delta = self.telemetry.ledger_delta
        if delta is None:
            return None
        try:
            self._budget_at(delta, 1)
        except (ValueError, AttributeError, TypeError):
            return None
        return lambda executed: self._budget_at(delta, executed)

    def _bytes_per_round(self) -> float | None:
        """§16 modeled communication footprint: ``4 * comm_floats(d)``.

        Static per spec (the compression plan changes per round, its SIZE
        does not), so it is computed once host-side and attached to every
        executed round event — the device payload is untouched."""
        comm = getattr(self.algorithm, "comm_floats", None)
        if comm is None:
            return None
        try:
            return 4.0 * float(comm(self.dim))
        except (TypeError, ValueError):
            return None

    def _tap_session(self, tracker, start_round: int) -> "_tap_mod.TapSession":
        return _tap_mod.TapSession(
            tracker, start_round=start_round, ledger_fn=self._ledger_fn(),
            faults_active=self.fault is not None and self.fault.injects,
            bytes_per_round=self._bytes_per_round(), call=self._calls)

    # -- entry points ------------------------------------------------------

    def run(self, key: jax.Array, *, tracker: Tracker | None = None,
            checkpoint_dir: str | None = None,
            checkpoint_every: int | None = None,
            on_divergence: RecoveryPolicy | None = None) -> RunResult:
        """Run all ``train.rounds`` rounds from round 0.

        ``tracker`` streams per-round §15 telemetry (η, metric on cadence,
        clip, realized cohort, fault totals, wall-clock, cumulative privacy
        ledger) to the sink while the compiled engines run.  Results are
        bit-identical to the untracked run; ``None`` or a ``NullTracker``
        compiles the tap out entirely.

        ``checkpoint_dir`` saves the full resumable state (carry + histories
        + RNG key + round counter) every ``checkpoint_every`` rounds (plus
        once at the end); ``resume`` picks it up bit-exactly.

        ``on_divergence`` (requires ``checkpoint_dir`` and an armed
        ``FaultSpec(watchdog=True)``) auto-recovers a watchdog-tripped run:
        roll back to the newest intact checkpoint, back off, re-run — see
        ``RecoveryPolicy`` and DESIGN.md §13.  Retried rounds join the
        privacy composition reported by ``privacy_report`` (and charge the
        live ledger), and each rollback is logged as a tracker event.
        """
        self._calls += 1
        with jax.profiler.TraceAnnotation(spans.RUN, call=self._calls):
            self._validate_cohort(self.num_clients)
            if checkpoint_every is not None and checkpoint_dir is None:
                raise ValueError("checkpoint_every requires checkpoint_dir "
                                 "(nothing would be saved)")
            if on_divergence is not None:
                if not self._watchdog:
                    raise ValueError(
                        "on_divergence requires FaultSpec(watchdog=True) — "
                        "without the watchdog a diverged run never trips")
                if checkpoint_dir is None:
                    raise ValueError("on_divergence requires checkpoint_dir "
                                     "(rollback needs a checkpoint target)")
            if not self._tap_on(tracker):
                return self._run_dispatch(key, checkpoint_dir, checkpoint_every,
                                          on_divergence, tap=False)
            _tap_mod.install(self._tap_session(tracker, 0))
            tracker.start_phase("run", 0)
            try:
                return self._run_dispatch(key, checkpoint_dir, checkpoint_every,
                                          on_divergence, tap=True)
            finally:
                # flush every in-flight io_callback BEFORE detaching the
                # session, so no emission lands after finish()
                with jax.profiler.TraceAnnotation(spans.FLUSH):
                    jax.effects_barrier()
                _tap_mod.uninstall()
                tracker.finish()

    def _run_dispatch(self, key, checkpoint_dir, checkpoint_every,
                      on_divergence, *, tap: bool) -> RunResult:
        """Engine dispatch shared by tracked and untracked ``run``."""
        if self.engine.engine == "eager":
            if self.shard.mesh is not None:
                raise ValueError("client sharding requires engine='scan'")
            if checkpoint_dir is not None:
                raise ValueError("checkpointing requires engine='scan'")
            t = self.train
            out = _srv._run_eager(
                self.algorithm, self._local_fn, self._w0, self.client_batches,
                rounds=t.rounds, eta_l=t.eta_l, key=key,
                eval_fn=self.eval_fn, avg_last=t.avg_last,
                eval_every=t.eval_every, cohort=self.cohort,
                fault=self.fault, tau=int(t.tau), tap=tap)
            out.final_w = self._restore_params(out.final_w)
            out.last_w = self._restore_params(out.last_w)
            return out
        return self._run_scan(key, start=0, carry=None, hist=[],
                              checkpoint_dir=checkpoint_dir,
                              checkpoint_every=checkpoint_every,
                              on_divergence=on_divergence, tap=tap)

    def resume(self, checkpoint_dir: str, *,
               checkpoint_every: int | None = None,
               tracker: Tracker | None = None) -> RunResult:
        """Continue the latest checkpoint in ``checkpoint_dir`` up to
        ``train.rounds`` and return the FULL RunResult (pre-checkpoint
        histories included) — bit-exactly what the uninterrupted run with the
        same chunk boundaries returns.

        A ``tracker`` is told the resume round (``start_phase('resume',
        step)``) and receives events for the RESUMED rounds only — never a
        duplicate of a round the checkpointed run already emitted; the
        cumulative ledger still counts from round 0.
        """
        self._calls += 1
        with jax.profiler.TraceAnnotation(spans.RUN, call=self._calls):
            self._validate_cohort(self.num_clients)
            step, key, carry, hist = self._load(checkpoint_dir)
            if step > self.train.rounds:
                raise ValueError(f"checkpoint is at round {step}, past this "
                                 f"session's train.rounds={self.train.rounds}")
            if step == self.train.rounds:
                return self._assemble(carry, [hist])
            if not self._tap_on(tracker):
                return self._run_scan(key, start=step, carry=carry, hist=[hist],
                                      checkpoint_dir=checkpoint_dir,
                                      checkpoint_every=checkpoint_every)
            _tap_mod.install(self._tap_session(tracker, step))
            tracker.start_phase("resume", step)
            try:
                return self._run_scan(key, start=step, carry=carry, hist=[hist],
                                      checkpoint_dir=checkpoint_dir,
                                      checkpoint_every=checkpoint_every, tap=True)
            finally:
                with jax.profiler.TraceAnnotation(spans.FLUSH):
                    jax.effects_barrier()
                _tap_mod.uninstall()
                tracker.finish()

    def run_batched(self, keys: jax.Array, *, batched_w0: bool = False,
                    batched_data: bool = False,
                    tracker: Tracker | None = None) -> RunResult:
        """One batched program over S seeds (``keys`` is (S,)-stacked PRNG
        keys); set ``batched_w0`` / ``batched_data`` when w0 / client_batches
        carry a matching leading seed axis.  Every RunResult field gains a
        leading (S,) axis.  The mesh shards the client axis exactly as in
        ``run`` (seeds stay vmapped inside each shard).  The batched engine
        is always one full-length scan program (``chunk_rounds`` /
        ``scan_unroll`` do not apply); it has no eager counterpart.

        A ``tracker`` fans out to per-seed sub-trackers (events gain a
        ``"seed"`` field).  The stream path streams live per seed; the
        vmapped scan path has no per-round host hook (a tap inside vmap
        would serialize the seed axis), so its events are REPLAYED from the
        returned histories after the program finishes — same schema, minus
        wall-clock timing and fault fields.
        """
        if self.fault is not None:
            raise ValueError(
                "run_batched has no fault-injection/watchdog support; run "
                "seeds through run() when a FaultSpec is active (a silently "
                "fault-free sweep would misreport the fault model)")
        if self.engine.engine == "stream":
            # streamed seed sweep: the seeds run SEQUENTIALLY through the one
            # compiled stream program (this session's cache entry compiles on
            # the first seed and hits on the rest) — a vmapped stream would
            # multiply peak chunk memory by S, defeating the engine's point.
            # Results match per-seed run() bit-for-bit by construction.
            if batched_w0 or batched_data:
                raise ValueError(
                    "run_batched(engine='stream') sweeps seeds through one "
                    "compiled stream program; per-seed w0/data axes are not "
                    "supported — loop run() with per-seed sessions instead")
            results = [
                self.run(k, tracker=tracker.sub(i) if self._tap_on(tracker)
                         else None)
                for i, k in enumerate(keys)]

            def stack(field: str):
                vals = [getattr(r, field) for r in results]
                return jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *vals)

            return RunResult(final_w=stack("final_w"),
                             last_w=stack("last_w"),
                             eta_history=stack("eta_history"),
                             metric_history=stack("metric_history"),
                             eta_naive_history=stack("eta_naive_history"),
                             eta_target_history=stack("eta_target_history"))
        if self.engine.engine != "scan":
            raise ValueError(
                f"run_batched has no {self.engine.engine!r} engine; use "
                "engine='scan' (the default) or loop run() — a batched eager "
                "loop is just a Python loop over run()")
        if batched_w0 and self._unravel is not None:
            raise ValueError(
                "batched_w0 with a pytree model is ambiguous (the seed axis "
                "would be raveled into the parameters); stack flat vectors "
                "via flatten_model and unravel per seed instead")
        # with batched_data the client axis is 1 (seed axis leads)
        self._validate_cohort(jax.tree_util.tree_leaves(
            self.client_batches)[0].shape[1 if batched_data else 0])
        t, s = self.train, self.shard
        tail_n = self._tail_n()
        ts = jnp.arange(t.rounds, dtype=jnp.int32)
        eta_l = jnp.float32(t.eta_l)
        if s.mesh is not None:
            client_axis_pos = 1 if batched_data else 0
            m_true = jax.tree_util.tree_leaves(
                self.client_batches)[0].shape[client_axis_pos]
            batches, mask = pad_cohort(self.client_batches,
                                       s.mesh.shape[s.client_axis],
                                       axis=client_axis_pos)
            leaves, treedef = jax.tree_util.tree_flatten(batches)
            fn = _srv._sharded_batched_fn(
                self.algorithm, self._local_fn, self.eval_fn, tail_n,
                bool(batched_w0), bool(batched_data), s.mesh, s.client_axis,
                treedef, tuple(x.ndim for x in leaves), mask.shape[0], m_true,
                t.eval_every, self.cohort)
            final_w, last_w, etas, metrics, naives, targets = fn(
                self._w0, keys, batches, mask, eta_l, ts)
        else:
            fn = _srv._batched_run_fn(
                self.algorithm, self._local_fn, self.eval_fn, tail_n,
                bool(batched_w0), bool(batched_data), t.eval_every, self.cohort)
            final_w, last_w, etas, metrics, naives, targets = fn(
                self._w0, keys, self.client_batches, eta_l, ts)
        result = RunResult(final_w=self._restore_batched(final_w),
                           last_w=self._restore_batched(last_w),
                           eta_history=etas, metric_history=metrics,
                           eta_naive_history=naives,
                           eta_target_history=targets)
        if self._tap_on(tracker):
            self._replay_batched(tracker, result)
        return result

    def _replay_batched(self, tracker: "Tracker", result: RunResult) -> None:
        """Post-hoc per-seed event replay for the vmapped scan path (§15)."""
        import math as _math
        ledger = self._ledger_fn()
        bytes_pr = self._bytes_per_round()
        etas = np.asarray(jax.device_get(result.eta_history))
        metrics = np.asarray(jax.device_get(result.metric_history))
        naives = np.asarray(jax.device_get(result.eta_naive_history))
        targets = np.asarray(jax.device_get(result.eta_target_history))
        for i in range(etas.shape[0]):
            sub = tracker.sub(i)
            sub.start_phase("replay", 0)
            for t in range(etas.shape[1]):
                event = {"eta": float(etas[i, t]),
                         "eta_naive": float(naives[i, t]),
                         "eta_target": float(targets[i, t])}
                if bytes_pr is not None:
                    event["bytes_per_round"] = bytes_pr
                if _math.isfinite(float(metrics[i, t])):
                    event["metric"] = float(metrics[i, t])
                if ledger is not None:
                    rep = ledger(t + 1)
                    event.update(ledger_rounds=t + 1, mu=float(rep.mu),
                                 eps=float(rep.eps_numerical),
                                 eps_rdp=float(rep.eps_rdp))
                sub.log(t, event)
        tracker.finish()

    def spec_identity(self) -> str:
        """One-line frozen-spec identity string for run manifests (§15).

        Deterministic across processes for one configuration: the frozen
        specs repr their fields; the mesh contributes only its axis shape
        (device objects are process-local).  ``launch/dryrun`` records this
        so a launched run is attributable to its exact spec set.
        """
        s = self.shard
        mesh = ("none" if s.mesh is None else ",".join(
            f"{k}={v}" for k, v in sorted(dict(s.mesh.shape).items())))
        parts = [
            f"algorithm={self.algorithm.name}",
            f"train={self.train!r}",
            f"local={self.local!r}",
            f"engine={self.engine!r}",
            f"stream={self.stream!r}",
            f"cohort={(self.cohort if self.cohort is not None else CohortSpec())!r}",
            f"fault={(self.fault if self.fault is not None else FaultSpec())!r}",
            f"data={self.data!r}",
            f"telemetry={self.telemetry!r}",
            f"shard=mesh[{mesh}] axis={s.client_axis}",
        ]
        return " | ".join(parts)

    def privacy_report(self, delta: float) -> accounting.PrivacyReport:
        """Privacy budget of this session's full run, amplification-aware.

        CDP algorithms compose over ``train.rounds`` with the cohort's
        per-round sampling rate feeding the subsampled-GDP accounting
        (``accounting.cdp_budget(sampling_q=...)`` — conditional-sensitivity
        inflation plus CLT amplification, see its docstring); LDP reports are
        per-release (local guarantees do not amplify under central
        subsampling of who participates).  Raises for non-private algorithms.
        The sampling rate uses ``self.num_clients`` — construct the session
        with an explicit ``num_clients=`` when client data carries a leading
        seed axis (``run_batched(batched_data=True)``).

        Faults enter the accounting in both directions (DESIGN.md §13): the
        per-round rate is the REALIZED participation q * (1 - dropout) (a
        dropped client's data never touches the release), and every round
        re-executed by ``run(on_divergence=...)`` recovery joins the
        composition — call after ``run`` to fold that run's retries in.
        """
        return self._budget_at(delta, self.train.rounds + self._rounds_retried)

    def _budget_at(self, delta: float, rounds: int) -> accounting.PrivacyReport:
        """``privacy_report`` at an explicit executed-round count.

        The live telemetry ledger (§15) calls this every round with the
        rounds executed SO FAR (retries included), so the streamed ε/μ
        curve composes exactly like the end-of-run report — the final
        ledger entry equals ``privacy_report(delta)`` by construction.
        """
        alg = self.algorithm
        q = 1.0 if self.cohort is None else self.cohort.sampling_rate(self.num_clients)
        dropout = (self.fault.dropout
                   if self.fault is not None and self.fault.injects else 0.0)
        q = accounting.realized_participation(q, dropout)
        if hasattr(alg, "budget"):
            # composed algorithms (DESIGN.md §11): the mechanism owns its
            # accounting; the hook reproduces the name-dispatch below exactly
            # for every legacy registry name (pinned by tests/test_session.py)
            return alg.budget(delta, rounds=rounds, dim=self.dim,
                              sampling_q=q)
        name = alg.name
        if name in ("dp-fedavg-ldp-gauss", "ldp-fedexp-gauss"):
            return accounting.ldp_gaussian_budget(alg.clip_norm, alg.sigma, delta)
        if name in ("dp-fedavg-privunit", "ldp-fedexp-privunit"):
            return accounting.privunit_budget(alg.eps0, alg.eps1, alg.eps2)
        if name == "cdp-fedexp":
            sigma_xi = (alg.sigma_xi if alg.sigma_xi is not None
                        else self.dim * alg.sigma**2 / alg.num_clients)
            return accounting.cdp_budget(alg.clip_norm, alg.sigma,
                                         alg.num_clients, rounds,
                                         delta, sigma_xi=sigma_xi, sampling_q=q)
        if name in ("dp-fedavg-cdp", "dp-fedadam-cdp"):
            return accounting.cdp_budget(alg.clip_norm, alg.sigma,
                                         alg.num_clients, rounds,
                                         delta, sampling_q=q)
        if name == "cdp-fedexp-adaptive-clip":
            # single source of truth for the z-tracking accounting (the
            # 1/sqrt(q) realized-cohort inflation) lives on the mechanism
            from repro.core.compose import CentralGaussian
            return CentralGaussian(z_mult=alg.z_mult,
                                   num_clients=alg.num_clients).budget(
                delta, rounds=rounds, dim=self.dim,
                sampling_q=q, with_numerator=True)
        raise ValueError(f"{name!r} is not a private algorithm")

    # -- scan-engine internals --------------------------------------------

    @staticmethod
    def _cat_hist(outs):
        """Concatenate per-chunk history tuples (length-0 arrays when empty)."""
        return tuple(
            jnp.concatenate([jnp.asarray(o[i]) for o in outs])
            if outs else jnp.zeros((0,), jnp.float32)
            for i in range(4))

    def _assemble(self, carry, outs) -> RunResult:
        with jax.profiler.TraceAnnotation(spans.ASSEMBLE):
            etas, metrics, naives, targets = self._cat_hist(outs)
            if len(carry) == 4:  # watchdog carry (§13)
                w_last, _, tail, fault_t = carry
                ft = int(jax.device_get(fault_t))
                fault_round = ft if ft >= 0 else None
            else:
                w_last, _, tail = carry
                fault_round = None
            return RunResult(
                final_w=self._restore_params(jnp.mean(tail, axis=0)),
                last_w=self._restore_params(w_last),
                eta_history=etas,
                metric_history=metrics,
                eta_naive_history=naives,
                eta_target_history=targets,
                fault_round=fault_round,
            )

    def _initial_carry(self, donate: bool):
        """Round-0 carry (w, algorithm state, tail window)."""
        # Donation would consume the caller's w0 buffer; hand a copy.
        w = jnp.array(self._w0, copy=True) if donate else jnp.asarray(self._w0)
        return (w, self.algorithm.init_state(w),
                jnp.zeros((self._tail_n(),) + w.shape, w.dtype))

    def lower(self, key: jax.Array, *, tap: bool = False) -> jax.stages.Lowered:
        """Lower, without running, the first compiled round program that
        ``run(key)`` executes (the chunk starting at round 0), e.g. to read
        its HLO: the untracked program, or with ``tap`` the one a run with a
        tracker executes (§15).  The eager engine and host-resident sources
        run no single program and raise."""
        if self.engine.engine == "eager" or self._source is not None:
            raise ValueError("lower() needs a compiled scan or stream "
                             "engine on device-resident data")
        t = self.train
        donate = self._donate()
        carry = self._initial_carry(donate)
        if self._watchdog:
            carry = carry + (jnp.int32(-1),)
        fn, batches, extra = self._chunk_callable(donate, tap=tap)
        start, stop = self._chunk_bounds(0, t.rounds, self.engine.chunk_rounds,
                                         None, self.telemetry.profile_rounds)[0]
        return fn.lower(carry, key, jnp.arange(start, stop, dtype=jnp.int32),
                        batches, *extra, jnp.float32(t.eta_l))

    def _run_scan(self, key, *, start: int, carry, hist,
                  checkpoint_dir: str | None,
                  checkpoint_every: int | None,
                  on_divergence: RecoveryPolicy | None = None,
                  tap: bool = False) -> RunResult:
        t = self.train
        policy = on_divergence
        watchdog = self._watchdog
        donate = self._donate()
        if carry is None:
            carry = self._initial_carry(donate)
        if watchdog and len(carry) == 3:
            carry = carry + (jnp.int32(-1),)
        fn, batches, extra = self._chunk_callable(donate, tap=tap)
        eta_l = jnp.float32(t.eta_l)

        # §15 profiler window: (a, b) splits chunks at a and b so the traced
        # region covers exactly rounds [a, b) of the compiled program
        profile = self.telemetry.profile_rounds
        prof_dir = self.telemetry.profile_dir
        prof_active = False

        def _prof_stop(round_edge: int) -> None:
            nonlocal prof_active
            if not prof_active:
                return
            jax.block_until_ready(carry)
            jax.profiler.stop_trace()
            prof_active = False
            sess = _tap_mod.active()
            if tap and sess is not None:
                sess.profile_event("stop", round_edge, prof_dir)

        outs = list(hist)  # resumed histories (if any) lead the concat
        if policy is not None and ckpt.latest_step(checkpoint_dir) is None:
            # a rollback target must exist before any round runs
            self._save(checkpoint_dir, start, key, carry, self._cat_hist(outs))
        bounds = self._chunk_bounds(start, t.rounds, self.engine.chunk_rounds,
                                    checkpoint_every, profile)
        retries = 0
        inject_pending = self._inject_divergence is not None
        idx = 0
        while idx < len(bounds):
            s, e = bounds[idx]
            if inject_pending:
                carry = self._inject_divergence(carry, retries)
                inject_pending = False
            if profile is not None and s == profile[0] and not prof_active:
                jax.profiler.start_trace(prof_dir)
                prof_active = True
                sess = _tap_mod.active()
                if tap and sess is not None:
                    sess.profile_event("start", s, prof_dir)
            with jax.profiler.TraceAnnotation(spans.DISPATCH,
                                              call=self._calls,
                                              rounds=f"{s}:{e}"):
                carry, chunk_outs = fn(carry, key,
                                       jnp.arange(s, e, dtype=jnp.int32),
                                       batches, *extra, eta_l)
            fault_t = int(jax.device_get(carry[3])) if watchdog else -1
            if prof_active and e >= min(profile[1], t.rounds):
                _prof_stop(e)
            if fault_t >= 0 and policy is not None \
                    and retries < policy.max_retries:
                # rollback: newest intact checkpoint, backoff, re-run.  The
                # rounds past the rollback step were EXECUTED (their releases
                # happened) and will re-run — they join the privacy
                # composition (privacy_report)
                _prof_stop(e)  # never leave a trace spanning a rollback
                retries += 1
                if policy.backoff > 0.0:
                    time.sleep(policy.backoff * retries)
                step, key, carry, restored = self._load(
                    checkpoint_dir, retries=2, backoff=policy.backoff)
                self._rounds_retried += fault_t + 1 - step
                if tap:
                    # flush the doomed chunk's emissions, then rewind the
                    # reorder buffer so re-run rounds deliver again; the
                    # executed count keeps the rolled-back rounds (§13)
                    jax.effects_barrier()
                    sess = _tap_mod.active()
                    if sess is not None:
                        sess.rollback(step, fault_t, retries)
                outs = [restored]
                bounds = self._chunk_bounds(step, t.rounds,
                                            self.engine.chunk_rounds,
                                            checkpoint_every, profile)
                idx = 0
                inject_pending = self._inject_divergence is not None
                continue
            outs.append(chunk_outs)
            # never persist a tripped carry — the rollback target must stay
            # the last HEALTHY state
            if checkpoint_dir is not None and fault_t < 0 and (
                    e == t.rounds
                    or (checkpoint_every and e % checkpoint_every == 0)):
                self._save(checkpoint_dir, e, key, carry,
                           self._cat_hist(outs))
            idx += 1
        return self._assemble(carry, outs)


def _key_data(key):
    """(raw uint32 key data, was_typed) for old- and new-style PRNG keys."""
    if jnp.issubdtype(jnp.asarray(key).dtype, jax.dtypes.prng_key):
        return jax.device_get(jax.random.key_data(key)), True
    return jax.device_get(jnp.asarray(key)), False


def _key_restore(data, typed: bool):
    arr = jnp.asarray(data, dtype=jnp.uint32)
    return jax.random.wrap_key_data(arr) if typed else arr
