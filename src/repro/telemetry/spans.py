"""Names of the round's layers, as the profiler sees them (DESIGN.md §15).

Device scopes are ``jax.named_scope`` names: metadata in the compiled HLO
(``metadata={op_name=".../fedsim.release/..."}``), so they cost nothing at
run time.  Every engine (scan, stream, gather-stream, sharded and the
host-resident driver) opens them at the same boundaries.  A profiler trace's
device ops carry no ``op_name``; a reader maps each op to its scope through
the compiled program's HLO text (``FederatedSession.lower(key, tap=...)``).
An op belongs to the first ``fedsim.*`` component of its ``op_name``.

Host spans are ``jax.profiler.TraceAnnotation`` names, on the profiler's
clock: the same clock as the device planes.  They cost one flag check each
while no trace is being taken.

Operators see both in any ``jax.profiler`` trace of a run, including the
window of ``TelemetrySpec(profile_rounds=(a, b))``.  The benchmark's layer
breakdown (``chipbench/layers.py``, through ``chipbench/scopes.py``) reads
them as the metrics named below; the spans it does not turn into a metric
name the idle gaps and the host work on its stderr.

===================  =====  ==================================  =============================
name                 kind   what it covers                      read as
===================  =====  ==================================  =============================
fedsim.local_update  scope  the clients' local training         ``local_update_ms_per_round``
                            (``local_fn``)
fedsim.release       scope  clip, noise and aggregate: the      ``release_us_per_round``
                            mechanism's release / moments /
                            finalize, the compressed noise,
                            the ``dp_aggregate`` kernel and
                            its pad
fedsim.server_step   scope  the global step: eta and the        ``server_step_us_per_round``
                            server apply
fedsim.eval          scope  the per-round eval metric           stderr breakdown
fedsim.tap           scope  the telemetry payload and its       ``tap_ms_per_round``
                            ``io_callback``
fedsim.psum          scope  the cross-shard ``psum`` of the     stderr, sharded engines
                            moments
fedsim.run           span   one ``run()`` / ``resume()``        idle-gap names
                            (``call``)
fedsim.dispatch      span   one chunk program call (``rounds``  idle-gap names
                            ``s:e``); per round inside it on
                            the host driver
fedsim.assemble      span   the ``RunResult`` from the chunks'  idle-gap names
                            outputs
telemetry.flush      span   the ``effects_barrier`` ending a    idle-gap names
                            tracked run
telemetry.emit       span   one round's ``device_emit``         ``tap_host_ms_per_round``
                            (``round``)
telemetry.ledger     span   the privacy ledger, inside          stderr breakdown
                            ``telemetry.emit``
telemetry.log        span   ``tracker.log``, inside             stderr breakdown
                            ``telemetry.emit``
===================  =====  ==================================  =============================
"""
from __future__ import annotations

# device scopes
LOCAL_UPDATE = "fedsim.local_update"
RELEASE = "fedsim.release"
SERVER_STEP = "fedsim.server_step"
EVAL = "fedsim.eval"
TAP = "fedsim.tap"
PSUM = "fedsim.psum"

# host spans
RUN = "fedsim.run"
DISPATCH = "fedsim.dispatch"
ASSEMBLE = "fedsim.assemble"
FLUSH = "telemetry.flush"
EMIT = "telemetry.emit"
LEDGER = "telemetry.ledger"
LOG = "telemetry.log"
SPANS = (RUN, DISPATCH, ASSEMBLE, FLUSH, EMIT, LEDGER, LOG)
