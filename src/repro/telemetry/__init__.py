"""repro.telemetry — streaming round trackers for long runs (DESIGN.md §15).

Public surface: the ``Tracker`` protocol and its concrete sinks.  The engine
tap internals live in ``repro.telemetry.tap`` and are wired by
``fedsim/session.py``; user code only ever constructs a tracker and passes
it to ``FederatedSession.run(tracker=...)``.

``repro.telemetry.spans`` names the round's layers as a profiler trace
shows them (any ``jax.profiler`` trace, e.g. ``TelemetrySpec(profile_rounds=)``):
device scopes ``fedsim.local_update``, ``fedsim.release``,
``fedsim.server_step``, ``fedsim.eval``, ``fedsim.tap`` and ``fedsim.psum``
in the compiled program's ``op_name`` metadata, and host spans
``fedsim.run``, ``fedsim.dispatch``, ``fedsim.assemble``,
``telemetry.flush``, ``telemetry.emit``, ``telemetry.ledger`` and
``telemetry.log``.  Its docstring says what each covers and what reads it.
"""
from repro.telemetry.trackers import (
    CompositeTracker,
    JsonlTracker,
    NullTracker,
    StdoutTracker,
    Tracker,
    WandbTracker,
)

__all__ = ["Tracker", "NullTracker", "StdoutTracker", "JsonlTracker",
           "CompositeTracker", "WandbTracker"]
