"""Host side of the §15 engine tap.

The compiled engines emit one fixed-layout float32 vector per round through
``jax.experimental.io_callback`` (see ``fedsim/server.py``); this module is
where those device emissions become tracker events.

Ordering contract (DESIGN.md §15): non-sharded engines emit with
``ordered=True`` inside their round scan, so emissions arrive in round
order.  ``shard_map`` engines emit with ``ordered=False`` — ordered
callbacks inside shard_map are not reliable on this jax version — and EVERY
shard executes the callback, so the device passes its ``axis_index`` along
and the host (a) drops every emission with shard != 0 and (b) reorders by
round index in a buffer, delivering strictly consecutive rounds to the
tracker.  Both cases funnel through ``device_emit``.

A ``TapSession`` is installed for the duration of one ``run()`` (module
global — io_callback gives the device no way to address a specific host
object, and sessions never run concurrently in-process).  It owns:

* the reorder buffer + next-expected round (reset on §13 rollback),
* wall-clock round timing (perf_counter delta between deliveries),
* the cumulative privacy ledger (``ledger_fn(rounds_executed)`` →
  ``PrivacyReport``; retried rounds charge the ledger per §13 because every
  EXECUTED round increments the count, including rounds later rolled back),
* watchdog-freeze handling: frozen rounds (t > fault_t) emit NaN payloads
  on-device; the host logs them as frozen without charging the ledger.

The payload layout must match ``fedsim/server.py::_tap_payload`` exactly.
"""
from __future__ import annotations

import math
import time

import jax
import numpy as np

from repro.telemetry import spans

__all__ = ["TapSession", "install", "uninstall", "active", "device_emit",
           "PAYLOAD_LEN"]

# float32 payload slots (device side builds this in _tap_payload)
_ETA, _NAIVE, _TARGET, _METRIC, _CLIP, _PART, _REAL, _DROP, _STRAG, _CORR, \
    _FAULT_T, _SIGMA = range(12)
PAYLOAD_LEN = 12

_ACTIVE: "TapSession | None" = None


class TapSession:
    def __init__(self, tracker, *, start_round: int = 0, ledger_fn=None,
                 faults_active: bool = False,
                 bytes_per_round: float | None = None, call: int = 0):
        self.tracker = tracker
        self.call = int(call)  # the session's run/resume count, for spans
        self.expected_t = int(start_round)
        self.ledger_fn = ledger_fn
        self.faults_active = faults_active
        # §16 communication footprint: 4 * algorithm.comm_floats(d), STATIC
        # per spec — attached host-side to every executed round event, so the
        # device payload layout is untouched and tap-on stays bit-identical
        self.bytes_per_round = (None if bytes_per_round is None
                                else float(bytes_per_round))
        # rounds actually run (incl. later rolled back); a resume starts at
        # the checkpoint round so the cumulative ledger counts from round 0
        self.executed = int(start_round)
        self.buffer: dict[int, np.ndarray] = {}
        self._t0 = time.perf_counter()

    # -- device-facing -----------------------------------------------------
    def emit(self, t: int, shard: int, vec: np.ndarray) -> None:
        if shard != 0:
            return  # every shard fires the callback; only shard 0 reports
        self.buffer[t] = np.asarray(vec)
        # deliver any consecutive run starting at expected_t (unordered
        # shard_map emissions can arrive out of round order)
        while self.expected_t in self.buffer:
            v = self.buffer.pop(self.expected_t)
            self._deliver(self.expected_t, v)
            self.expected_t += 1

    # -- host-facing (rollback notifications from _run_scan) ---------------
    def rollback(self, to_round: int, fault_round: int, attempt: int) -> None:
        self.buffer.clear()
        self.expected_t = int(to_round)
        self._t0 = time.perf_counter()
        self.tracker.log(int(fault_round), {
            "event": "rollback", "to_round": int(to_round),
            "attempt": int(attempt)})

    def profile_event(self, action: str, round_: int, trace_dir: str) -> None:
        self.tracker.log(int(round_), {
            "event": f"profile_{action}", "trace_dir": trace_dir})

    # -- internals ----------------------------------------------------------
    def _deliver(self, t: int, v: np.ndarray) -> None:
        now = time.perf_counter()
        dt, self._t0 = now - self._t0, now
        ft = int(v[_FAULT_T]) if math.isfinite(float(v[_FAULT_T])) else -1
        frozen = ft >= 0 and t > ft
        event = {"round_time_s": dt}
        if frozen:
            # watchdog froze the carry at fault_t; this round did not run
            event["frozen"] = True
            event["watchdog_fault_round"] = ft
            self._log(t, event)
            return
        self.executed += 1
        event.update(
            eta=float(v[_ETA]), eta_naive=float(v[_NAIVE]),
            eta_target=float(v[_TARGET]))
        if self.bytes_per_round is not None:
            event["bytes_per_round"] = self.bytes_per_round
        if math.isfinite(float(v[_METRIC])):
            event["metric"] = float(v[_METRIC])
        if math.isfinite(float(v[_CLIP])):
            event["clip"] = float(v[_CLIP])
        if len(v) > _SIGMA and math.isfinite(float(v[_SIGMA])):
            # §17 per-round noise std: round-indexed schedules emit sigma(t);
            # fixed-sigma releases emit the constant; mechanisms with no
            # shared noise std (NoPrivacy, PrivUnit, heterogeneous
            # per-client) emit NaN and the field is omitted
            event["sigma"] = float(v[_SIGMA])
        event["participants"] = int(v[_PART])
        if self.faults_active:
            event.update(
                realized_clients=int(v[_REAL]), dropped=int(v[_DROP]),
                stragglers=int(v[_STRAG]), corrupt=int(v[_CORR]))
        if ft >= 0:
            event["watchdog_fault_round"] = ft
        if self.ledger_fn is not None:
            # observability must never kill a run: an accounting failure
            # surfaces once as an event field and disables the ledger
            try:
                with jax.profiler.TraceAnnotation(spans.LEDGER):
                    rep = self.ledger_fn(self.executed)
            except Exception as e:  # noqa: BLE001 - deliberate firewall
                event["ledger_error"] = repr(e)
                self.ledger_fn = None
            else:
                event.update(
                    ledger_rounds=self.executed, mu=float(rep.mu),
                    eps=float(rep.eps_numerical), eps_rdp=float(rep.eps_rdp))
        self._log(t, event)

    def _log(self, t: int, event: dict) -> None:
        with jax.profiler.TraceAnnotation(spans.LOG):
            self.tracker.log(t, event)


def install(session: TapSession) -> None:
    global _ACTIVE
    if _ACTIVE is not None:
        raise RuntimeError("a telemetry TapSession is already active; "
                           "sessions may not run concurrently in-process")
    _ACTIVE = session


def uninstall() -> None:
    global _ACTIVE
    _ACTIVE = None


def active() -> "TapSession | None":
    return _ACTIVE


def device_emit(t, shard, vec) -> None:
    """The io_callback target.  A late callback flushed after uninstall()
    (jax.effects_barrier runs before uninstall, so this is belt-and-braces)
    is dropped rather than crashed on."""
    s = _ACTIVE
    if s is not None:
        with jax.profiler.TraceAnnotation(spans.EMIT, call=s.call,
                                          round=int(t)):
            s.emit(int(t), int(shard), np.asarray(vec))
