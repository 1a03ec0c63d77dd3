"""Jit-safe solve telemetry: the ``SolveEvent`` stream.

Every layer of the stack emits events through two entry points:

  * :func:`emit` — host-side code (the solve service, the bilevel outer
    loop, caches) emits immediately;
  * :func:`jit_event` — traced code (solver bodies, the implicit-diff
    backward path) stages a ``jax.debug.callback`` so the event fires at
    *execution* time with runtime values (iteration counts, residuals),
    from inside ``jit``/``lax.while_loop``/``lax.custom_linear_solve``
    (:func:`jit_event_pair` delivers a ``*_start``/``*_done`` pair from
    one staged callback — host callbacks are the dominant enabled-mode
    cost, so pairs are never staged as two).

Both are gated by the process-level :func:`observe` switch.  The gate is
checked at **trace time**: with observability disabled (the default),
``jit_event`` returns before staging anything, so the compiled program is
bit-identical to an uninstrumented build — the disabled-mode overhead is
zero by construction (``benchmarks/obs_overhead.py`` gates it at <= 2%
against the raw solver anyway).  The flip side: programs compiled while
disabled stay uninstrumented until re-traced — enable observability
*before* building jitted functions or services you want telemetry from.

Sharded solves are instrumented at the solver-registry seam, *outside*
``shard_map`` — the callback therefore fires **once per compiled program
execution**, not once per device, and its values are the gathered global
diagnostics (asserted by the 8-device CI lane).  Per-iteration events
(``iteration_events=True``) are the one exception: they ride inside the
solver loop body, which for the sharded solvers runs per shard.

Event kinds (the schema; ``tags`` are static strings/ints fixed at trace
time, ``values`` are runtime arrays):

  ==================  =====================================================
  ``solve_start``     a registry solver begins (tags: solver, B, d, dtype,
                      mesh_size)
  ``solve``           a registry solve finished (values: iterations,
                      residual, converged — per instance)
  ``iteration``       one solver-loop step (opt-in; deep debugging)
  ``converged``       an ``IterativeSolver.run``/``run_stochastic`` outer
                      loop finished (values: iterations, error, converged)
  ``backward_start``  an implicit-diff backward/tangent solve begins
                      (tags: direction, backward mode, matvec_budget)
  ``backward_done``   ... and finished (values incl.
                      hypergrad_error_estimate when measured)
  ``dispatch``        a routing decision resolved (host, trace-time)
  ``cache_hit`` / ``cache_miss``  warm-start cache lookups (host)
  ``bilevel_step``    one outer step of ``solve_bilevel`` (host)
  ==================  =====================================================

Events fan out to: the in-memory recorder (``record=True``), registered
subscribers, the global tracer's JSONL stream (when configured), and a
metrics bridge that folds per-solve iteration counts into the global
``MetricsRegistry`` histograms.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from repro.observability import metrics as _metrics
from repro.observability import spans as _spans

__all__ = [
    "SolveEvent", "EVENT_KINDS", "observe", "observing",
    "observing_iterations", "emit", "jit_event", "jit_event_pair",
    "subscribe", "recorded", "clear_recorded",
]

EVENT_KINDS = (
    "solve_start", "solve", "iteration", "converged", "backward_start",
    "backward_done", "dispatch", "cache_hit", "cache_miss", "bilevel_step",
)


@dataclasses.dataclass(frozen=True)
class SolveEvent:
    """One telemetry event: a kind, static tags, and runtime values.

    ``t`` is ``time.perf_counter()`` at emission (host receipt time for
    ``jit_event`` — ordering within a device stream is preserved, exact
    device-side timing is not the contract).  ``tags`` are trace-time
    statics (solver name, B, d, dtype, mesh_size, backward mode);
    ``values`` are host copies of runtime arrays (iterations, residuals,
    convergence flags, error estimates).
    """
    kind: str
    t: float
    tags: Dict[str, Any]
    values: Dict[str, Any]


_lock = threading.Lock()
_enabled = False
_iteration_events = False
_recording = False
_records: list = []
_subscribers: list = []


def observing() -> bool:
    """True when the process-level observability switch is on."""
    return _enabled


def observing_iterations() -> bool:
    """True when per-iteration events are enabled (opt-in; expensive)."""
    return _enabled and _iteration_events


class _ObserveHandle:
    """Context manager restoring the prior observability configuration."""

    def __init__(self, prev_state, owns_tracer: bool):
        self._prev = prev_state
        self._owns_tracer = owns_tracer

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        global _enabled, _iteration_events, _recording
        _enabled, _iteration_events, _recording = self._prev
        if self._owns_tracer:
            _spans.remove_tracer()
        return False


def observe(enabled: bool = True, *, iteration_events: bool = False,
            record: bool = False, trace_path=None) -> _ObserveHandle:
    """Flip the process-level observability switch.

    Applies immediately; the return value doubles as a context manager
    that restores the previous configuration (and removes a tracer this
    call installed) on exit — ``with observe(enabled=True): ...`` is the
    test/benchmark idiom.

    ``iteration_events`` opts into per-loop-step events (deep debugging —
    a host callback per solver iteration; never on by default).
    ``record=True`` accumulates events in-process for :func:`recorded`.
    ``trace_path`` installs a global JSONL tracer at that path (see
    ``repro.observability.spans``), so events and spans stream to disk.

    The switch is read at trace time: functions jitted while disabled
    stay uninstrumented until re-traced (and vice versa) — enable first,
    then build the jitted functions/services you want telemetry from.
    Beware that jax's trace cache keys on callable identity: wrapping
    the SAME function object in a new ``jax.jit`` (or re-running
    ``make_jaxpr`` on it) after flipping the switch can serve the stale
    trace — build a fresh callable for a fresh trace.
    """
    global _enabled, _iteration_events, _recording
    prev = (_enabled, _iteration_events, _recording)
    _enabled = bool(enabled)
    _iteration_events = bool(iteration_events)
    _recording = bool(record)
    owns_tracer = trace_path is not None
    if owns_tracer:
        _spans.configure_tracer(trace_path)
    return _ObserveHandle(prev, owns_tracer)


def recorded() -> tuple:
    """Events captured so far under ``observe(record=True)``."""
    with _lock:
        return tuple(_records)


def clear_recorded() -> None:
    """Drop the in-process event recording buffer."""
    with _lock:
        _records.clear()


def subscribe(fn: Callable[[SolveEvent], None]) -> Callable[[], None]:
    """Register an event subscriber; returns an unsubscribe callable."""
    with _lock:
        _subscribers.append(fn)

    def unsubscribe():
        with _lock:
            if fn in _subscribers:
                _subscribers.remove(fn)

    return unsubscribe


# -- dispatch ----------------------------------------------------------------

def _host(v):
    """Copy a runtime value to host numpy (labels/strings pass through)."""
    if isinstance(v, (str, bytes, bool, int, float, type(None))):
        return v
    try:
        return np.asarray(v)
    except Exception:
        return v


def _jsonable(v):
    """Best-effort JSON-safe rendering of an event value."""
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, (np.generic,)):
        return v.item()
    return v


def _bridge_metrics(ev: SolveEvent) -> None:
    """Fold an event into the global registry (counters + histograms)."""
    reg = _metrics.global_registry()
    solver = str(ev.tags.get("solver", ""))
    reg.counter("repro_events_total",
                help="telemetry events by kind and solver",
                kind=ev.kind, solver=solver).inc()
    its = ev.values.get("iterations")
    if its is not None and ev.kind in ("solve", "converged"):
        arr = np.asarray(its, dtype=np.float64).ravel()
        arr = arr[arr >= 0]          # negative: uncounted (custom solvers)
        if arr.size:
            reg.histogram("repro_solve_iterations",
                          help="per-instance solver iteration counts",
                          buckets=_metrics.ITERATION_BUCKETS,
                          solver=solver).observe_many(arr.tolist())
    est = ev.values.get("hypergrad_error_estimate")
    if est is not None and ev.kind == "backward_done":
        arr = np.asarray(est, dtype=np.float64).ravel()
        arr = arr[np.isfinite(arr)]
        if arr.size:
            reg.histogram("repro_hypergrad_error_estimate",
                          help="relative residual of the implicit "
                               "backward system",
                          buckets=_metrics.DEFAULT_BUCKETS,
                          backward=str(ev.tags.get("backward", "")),
                          ).observe_many(arr.tolist())


def _dispatch(kind: str, tags: Dict[str, Any],
              values: Dict[str, Any]) -> None:
    """Deliver one event to every sink (recorder/metrics/tracer/subs)."""
    vals = {k: _host(v) for k, v in values.items()}
    ev = SolveEvent(kind=kind, t=time.perf_counter(), tags=dict(tags),
                    values=vals)
    with _lock:
        if _recording:
            _records.append(ev)
        subs = list(_subscribers)
    _bridge_metrics(ev)
    tr = _spans.current_tracer()
    if tr is not None:
        tr.add_event(ev.kind, ev.t, tags=ev.tags,
                     values={k: _jsonable(v) for k, v in vals.items()})
    for fn in subs:
        fn(ev)


def emit(kind: str, tags: Optional[Dict[str, Any]] = None,
         **values) -> None:
    """Emit one event from host code; no-op while observability is off."""
    if not _enabled:
        return
    _dispatch(kind, tags or {}, values)


def jit_event(kind: str, tags: Optional[Dict[str, Any]] = None,
              **values) -> None:
    """Emit one event from *traced* code, jit-safely.

    When observability is enabled at trace time, stages a
    ``jax.debug.callback`` carrying ``values`` (arrays allowed — they are
    copied to host at execution time); when disabled, returns before
    staging anything, so the compiled program is unchanged.  Safe inside
    ``jit``, ``lax.while_loop`` bodies, and ``custom_linear_solve``
    templates; place calls *outside* ``shard_map`` for once-per-program
    semantics.
    """
    if not _enabled:
        return
    cb = functools.partial(_dispatch, kind, dict(tags or {}))
    jax.debug.callback(cb, values)


def jit_event_pair(start_kind: str, end_kind: str,
                   tags: Optional[Dict[str, Any]] = None, **values) -> None:
    """Stage ONE callback delivering a start/end event pair.

    A bare ``jax.debug.callback`` costs hundreds of microseconds of
    host-sync per staged call on CPU — it dominates enabled-mode
    overhead, dwarfing anything the dispatch fan-out does.  Pairing the
    ``*_start``/``*_done`` idiom into a single callback halves that
    cost.  The start event carries tags only and shares the end event's
    host receipt time; stream *ordering* is preserved, and per-event
    host timing was never the contract (spans measure time).
    """
    if not _enabled:
        return
    start_tags, end_tags = dict(tags or {}), dict(tags or {})

    def cb(vals):
        _dispatch(start_kind, start_tags, {})
        _dispatch(end_kind, end_tags, vals)

    jax.debug.callback(cb, values)
