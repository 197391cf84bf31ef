"""repro.obs core — structured, jit-safe telemetry (DESIGN.md §10).

One event = one flat JSON object.  Common envelope:

    ts      float   host wall-clock (time.time()) at emission
    kind    str     "event" | "phase" | "jit" | "counter" | "retrace"
                    | "session"
    name    str     dotted event name ("engine.round", "phy.solve", ...)
    ...             scalar payload fields + the active context tags

A ``phase`` event (``trace.scope``, and the ``jax.compile`` span of each
backend compile while a session is active) also carries ``dur_s``,
``t0_ns`` / ``t1_ns`` on the wall clock (``time.time_ns()``, the clock a
profiler trace records its start on) and ``parent``, the name of the
enclosing open scope (None at the top).

Sinks: an in-memory list (``ObsSession.events``, for tests and
programmatic consumers) and a JSONL file (one event per line — what
``python -m repro.obs.report`` renders).

The jit-safety contract, in one paragraph: host-side emission
(:func:`record`, :func:`counter`, ``trace.scope``) never touches device
state.  In-jit emission (:func:`jit_tap`) is gated at TRACE time — if
no session with ``jit_stream=True`` is active when the surrounding
function is traced, *nothing* is staged and the compiled program is
bit-identical to uninstrumented code (zero extra ops, zero extra
dispatches; asserted by tests/test_obs.py).  When a session IS active
at trace time, each tap site stages exactly one ``jax.debug.callback``
whose values stream to the host off the hot path (no blocking
round-trip inside the step); delivery re-resolves the active session
when the compiled step actually runs, so a step traced under one
session keeps reporting to whichever session drives later runs (and
drops events when none is active).

Public API / invariants:

* ``session(jsonl=..., memory=..., jit_stream=..., profile_round=...)``
  — the one entry point; everything else is a no-op without it.
* Emission: ``record(name, **fields)`` (host scalars),
  ``counter(name, n)`` (accumulated, flushed once at close),
  ``jit_tap(name, values)`` (in-jit, trace-time gated),
  ``enabled()`` / ``jit_stream_enabled()`` (the gates).
* Invariant 1 — zero cost when off: no active session means no staged
  ops, no host callbacks, no allocations beyond one attribute check
  per call site.
* Invariant 2 — never blocks the hot path: in-jit taps use
  ``ordered=False`` callbacks; phase scopes (repro.obs.trace) do the
  blocking at phase boundaries instead.
* Invariant 3 — the stream never holds a full tensor: array payloads
  are scalarized (0-d -> item, size <= 64 -> list, larger ->
  min/max/mean/size summary).
* Spans and counters stay in memory (and in the JSONL file, when one
  is given) until the session ends; nothing reads them back mid-run.
* Consumers: ``python -m repro.obs.report`` renders a trace
  (per-round table, phase breakdown, wire traffic, async rounds,
  counter totals, retraces); sessions nest via the module-level
  active-session slot under ``_LOCK``.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

_LOCK = threading.Lock()
_ACTIVE: Optional["ObsSession"] = None
_MISSING = object()
# jax.monitoring's time span around each backend compile (or load from
# the persistent compilation cache), with the compiled function's name
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def active_session() -> Optional["ObsSession"]:
    return _ACTIVE


def enabled() -> bool:
    """True iff an obs session is currently active."""
    return _ACTIVE is not None


def jit_stream_enabled() -> bool:
    """True iff an active session accepts in-jit taps (trace-time gate
    of :func:`jit_tap`)."""
    return _ACTIVE is not None and _ACTIVE.jit_stream


# ----------------------------------------------------------------- sinks
class MemorySink:
    """Append events to a plain list (``ObsSession.events``)."""

    def __init__(self) -> None:
        self.events: List[Dict[str, Any]] = []

    def emit(self, event: Dict[str, Any]) -> None:
        self.events.append(event)

    def close(self) -> None:
        pass


class JsonlSink:
    """One JSON object per line; the report CLI's input format."""

    def __init__(self, path: str) -> None:
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._f = open(path, "w")

    def emit(self, event: Dict[str, Any]) -> None:
        self._f.write(json.dumps(event) + "\n")

    def close(self) -> None:
        self._f.flush()
        self._f.close()


def _scalar(v: Any) -> Any:
    """JSON-ready view of a payload value: python scalars pass through,
    0-d arrays become scalars, small arrays become lists, large arrays
    are summarized (events are telemetry, not checkpoints)."""
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    a = np.asarray(v)
    if a.ndim == 0:
        return a.item()
    if a.size <= 64:
        return a.tolist()
    return {"min": float(a.min()), "max": float(a.max()),
            "mean": float(a.mean()), "size": int(a.size)}


# --------------------------------------------------------------- session
class ObsSession:
    """One telemetry session: sinks + context tags + counters.

    ``profile_round`` arms a ``jax.profiler`` trace capture around that
    round (started/stopped by ``trace.round_scope``); ``retrace_storm``
    is the per-session retrace count at which a step function is
    flagged as a silent retrace storm (``storm: true`` on the retrace
    event).
    """

    def __init__(self, jsonl: Optional[str] = None, memory: bool = True,
                 jit_stream: bool = True,
                 profile_round: Optional[int] = None,
                 profile_dir: str = "runs/profile",
                 retrace_storm: int = 3) -> None:
        self.sinks: List[Any] = []
        self.memory = MemorySink() if memory else None
        if self.memory is not None:
            self.sinks.append(self.memory)
        self.jsonl_path = jsonl
        if jsonl:
            self.sinks.append(JsonlSink(jsonl))
        if not self.sinks:
            raise ValueError("session needs a sink: jsonl= or memory=True")
        self.jit_stream = jit_stream
        self.profile_round = profile_round
        self.profile_dir = profile_dir
        self.retrace_storm = retrace_storm
        self.tags: Dict[str, Any] = {}
        self.counters: Dict[str, float] = {}
        # per-session retrace counts (trace.retrace_probe fills these;
        # the global counts in repro.obs.trace survive across sessions)
        self.retraces: Dict[str, int] = {}
        self.profiling = False
        # names of the open ``trace.scope``s, innermost last
        self.stack: List[str] = []

    def parent(self) -> Optional[str]:
        """The innermost open scope's name, or None."""
        return self.stack[-1] if self.stack else None

    def on_time_span(self, event: str, start: float, end: float,
                     **kwargs: Any) -> None:
        """``jax.monitoring`` time-span listener: each backend compile
        becomes a ``jax.compile`` phase span (``start``/``end`` are
        ``time.time()`` seconds)."""
        if event != COMPILE_EVENT:
            return
        self.emit("phase", "jax.compile", dur_s=end - start,
                  t0_ns=int(start * 1e9), t1_ns=int(end * 1e9),
                  parent=self.parent(),
                  fun_name=str(kwargs.get("fun_name", "")))

    @property
    def events(self) -> List[Dict[str, Any]]:
        if self.memory is None:
            raise ValueError("session was opened with memory=False")
        return self.memory.events

    def emit(self, kind: str, name: str, **fields: Any) -> None:
        event: Dict[str, Any] = {"ts": time.time(), "kind": kind,
                                 "name": name}
        for k, v in self.tags.items():
            event[k] = _scalar(v)
        for k, v in fields.items():
            event[k] = _scalar(v)
        with _LOCK:
            for sink in self.sinks:
                sink.emit(event)

    def close(self) -> None:
        for cname in sorted(self.counters):
            self.emit("counter", cname, total=self.counters[cname])
        for name in sorted(self.retraces):
            self.emit("retrace", name, count=self.retraces[name],
                      final=True,
                      storm=self.retraces[name] >= self.retrace_storm)
        self.emit("session", "end")
        for sink in self.sinks:
            sink.close()


@contextlib.contextmanager
def session(jsonl: Optional[str] = None, memory: bool = True,
            jit_stream: bool = True, profile_round: Optional[int] = None,
            profile_dir: str = "runs/profile", retrace_storm: int = 3):
    """Activate an obs session for the dynamic extent of the block.

    Only one session may be active at a time (the global is what makes
    instrumented library code zero-config).  Enter the session BEFORE
    the instrumented jitted steps are first traced — jit taps are a
    trace-time decision (see module docstring).
    """
    global _ACTIVE
    if _ACTIVE is not None:
        raise RuntimeError("an obs session is already active; nest "
                           "obs.context() instead of obs.session()")
    sess = ObsSession(jsonl=jsonl, memory=memory, jit_stream=jit_stream,
                      profile_round=profile_round,
                      profile_dir=profile_dir,
                      retrace_storm=retrace_storm)
    _ACTIVE = sess
    import jax  # local, as in jit_tap: importing obs touches no jax

    jax.monitoring.register_event_time_span_listener(sess.on_time_span)
    try:
        dev = jax.devices()[0]
        sess.emit("session", "start", jit_stream=jit_stream,
                  jsonl=jsonl or "", platform=dev.platform,
                  device_kind=dev.device_kind,
                  device_count=jax.device_count())
        yield sess
    finally:
        jax.monitoring.unregister_event_time_span_listener(
            sess.on_time_span)
        try:
            sess.close()
        finally:
            _ACTIVE = None


# ------------------------------------------------------------- host API
def record(name: str, **fields: Any) -> None:
    """Host-side event emission; no-op without an active session."""
    sess = _ACTIVE
    if sess is not None:
        sess.emit("event", name, **fields)


def counter(name: str, value: float = 1.0) -> None:
    """Accumulate into a named session counter (flushed as one
    ``kind: counter`` event per name when the session closes)."""
    sess = _ACTIVE
    if sess is not None:
        sess.counters[name] = sess.counters.get(name, 0.0) + float(value)


@contextlib.contextmanager
def context(**tags: Any):
    """Attach tags (scenario / quantizer / round / ...) to every event
    emitted inside the block, including jit-tap deliveries that land
    while the tagged computation runs."""
    sess = _ACTIVE
    if sess is None:
        yield
        return
    old = {k: sess.tags.get(k, _MISSING) for k in tags}
    sess.tags.update(tags)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is _MISSING:
                sess.tags.pop(k, None)
            else:
                sess.tags[k] = v


# ------------------------------------------------------------ in-jit API
def jit_tap(name: str, values: Dict[str, Any], **tags: Any) -> None:
    """Stream values out of jit-traced code via ``jax.debug.callback``.

    Call from inside a function that will be (or is being) jit-traced.
    Gated at trace time: without an active ``jit_stream`` session this
    stages NOTHING — the compiled program is bit-identical to the
    uninstrumented one.  With one, the callback delivers the values to
    whatever session is active when the compiled step executes
    (dropped if none), so recompilation is never needed to re-point
    telemetry.  Works under ``vmap``/``lax.map`` (one delivery per
    batch element / iteration) and in donated-argument jits.
    """
    if not jit_stream_enabled():
        return
    import jax

    keys = tuple(values)

    def _deliver(*vals):
        sess = _ACTIVE
        if sess is not None:
            sess.emit("jit", name, **tags, **dict(zip(keys, vals)))

    jax.debug.callback(_deliver, *[values[k] for k in keys],
                       ordered=False)
