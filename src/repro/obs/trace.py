"""Phase-level wall-clock tracing + jit recompilation detector.

``scope("train_round")`` times a phase of the round lifecycle, placed
on the wall clock and under its enclosing scope, with an optional
``jax.block_until_ready`` boundary (register the phase's device outputs
with ``sc.block(...)``) so the recorded duration is real compute, not
dispatch time.  ``round_scope(t)`` tags everything inside
with the round number and arms the optional ``jax.profiler`` capture
when ``t == session.profile_round``.

``retrace_probe(name)`` wraps a python callable that is about to be
``jax.jit``-ed: the wrapper body only runs when jax TRACES the function
(a jit cache miss), so each execution of the wrapper is exactly one
(re)compilation.  Counts are kept globally (``retrace_counts()``) and
per session; a session flags a step that retraces ``retrace_storm``
times as a silent retrace storm.  The probe adds zero device work and
zero per-dispatch host work — cache hits never enter the wrapper.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import os
import time
import warnings
from typing import Callable, Dict, Optional

from . import core


# ------------------------------------------------------------ phase scope
class _Scope:
    """Handle yielded by :func:`scope`; collects device values to block
    on at phase exit so the timing closes over finished compute."""

    def __init__(self) -> None:
        self._block: list = []

    def block(self, *values):
        """Register device values (arrays / pytrees) to
        ``block_until_ready`` at scope exit.  Returns the single value
        (or the tuple) for inline use."""
        self._block.extend(values)
        return values[0] if len(values) == 1 else values


@contextlib.contextmanager
def scope(name: str, **tags):
    """Time a phase; emits one ``kind: phase`` event with ``dur_s``
    (``perf_counter``), its start and end ``t0_ns`` / ``t1_ns`` on the
    wall clock (``time.time_ns()``) and ``parent``, the enclosing open
    scope's name.  Blocks on the device only for values registered
    with ``sc.block(...)``.

    Without an active session: zero work — yields an inert handle and
    never touches the clock or the device.
    """
    sc = _Scope()
    sess = core.active_session()
    if sess is None:
        yield sc
        return
    parent = sess.parent()
    sess.stack.append(name)
    t0_ns = time.time_ns()
    t0 = time.perf_counter()
    try:
        yield sc
    finally:
        if sc._block:
            import jax
            jax.block_until_ready(sc._block)
        dur = time.perf_counter() - t0
        t1_ns = time.time_ns()
        sess.stack.pop()
        sess.emit("phase", name, dur_s=dur, t0_ns=t0_ns, t1_ns=t1_ns,
                  parent=parent, **tags)


@contextlib.contextmanager
def round_scope(t: int, **tags):
    """Tag the block's events with ``round=t``; start/stop the
    session's ``jax.profiler`` trace capture when ``t`` is the armed
    ``profile_round``."""
    sess = core.active_session()
    if sess is None:
        yield
        return
    profile = (sess.profile_round is not None and t == sess.profile_round
               and not sess.profiling)
    if profile:
        import jax
        os.makedirs(sess.profile_dir, exist_ok=True)
        jax.profiler.start_trace(sess.profile_dir)
        sess.profiling = True
    with core.context(round=t, **tags):
        try:
            yield
        finally:
            if profile:
                import jax
                jax.profiler.stop_trace()
                sess.profiling = False
                sess.emit("event", "profile.captured",
                          dir=sess.profile_dir)


# ----------------------------------------------------- recompile detector
_RETRACE_COUNTS: Dict[str, int] = collections.Counter()


def retrace_probe(name: str, fn: Optional[Callable] = None):
    """Decorator counting (re)traces of a to-be-jitted callable.

    Use as ``jax.jit(retrace_probe("sim.fused_step")(step))`` or as a
    decorator between ``@jax.jit`` and the ``def``.  The wrapper body
    executes only when jax traces the function, i.e. once per jit
    cache entry — each execution is one compilation of ``name``.
    """

    def deco(f: Callable) -> Callable:
        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            _RETRACE_COUNTS[name] += 1
            sess = core.active_session()
            if sess is not None:
                n = sess.retraces[name] = sess.retraces.get(name, 0) + 1
                storm = n >= sess.retrace_storm
                sess.emit("retrace", name, count=n, storm=storm)
                if n == sess.retrace_storm:
                    warnings.warn(
                        f"obs: {name!r} traced {n} times this session "
                        "— possible silent retrace storm (changing "
                        "shapes/dtypes or python-object hashing on a "
                        "hot step function)", stacklevel=2)
            return f(*args, **kwargs)
        return wrapper

    return deco(fn) if fn is not None else deco


def retrace_counts() -> Dict[str, int]:
    """Global (process-lifetime) trace counts per probed name."""
    return dict(_RETRACE_COUNTS)


def reset_retrace_counts() -> None:
    _RETRACE_COUNTS.clear()
