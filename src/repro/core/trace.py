"""Spans and counters of the hub's request path, on the profiler's clock.

``span(name, **meta)`` times a synchronous section.  While a profiler
records, it is also a ``jax.profiler.TraceAnnotation("c3o." + name,
**meta)``, an event on the same clock as the device's operations.  It
adds the section's count, total seconds and self seconds (total less the
spans recorded inside it) to the current ``Recorder``.
``interval(name, seconds)`` adds a duration measured elsewhere (a queue
wait, a request in flight) without a trace event.  ``Open`` is a traced
section that another task may close (the edge's idle time between
requests).

Each context keeps a stack of its open spans on a ``_Root`` that names
its recorder.  ``recording(rec)`` starts a new stack: a lane's worker
task starts one on its lane's own recorder, so the engine spans inside
its dispatches land on that lane, and the edge starts one per request.
Everything else lands on ``PROCESS``.  One ``jax.monitoring`` listener,
registered at import, records each lowering (``engine.lower``) and
backend compile (``engine.compile``) into the current recorder as a
child of the innermost open span, so a new shape met inside a lane tick
is charged to that lane and that span.

Spans are always on.  With no profiler running a span makes no trace
event and costs about a microsecond of Python: two clock reads, a list
push and pop, and a locked update of the recorder's totals.
"""
from __future__ import annotations

import threading
from contextvars import ContextVar
from time import perf_counter
from typing import Dict, List, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation

#: prefix of every trace event this module writes
PREFIX = "c3o."

#: one row of a recorder snapshot: (name, count, total_s, self_s)
SpanRow = Tuple[str, int, float, float]


class Recorder:
    """Count, total seconds and self seconds per span name.

    A lane's recorder is written from its worker task and, when the lane
    dispatches on an executor, from that thread too; hence the lock.
    Totals are updated in place, so recording allocates nothing that
    outlives it (the collector sees no more work)."""

    __slots__ = ("_lock", "_totals")

    def __init__(self):
        self._lock = threading.Lock()
        self._totals: Dict[str, List] = {}

    def add(self, name: str, seconds: float, self_s: float) -> None:
        with self._lock:
            row = self._totals.get(name)
            if row is None:
                self._totals[name] = [1, seconds, self_s]
            else:
                row[0] += 1
                row[1] += seconds
                row[2] += self_s

    def snapshot(self) -> Tuple[SpanRow, ...]:
        """Every name's (name, count, total_s, self_s), sorted by name."""
        with self._lock:
            return tuple((k, c, t, s)
                         for k, (c, t, s) in sorted(self._totals.items()))


#: where spans land outside any lane: set-up fits, warm-up, the edge
PROCESS = Recorder()


class _Root:
    """The bottom of a span stack: the recorder its spans land on."""

    __slots__ = ("rec", "child")

    def __init__(self, rec: Recorder):
        self.rec = rec
        self.child = 0.0


#: the open spans of this context, innermost last, above its ``_Root``.
#: A list (not a context variable per span) keeps a span to a list
#: append and pop; ``recording`` gives a task or a request a list of its
#: own, and a context without one makes its own on its first span.
_STACK: ContextVar[Optional[list]] = ContextVar("c3o_spans", default=None)


def _stack() -> list:
    st = _STACK.get()
    if st is None:
        st = [_Root(PROCESS)]
        _STACK.set(st)
    return st


class span:
    """``with span("engine.sync"): ...`` -- see the module docstring."""

    __slots__ = ("name", "meta", "child", "_stack", "_ann", "_t0")

    def __init__(self, name: str, **meta):
        self.name = name
        self.meta = meta

    def __enter__(self) -> "span":
        st = self._stack = _stack()
        st.append(self)
        self.child = 0.0
        self._ann = _annotation(self.name, self.meta) if _tracing() \
            else None
        self._t0 = perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        dt = perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        st = self._stack
        if st[-1] is self:
            st.pop()
        else:                  # a span of a task sharing this list is open
            st.remove(self)
        st[-1].child += dt
        st[0].rec.add(self.name, dt, dt - self.child)


class Open:
    """A traced section opened now and closed by ``close``, possibly
    from another task: it has no parent and no children."""

    __slots__ = ("name", "_ann", "_rec", "_t0")

    def __init__(self, name: str):
        self.name = name
        self._rec = _stack()[0].rec
        self._ann = _annotation(name, {}) if _tracing() else None
        self._t0 = perf_counter()

    def close(self) -> None:
        dt = perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._rec.add(self.name, dt, dt)


#: whether a profiler is recording now (an event opened while none is
#: would not be recorded, so none is made)
_tracing = TraceAnnotation.is_enabled


def _annotation(name: str, meta: dict) -> TraceAnnotation:
    ann = TraceAnnotation(PREFIX + name, **meta)
    ann.__enter__()
    return ann


def interval(name: str, seconds: float) -> None:
    """Add a duration measured elsewhere to the current recorder."""
    _stack()[0].rec.add(name, seconds, seconds)


class recording:
    """``with recording(rec):`` spans in this context land on ``rec``,
    on a stack of their own (a task's spans may then cross an ``await``
    without another task's opening inside them)."""

    __slots__ = ("rec", "_token")

    def __init__(self, rec: Recorder):
        self.rec = rec

    def __enter__(self) -> Recorder:
        self._token = _STACK.set([_Root(self.rec)])
        return self.rec

    def __exit__(self, *exc) -> None:
        _STACK.reset(self._token)


#: jax.monitoring duration events -> the span name they are recorded as
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "engine.lower",
    "/jax/core/compile/backend_compile_duration": "engine.compile",
}


def _on_duration(event: str, seconds: float, **_) -> None:
    name = _COMPILE_EVENTS.get(event)
    if name is not None:
        st = _stack()
        st[-1].child += seconds
        st[0].rec.add(name, seconds, seconds)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
