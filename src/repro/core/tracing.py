"""Spans and counters of the colony's own work.

A span is one thread's named, timed interval::

    with tracing.span("colony.executor.process", processid=pid) as sp:
        ...
        sp.set(files=3)

Spans are recorded while tracing is on: after :func:`enable`, or while
the profiler that a process installed with :func:`install` is capturing.
Off, :func:`span` returns one shared no-op context and records nothing.
Each recorded span keeps its name, wall-clock start and end (seconds),
the native id of its thread, its parent (the span open on the same
thread when it began), the ``processid`` that ties one batch's spans
together (given, or taken from the parent) and its other attributes, in
a bounded buffer until :func:`drain`. Names start with ``colony.``;
spans sit at batch and RPC granularity, never inside a decode step.

Counters (:func:`count`) are kept whether tracing is on or not: how many
times something happened and how many seconds it took. An object may own
a :class:`Counters` of its own (the serving engine does); every live one
is part of :func:`counters`, which ``colonystats`` returns to operators.

Standard library only: processes that never import JAX (a load
generator, a broker) import it. A JAX process installs
``jax.profiler.TraceAnnotation`` (``serve/engine.py`` does at import), so
every span is also an annotation in the profiler's trace, on the clock
of the device's events.
"""

from __future__ import annotations

import itertools
import threading
import time
import weakref
from collections import deque
from typing import Any, Callable

from ..analysis.locktrack import make_lock

# Spans kept until drained; the oldest go first when it is full.
BUFFER_SPANS = 1 << 16  # about 25 traced windows of the chat cells

_lock = make_lock("tracing")  # leaf: guards the buffer and counter dicts only
_buffer: deque[dict] = deque(maxlen=BUFFER_SPANS)
_dropped = 0
_on = False
_annotate: Callable[[str], Any] | None = None
_capturing: Callable[[], bool] | None = None
_ids = itertools.count(1)
_local = threading.local()


class Counters:
    """Named counters, each a number of events and their seconds."""

    def __init__(self, prefix: str = "") -> None:
        self.prefix = prefix
        self._c: dict[str, list] = {}
        with _lock:
            _registries.add(self)

    def count(self, name: str, n: int = 1, seconds: float = 0.0) -> None:
        with _lock:
            c = self._c.get(name)
            if c is None:
                self._c[name] = [n, seconds]
            else:
                c[0] += n
                c[1] += seconds

    def get(self, name: str) -> tuple[int, float]:
        with _lock:
            n, s = self._c.get(name, (0, 0.0))
        return n, s

    def snapshot(self) -> dict[str, dict]:
        with _lock:
            return {self.prefix + k: {"n": n, "seconds": s} for k, (n, s) in self._c.items()}


_registries: weakref.WeakSet[Counters] = weakref.WeakSet()
_root = Counters()


class _NoSpan:
    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **attrs: Any) -> None:
        return None


_NOOP = _NoSpan()


class _Span:
    __slots__ = ("name", "attrs", "id", "parent", "processid", "start", "_ann")

    def __init__(self, name: str, attrs: dict) -> None:
        self.name, self.attrs = name, attrs

    def __enter__(self) -> "_Span":
        stack = _stack()
        up = stack[-1] if stack else None
        self.id = next(_ids)
        self.parent = up.id if up is not None else None
        self.processid = self.attrs.pop("processid", None) or (up.processid if up else None)
        stack.append(self)
        self._ann = _annotate(self.name) if _annotate is not None else None
        if self._ann is not None:
            self._ann.__enter__()
        self.start = time.time()
        return self

    def __exit__(self, *exc) -> None:
        end = time.time()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        _keep({"name": self.name, "start": self.start, "end": end,
               "thread": threading.get_native_id(), "id": self.id, "parent": self.parent,
               "processid": self.processid, "attrs": self.attrs})

    def set(self, **attrs: Any) -> None:
        """Add attributes; a ``processid`` set here passes to the spans
        that begin inside this one from now on."""
        if "processid" in attrs:
            self.processid = attrs.pop("processid")
        self.attrs.update(attrs)


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _keep(rec: dict) -> None:
    global _dropped
    with _lock:
        if len(_buffer) == BUFFER_SPANS:
            _dropped += 1
        _buffer.append(rec)


def is_on() -> bool:
    return _on or (_capturing is not None and _capturing())


def span(name: str, **attrs: Any):
    """A context that records one span while tracing is on."""
    if not is_on():
        return _NOOP
    return _Span(name, attrs)


def record(name: str, start: float, end: float, **attrs: Any) -> None:
    """Record a span measured by someone else (a compile that JAX timed),
    as a child of the span open on this thread."""
    if not is_on():
        return
    stack = _stack()
    up = stack[-1] if stack else None
    _keep({"name": name, "start": start, "end": end, "thread": threading.get_native_id(),
           "id": next(_ids), "parent": up.id if up else None,
           "processid": up.processid if up else None, "attrs": attrs})


def count(name: str, n: int = 1, seconds: float = 0.0) -> None:
    """Add to the process's counter ``name``."""
    _root.count(name, n, seconds)


def counters() -> dict[str, dict]:
    """Every counter of every live :class:`Counters`, by name."""
    with _lock:
        regs = list(_registries)
    out: dict[str, dict] = {}
    for reg in regs:
        for k, v in reg.snapshot().items():
            if k in out:
                out[k] = {"n": out[k]["n"] + v["n"], "seconds": out[k]["seconds"] + v["seconds"]}
            else:
                out[k] = v
    return out


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def drain() -> dict:
    """Hand back the recorded spans (and empty the buffer), how many were
    dropped since the last drain, and every counter."""
    global _dropped
    with _lock:
        spans, dropped = list(_buffer), _dropped
        _buffer.clear()
        _dropped = 0
    return {"spans": spans, "dropped": dropped, "counters": counters()}


def install(annotate: Callable[[str], Any] | None,
            capturing: Callable[[], bool] | None = None) -> None:
    """Open every span as ``annotate(name)`` as well, and record spans
    whenever ``capturing()`` says a profiler is capturing. ``None``
    removes both."""
    global _annotate, _capturing
    _annotate, _capturing = annotate, capturing if annotate is not None else None
