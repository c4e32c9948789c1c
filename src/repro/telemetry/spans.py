"""Program spans on the profiler's clock, and a compile counter.

``span(name)`` marks one stretch of host work: the preprocessing stages,
one supervised segment, the wait for a segment at the drain. Each span

* opens a ``jax.profiler.TraceAnnotation``, so it lands on the profiler's
  host line on the same clock as the device planes, and a trace reduction
  can hand the device's idle time to it;
* times itself with ``time.perf_counter()`` (``.seconds`` after exit), which
  is where the preprocessing stage times come from;
* is recorded in memory (count and seconds under its name) only while a
  profiler session records, as ``TraceAnnotation.is_enabled()`` tells at
  entry. So a traced window is exactly what :func:`snapshot` shows, and
  with no profiler a span costs one idle annotation, two clock reads and a
  push and pop on a thread-local stack.

The compile counter is always on, because the compiles that matter happen
in set-up, which is never traced: each backend compile adds its count and
seconds under the innermost span open on the compiling thread, or under
``""`` where none is open. It fires only when XLA compiles.

The record is process-wide, like the profiler session it mirrors.
"""
from __future__ import annotations

import threading
import time

import jax

__all__ = ["span", "snapshot", "reset", "COMPILE_EVENT"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_Annotation = jax.profiler.TraceAnnotation
_lock = threading.Lock()
_spans: dict[str, list] = {}      # span name -> [count, seconds], traced only
_compiles: dict[str, list] = {}   # innermost open span -> [count, seconds]


class _Open(threading.local):
    def __init__(self):
        self.names: list[str] = []


_open = _Open()


def _add(into: dict, name: str, seconds: float) -> None:
    with _lock:
        acc = into.get(name)
        if acc is None:
            acc = into[name] = [0, 0.0]
        acc[0] += 1
        acc[1] += seconds


class span:
    """``with span(name) as sp: ...``; ``sp.seconds`` is its wall time."""

    __slots__ = ("name", "seconds", "_annotation", "_recording", "_t0")

    def __init__(self, name: str):
        self.name = name
        self.seconds: float | None = None

    def __enter__(self) -> "span":
        self._recording = _Annotation.is_enabled()
        self._annotation = _Annotation(self.name)
        self._annotation.__enter__()
        _open.names.append(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        _open.names.pop()
        self._annotation.__exit__(*exc)
        if self._recording:
            _add(_spans, self.name, self.seconds)


def _on_event(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT:
        names = _open.names
        _add(_compiles, names[-1] if names else "", duration)


jax.monitoring.register_event_duration_secs_listener(_on_event)


def snapshot() -> dict:
    """{"spans": {name: {"count", "s"}}, "compile": {span: {"count", "s"}}}:
    the spans recorded while a profiler session recorded, and every backend
    compile since the process started (or since :func:`reset`)."""
    with _lock:
        return {key: {name: {"count": c, "s": s}
                      for name, (c, s) in table.items()}
                for key, table in (("spans", _spans),
                                   ("compile", _compiles))}


def reset() -> None:
    """Forget every recorded span and compile."""
    with _lock:
        _spans.clear()
        _compiles.clear()
