"""The program's own spans and compile counter.

``span(name, **meta)`` marks one layer's work.  It pushes ``name`` on
this thread's stack of open spans and, while a profiler session runs,
opens a ``jax.profiler.TraceAnnotation`` named ``livestack.<name>``
with ``meta`` as its metadata, so the span lies on the profiler's host
plane on the same clock as the device planes.  Without a profiler no
annotation is made: a span costs one check and a push and pop, so spans
stay in the code always, and tracing is on exactly while a profiler
runs.  A span object holds no per-entry state: one made once can be
entered again and again, from any thread.

The compile counter is a ``jax.monitoring`` listener registered once at
import.  It counts every executable JAX builds, a backend compile
(``/jax/core/compile/backend_compile_duration``) or a load from the
persistent compilation cache (``/jax/compilation_cache/cache_hits``),
and credits it to the innermost span open on the building thread
(``None`` outside every span).  ``counters()`` returns the counts,
``{"compile": {span name: builds}}``; while a profiler runs each build
also leaves a ``livestack.compile`` event inside that span, with the
span's name as its ``span`` metadata.
"""
from __future__ import annotations

import collections
import threading
from typing import Dict, Optional, Tuple

import jax

PREFIX = "livestack."
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"

_Annotation = jax.profiler.TraceAnnotation


class _Open(threading.local):
    def __init__(self):
        self.names: list = []       # open span names, innermost last
        self.notes: list = []       # their annotations (None: no profiler)


_open = _Open()
_lock = threading.Lock()
_compiles: Dict[Optional[str], int] = collections.Counter()


class span:
    """Context manager marking one layer's work (see the module
    docstring)."""
    __slots__ = ("name", "_label", "_meta")

    def __init__(self, name: str, **meta):
        self.name = name
        self._label = PREFIX + name
        self._meta = meta

    def __enter__(self) -> "span":
        note = None
        if _Annotation.is_enabled():
            note = _Annotation(self._label, **self._meta)
            note.__enter__()
        _open.notes.append(note)
        _open.names.append(self.name)
        return self

    def __exit__(self, *exc) -> None:
        _open.names.pop()
        note = _open.notes.pop()
        if note is not None:
            note.__exit__(*exc)


def stack() -> Tuple[str, ...]:
    """Names of the spans open on this thread, outermost first."""
    return tuple(_open.names)


def counters() -> Dict[str, Dict[Optional[str], int]]:
    """Executables built in this process so far, by the span that was
    innermost when each was built."""
    with _lock:
        return {"compile": dict(_compiles)}


def _built() -> None:
    name = _open.names[-1] if _open.names else None
    with _lock:
        _compiles[name] += 1
    if _Annotation.is_enabled():
        with _Annotation(PREFIX + "compile", span=str(name)):
            pass


def _on_duration(event: str, _secs: float, **_kw) -> None:
    if event == BACKEND_COMPILE:
        _built()


def _on_event(event: str, **_kw) -> None:
    if event == CACHE_HIT:
        _built()


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
