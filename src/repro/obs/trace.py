"""Program spans and counters, written into the ``jax.profiler`` trace.

Spans wrap the engines' host-visible phases under the naming convention
``potus/<layer>/<stage>`` (DESIGN.md §14.2). When tracing is on, each span
is a ``jax.profiler.TraceAnnotation`` of that name — its keyword arguments
become the event's stats — so a profile captured with ``jax.profiler``
holds the program's phases on the same clock as the device's operations.
The profiler's trace is the one record: with no profiler session running,
an annotation costs a few hundred nanoseconds and records nothing.

Counters (:func:`count`) add up quantities the trace cannot show, such as
the bytes handed to the device; :func:`take_counters` reads them and
starts them again from zero.

Tracing is **off by default**: :func:`span` is a no-op context manager
and :func:`count` returns at once until :func:`enable_tracing` runs, so the
engines leave the statements in place at no steady-state cost.
"""
from __future__ import annotations

import contextlib
import threading

__all__ = [
    "span",
    "count",
    "take_counters",
    "enable_tracing",
    "disable_tracing",
    "tracing_enabled",
]

_enabled = False
_counters: dict[str, int] = {}
_lock = threading.Lock()
_NOOP = contextlib.nullcontext()


def enable_tracing() -> None:
    global _enabled
    _enabled = True


def disable_tracing() -> None:
    global _enabled
    _enabled = False


def tracing_enabled() -> bool:
    return _enabled


def span(name: str, **stats):
    """``TraceAnnotation(name, **stats)`` when tracing is on, else a no-op."""
    if not _enabled:
        return _NOOP
    import jax.profiler

    return jax.profiler.TraceAnnotation(name, **stats)


def count(name: str, n: int) -> None:
    """Add ``n`` to counter ``name``; nothing happens while tracing is off."""
    if not _enabled:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + int(n)


def take_counters() -> dict[str, int]:
    """The counters' values since the last call; every counter restarts at 0."""
    with _lock:
        out = dict(_counters)
        _counters.clear()
    return out
