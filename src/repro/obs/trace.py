"""Nestable program spans, written into the JAX profiler's trace.

The engine asserts hard *runtime* contracts — zero retraces, zero host
transfers, epoch-consistent serving — and needs to see where a compile, a
maintenance tick, a served read or a job's host work spends its time,
beside what the device did meanwhile.  Code wraps its phases in ``with
span("ivm.apply"):`` (DESIGN.md §11); while a profiler session is active
each span is one host annotation named ``repro.<name>`` in that session's
trace, with its keyword arguments as the event's stats, on the same clock
as the device's operations.  The profiler is the one sink: there is no
buffer of our own and no switch.  An operator captures a trace with

    with jax.profiler.trace("/tmp/trace", create_perfetto_trace=True):
        ... run a workload ...

or from a running process through a profiler server
(``jax.profiler.start_server``), and reads it in Perfetto or TensorBoard,
or with ``jax.profiler.ProfileData``.

Two properties are load-bearing:

* **Cheap with no session.**  ``span()`` then returns a shared no-op
  context manager after one ``TraceAnnotation.is_enabled()`` call — no
  allocation, no clock read, no lock — so the instrumentation lives in the
  engine permanently.

* **No device syncs.**  A span never calls ``block_until_ready`` or
  otherwise forces the device to drain.  Around asynchronously dispatched
  jitted calls it therefore covers *host dispatch* (trace time on a cache
  miss); the device's own time is in the same trace, on its own planes.

This module imports no ``jax`` (lint rule ``obs-no-device``): ``repro.core``
hands it the profiler's annotation class through :func:`install` as it is
imported, and every module that opens a span imports ``repro.core`` first.
Until then ``span()`` is the no-op.
"""

from __future__ import annotations

__all__ = ["span", "install", "PREFIX"]

#: what every program span's name starts with in the profiler's trace; it
#: tells the program's spans from JAX's own host events
PREFIX = "repro."


class _NullSpan:
    """The no-session fast path: a shared, stateless no-op."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


def _never() -> bool:
    return False


_annotation = None
_is_enabled = _never


def install(annotation) -> None:
    """Write spans through ``annotation`` (``jax.profiler.TraceAnnotation``:
    a context manager taking a name and keyword stats, with a static
    ``is_enabled()`` that says whether a profiler session is active)."""
    global _annotation, _is_enabled
    _annotation, _is_enabled = annotation, annotation.is_enabled


def span(name: str, **args):
    """``with span("ivm.tick", rel="R2"):`` — mark a phase as the host
    annotation ``repro.ivm.tick`` in the active profiler session; the
    shared no-op when there is none."""
    if not _is_enabled():
        return _NULL
    return _annotation(PREFIX + name, **args)
