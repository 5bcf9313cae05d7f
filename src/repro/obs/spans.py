"""Host spans on the profiler's clock, timed into the registry.

``span(name, **args)`` marks one phase of a host-driven hot path:

* plane off — one :func:`repro.obs.enabled` check, then a shared no-op
  context manager: nothing is recorded or registered;
* plane on — a ``jax.profiler.TraceAnnotation(name, **args)``, so the span
  lands on the profiler's host clock beside the device trace of a traced
  run, and the span's inclusive wall time (``time.perf_counter_ns``) adds
  to the counter ``<name>.ns`` and 1 to ``<name>.n`` of ``REGISTRY``.

A span's parent is the span that encloses it on the same thread.  The
outermost span of a thread starts a *call*: it gets ``call=<process-wide
sequence number>`` unless the caller passed one, and every span nested in
it carries the same ``call`` argument, so one call's spans share an
identifier in the trace.  Spans are host code only; none goes inside a
jitted function.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time

from repro import obs

__all__ = ["span"]

_NO_SPAN = contextlib.nullcontext()
_calls = itertools.count(1)
_local = threading.local()


def _stack() -> list:
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


class _Span:
    __slots__ = ("name", "args", "parent", "_ann", "_t0")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args

    def __enter__(self):
        from jax.profiler import TraceAnnotation

        stack = _stack()
        self.parent = stack[-1] if stack else None
        if "call" not in self.args:
            self.args["call"] = (self.parent.args["call"] if self.parent
                                 else next(_calls))
        stack.append(self)
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        _stack().pop()
        obs.REGISTRY.counter(self.name + ".ns").inc(dt)
        obs.REGISTRY.counter(self.name + ".n").inc()
        return False


def span(name: str, **args):
    """Context manager timing one phase; a no-op when the plane is off."""
    if not obs.enabled():
        return _NO_SPAN
    return _Span(name, args)
