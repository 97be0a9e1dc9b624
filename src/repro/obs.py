"""Host spans: where a call into the library spends its host time.

``span(name, **attrs)`` times a block of host Python.  It does two things:

* it opens a ``jax.profiler.TraceAnnotation(name, **attrs)``, so under the
  profiler the block sits in the host plane of the trace, on the clock of
  the device events and nested inside whatever the caller annotated;
* it adds the block's duration to an in-memory table keyed by the span's
  path (``engine.fit/fit.place``: the names of the open spans of this
  thread, outermost first).  Per path the table holds a count and total
  seconds, and the same two numbers again for the spans during which JAX
  traced or compiled (``analysis.retrace``'s counters moved), with the
  seconds JAX spent tracing, lowering and compiling inside them, and the
  sums of the span's numeric attributes (a counter given as an attribute:
  its mean per call is its sum over the count).

``snapshot()`` returns a copy of the table.  The store is always on: a
span costs a few microseconds of host time.  Spans open only in host
Python: inside code that JAX is tracing, ``span`` does nothing, so a
caller's ``jit`` around a fit records no trace-time span.

Span names on the fit path (``docs/architecture.md``)::

    engine.fit                  DAEFEngine.fit (attributes tenants, samples)
      fit.prepare               checks, resolved config, keys, per-tenant
                                seeds and lambdas
      fit.place                 the input put on the program's device(s)
      fit.dispatch              the call of the compiled fit program

and on the reduce path::

    engine.reduce               DAEFEngine.reduce (attributes tenants,
                                group_size)
      reduce.prepare            checks, the host read of the seeds and
                                lambdas, the tree program and its mesh
      reduce.place              the fleet put on the tenant mesh
      reduce.dispatch           the call of the tree program (attributes
                                exchange_bytes: bytes each device sends;
                                encoder_factorizations: encoder SVDs or
                                eighs each device's program runs)
      reduce.dedup              one model kept per replicated group
"""
from __future__ import annotations

import contextlib
import threading
import time

import jax

from repro.analysis import retrace

_LOCAL = threading.local()
_LOCK = threading.Lock()
# path -> [count, seconds, compiled_count, compiled_seconds, jax_seconds,
#          {numeric attribute: sum}]
_TABLE: dict[str, list] = {}


def _stack() -> list[str]:
    stack = getattr(_LOCAL, "stack", None)
    if stack is None:
        stack = _LOCAL.stack = []
    return stack


class _Span:
    __slots__ = ("_name", "_attrs", "_stack", "_path", "_annotation",
                 "_counts", "_jax_s", "_t0")

    def __init__(self, name: str, attrs: dict):
        self._name, self._attrs = name, attrs

    def __enter__(self):
        self._stack = stack = _stack()
        self._path = path = f"{stack[-1]}/{self._name}" if stack else self._name
        stack.append(path)
        self._annotation = jax.profiler.TraceAnnotation(self._name, **self._attrs)
        self._annotation.__enter__()
        # trace_counts() installs retrace's listener on first use, so it
        # counts every compile from the first span on
        self._counts = retrace.trace_counts()
        self._jax_s = retrace.compile_seconds()
        self._t0 = time.perf_counter()  # repro-lint: disable=RPR006 (measurement code)
        return self

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._t0  # repro-lint: disable=RPR006 (measurement code)
        jax_s = retrace.compile_seconds() - self._jax_s
        compiled = retrace.trace_counts() != self._counts
        self._annotation.__exit__(*exc)
        self._stack.pop()
        with _LOCK:
            row = _TABLE.get(self._path)
            if row is None:
                row = _TABLE[self._path] = [0, 0.0, 0, 0.0, 0.0, {}]
            row[0] += 1
            row[1] += seconds
            for key, value in self._attrs.items():
                if isinstance(value, (int, float)) and not isinstance(value, bool):
                    row[5][key] = row[5].get(key, 0) + value
            if compiled:
                row[2] += 1
                row[3] += seconds
                row[4] += jax_s
        return False


_QUIET = contextlib.nullcontext()


def span(name: str, **attrs):
    """Context manager timing a block of host Python as span ``name`` (see
    the module docstring); ``attrs`` go to the profiler's annotation."""
    if not jax.core.trace_ctx.is_top_level():
        return _QUIET
    return _Span(name, attrs)


def snapshot() -> dict[str, dict]:
    """A copy of the span table: per path ``count`` and ``seconds``, and of
    those the spans during which JAX traced or compiled: ``compiled_count``,
    ``compiled_seconds`` and ``jax_seconds`` (JAX's own trace, lowering and
    compile seconds inside them); ``attrs``, the sums of the spans' numeric
    attributes."""
    with _LOCK:
        rows = {path: [*row[:5], dict(row[5])] for path, row in _TABLE.items()}
    keys = ("count", "seconds", "compiled_count", "compiled_seconds", "jax_seconds",
            "attrs")
    return {path: dict(zip(keys, row, strict=True)) for path, row in rows.items()}
