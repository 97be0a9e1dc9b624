"""Runtime trace/compile budget auditing: :func:`trace_guard`.

The repo's jit surfaces stake performance claims on *bounded tracing*:
``FleetServer.warmup()`` pre-traces the packer's whole shape set so a
mixed ragged serve never retraces; ``fit_stream`` re-traces one donated
accumulator step per layer, flat in the number of chunks.  Nothing in an
ordinary assertion notices when a refactor silently breaks that — the
numbers stay right, the speed evaporates.

``trace_guard`` turns the budget into an assertion::

    with trace_guard(max_traces=0):
        for _ in range(rounds):
            server.submit(...); server.flush()      # raises on any retrace

    with trace_guard() as rep:                      # measure, don't enforce
        engine.fit_stream(batches)
    assert rep.traces == expected

Counting uses JAX's public monitoring events
(``/jax/core/compile/jaxpr_trace_duration`` fires once per jaxpr trace —
i.e. per jit *tracing cache miss*, nested jits included — and
``.../backend_compile_duration`` once per XLA compile), so the guard
needs no private-API patching.  The names of the traced functions are
captured best-effort from JAX's compile logger for the error message.
"""
from __future__ import annotations

import logging
import re
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax

JAXPR_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_COUNTS = {JAXPR_TRACE_EVENT: 0, BACKEND_COMPILE_EVENT: 0}
_TIMED = (JAXPR_TRACE_EVENT, LOWER_EVENT, BACKEND_COMPILE_EVENT)
_SECONDS = 0.0
_LISTENING = False


def _listener(event: str, duration: float, **kwargs) -> None:  # noqa: ARG001
    global _SECONDS
    if event in _TIMED:
        _SECONDS += duration
        if event in _COUNTS:
            _COUNTS[event] += 1


def _ensure_listening() -> None:
    """Install the (permanent, idempotent) monitoring listener."""
    global _LISTENING
    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_listener)
        _LISTENING = True


def trace_counts() -> tuple[int, int]:
    """Process-lifetime ``(traces, compiles)`` counted so far (since the
    first guard/urge to count — the listener installs lazily)."""
    _ensure_listening()
    return _COUNTS[JAXPR_TRACE_EVENT], _COUNTS[BACKEND_COMPILE_EVENT]


def compile_seconds() -> float:
    """Process-lifetime seconds JAX spent tracing, lowering and compiling
    (backend compiles served from the persistent cache included), counted
    like :func:`trace_counts`; a jit traced inside another's trace counts
    in both."""
    _ensure_listening()
    return _SECONDS


class TraceBudgetExceeded(AssertionError):
    """Raised by :func:`trace_guard` when the block traced/compiled more
    than its budget allows."""


_NAME_RES = (
    re.compile(r"Finished tracing \+ transforming (\S+) for pjit"),
    re.compile(r"Finished jaxpr to MLIR module conversion jit\((\S+)\)"),
    re.compile(r"Finished XLA compilation of jit\((\S+)\)"),
)


class _NameCapture(logging.Handler):
    """Best-effort capture of which functions traced, for diagnostics."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.names: list[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        for rx in _NAME_RES:
            m = rx.search(msg)
            if m:
                self.names.append(m.group(1))
                return


@dataclass
class TraceReport:
    """Deltas observed inside one :func:`trace_guard` block."""

    traces: int = 0
    compiles: int = 0
    traced_names: list[str] = field(default_factory=list)
    _start: tuple[int, int] = (0, 0)

    def snapshot(self) -> None:
        t, c = trace_counts()
        self.traces = t - self._start[0]
        self.compiles = c - self._start[1]

    def __str__(self) -> str:
        names = f" ({', '.join(sorted(set(self.traced_names)))})" \
            if self.traced_names else ""
        return f"TraceReport(traces={self.traces}, " \
               f"compiles={self.compiles}{names})"


@contextmanager
def trace_guard(max_traces: int | None = None, *,
                max_compiles: int | None = None, what: str = "block"):
    """Count jit traces/compiles in the ``with`` block; optionally enforce.

    Args:
      max_traces: if given, raise :class:`TraceBudgetExceeded` when the
        block incurred more than this many jaxpr traces (``0`` asserts
        "fully warm — no retraces at all").  ``None`` = measure only.
      max_compiles: same for XLA backend compiles.
      what: label used in the failure message.

    Yields a :class:`TraceReport` whose ``traces``/``compiles`` are live
    (updated on exit and via ``snapshot()``).  Nested guards are fine —
    each sees its own deltas.  Note the count includes *nested* jit
    traces: one cold top-level call typically records several trace
    events.  Budgets therefore mean "at most N" for cold paths and the
    exact ``0`` for warm paths; flatness claims should compare deltas of
    two runs.
    """
    dispatch_logger = logging.getLogger("jax._src.dispatch")
    capture = _NameCapture()
    old_level = dispatch_logger.level
    old_propagate = dispatch_logger.propagate
    report = TraceReport(_start=trace_counts())
    dispatch_logger.addHandler(capture)
    # The dispatch logger formats the "Finished tracing ..." message only
    # when enabled for DEBUG; lower it for the duration of the guard (and
    # stop propagation so the debug lines reach only our capture handler,
    # not the console).
    if not dispatch_logger.isEnabledFor(logging.DEBUG):
        dispatch_logger.setLevel(logging.DEBUG)
        dispatch_logger.propagate = False
    try:
        yield report
    finally:
        report.snapshot()
        report.traced_names = capture.names
        dispatch_logger.removeHandler(capture)
        dispatch_logger.setLevel(old_level)
        dispatch_logger.propagate = old_propagate
    if max_traces is not None and report.traces > max_traces:
        raise TraceBudgetExceeded(
            f"{what}: {report.traces} jaxpr trace(s), budget {max_traces}"
            + (f"; traced: {sorted(set(report.traced_names))}"
               if report.traced_names else "")
        )
    if max_compiles is not None and report.compiles > max_compiles:
        raise TraceBudgetExceeded(
            f"{what}: {report.compiles} XLA compile(s), budget {max_compiles}"
        )


__all__ = ["trace_guard", "trace_counts", "compile_seconds", "TraceReport",
           "TraceBudgetExceeded", "JAXPR_TRACE_EVENT", "LOWER_EVENT",
           "BACKEND_COMPILE_EVENT"]
