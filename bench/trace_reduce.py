"""From a profiler trace to the numbers the per-layer metrics read.

``capture()`` records a JAX profiler trace of a block (host spans and device
operations on one clock) into a temporary directory; ``reduce()`` reads the
``.xplane.pb`` with ``jax.profiler.ProfileData`` and gives, over the window
marked by the benchmark's host span ``bench.window``:

* busy seconds of each device: the union of the intervals in which an
  operation ran (line ``XLA Ops`` of each ``/device:TPU:<i>`` plane), and
  their mean over the devices;
* device seconds per operation (named ``<program>:<op>``, see ``short_op``)
  and per program (line ``XLA Modules``);
* collective seconds (operations named like a collective), and the part of
  them during which no other operation ran on that device;
* the idle gaps between busy intervals, shared out among the benchmark's
  host spans (``bench.<phase>``) by the part of each gap they overlap, the
  rest counted as ``host:other``.

In a trace of a TPU v5e the device's events sit 1.2-1.6 ms before the
host's launch of the same program (``tests/data``), so the share of a gap
given to a span is exact only to about a millisecond per gap.

Seconds per device are averaged over the devices the trace holds.
"""
from __future__ import annotations

import bisect
import contextlib
import functools
import glob
import os
import re
import shutil
import tempfile
import time
from collections import defaultdict

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|"
                         r"all-to-all|\bsend\b|\brecv\b|send-done|recv-done")
NS = 1e-9


def union(intervals):
    """Merged, sorted, disjoint (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def _subtract(a, b):
    """Length of the disjoint intervals ``a`` not covered by disjoint ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _events(line):
    for ev in line.events:
        start = float(ev.start_ns)
        yield ev.name, start, start + float(ev.duration_ns)


@functools.lru_cache(maxsize=None)
def short_op(name: str) -> str:
    """``%fusion.5 = f32[..] fusion(..)`` -> ``fusion.5``; a custom call keeps
    its target (``custom-call.26 EighTpu``)."""
    head, _, rest = name.partition(" = ")
    m = _TARGET.search(rest)
    head = head.lstrip("%")
    return f"{head} {m.group(1)}" if m else head


@functools.lru_cache(maxsize=None)
def _is_collective(op: str) -> bool:
    return bool(_COLLECTIVE.search(op.lower()))


def short_module(name: str) -> str:
    """``jit__fit_program(6437..)`` -> ``jit__fit_program``."""
    return name.split("(", 1)[0]


def _in_modules(ops, modules):
    """Each op's enclosing program name (by time), or ``""``."""
    modules = sorted(modules, key=lambda m: m[1])
    out, j = [], 0
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        while j < len(modules) and modules[j][2] <= s:
            j += 1
        inside = j < len(modules) and modules[j][1] <= s
        out.append((name, s, e, short_module(modules[j][0]) if inside else ""))
    return out


def reduce(profile) -> dict:
    """Reduce a ``jax.profiler.ProfileData`` (see the module docstring)."""
    host_spans, devices = [], {}
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name.startswith(SPAN_PREFIX):
                        host_spans.append((name, s, e))
    windows = [(s, e) for name, s, e in host_spans if name == WINDOW_SPAN]
    spans = sorted((sp for sp in host_spans if sp[0] != WINDOW_SPAN), key=lambda sp: sp[1])
    span_ends = [e for _, _, e in spans]

    per_dev = []
    for _, plane in sorted(devices.items()):
        ops, modules = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [(short_op(name), s, e) for name, s, e in _events(line)]
            elif line.name == "XLA Modules":
                modules = list(_events(line))
        per_dev.append((_in_modules(ops, modules), modules))

    if windows:
        lo, hi = windows[0]
    else:
        starts = [s for ops, _ in per_dev for _, s, _, _ in ops]
        ends = [e for ops, _ in per_dev for _, _, e, _ in ops]
        lo, hi = (min(starts), max(ends)) if starts else (0.0, 0.0)

    n_dev = max(len(per_dev), 1)
    busy, coll, coll_exposed = 0.0, 0.0, 0.0
    op_ns, mod_ns = defaultdict(float), defaultdict(float)
    gap_ns = defaultdict(float)
    for ops, modules in per_dev:
        inside = []
        for name, s, e, module in ops:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                inside.append((name, s, e, module))
                op_ns[f"{module}:{name}" if module else name] += e - s
        for name, s, e in modules:
            cs, ce = _clip(s, e, lo, hi)
            if ce > cs:
                mod_ns[short_module(name)] += ce - cs
        busy_iv = union((s, e) for _, s, e, _ in inside)
        busy += covered(busy_iv)
        coll_iv = union((s, e) for n, s, e, _ in inside if _is_collective(n))
        other_iv = union((s, e) for n, s, e, _ in inside if not _is_collective(n))
        coll += covered(coll_iv)
        coll_exposed += _subtract(coll_iv, other_iv)
        edges = [lo] + [x for iv in busy_iv for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2], strict=True):
            if b > a:
                for label, ns in _share(a, b, spans, span_ends):
                    gap_ns[label] += ns

    return {
        "devices": len(per_dev),
        "window_s": (hi - lo) * NS,
        "busy_s": busy / n_dev * NS,
        "collective_s": coll / n_dev * NS,
        "collective_exposed_s": coll_exposed / n_dev * NS,
        "op_s": {k: v / n_dev * NS for k, v in op_ns.items()},
        "module_s": {k: v / n_dev * NS for k, v in mod_ns.items()},
        "gap_s": {k: v / n_dev * NS for k, v in gap_ns.items()},
    }


def _share(a, b, spans, span_ends):
    """The gap [a, b) shared out among the host spans by the part each
    overlaps; the rest is ``host:other``.  The benchmark's spans follow one
    another without nesting, so sorted by start they are sorted by end too
    (``span_ends``), and no part is counted twice."""
    out, rest = [], b - a
    i = bisect.bisect_right(span_ends, a)
    while i < len(spans) and spans[i][1] < b:
        n, s, e = spans[i]
        overlap = min(b, e) - max(a, s)
        if overlap > 0:
            out.append((n, overlap))
            rest -= overlap
        i += 1
    if rest > 0:
        out.append(("host:other", rest))
    return out


def top(table: dict, k: int = 10):
    """The ``k`` largest entries as ``[[name, value], ...]``."""
    return [[n, v] for n, v in sorted(table.items(), key=lambda kv: -kv[1])[:k]]


@contextlib.contextmanager
def capture(result: dict):
    """Trace the block; on exit ``result`` holds ``reduce()`` of the trace.
    The trace goes to a temporary directory, which is removed."""
    import jax
    from jax.profiler import ProfileData

    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            yield result
        finally:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
        t1 = time.perf_counter()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        profile = ProfileData.from_file(path[0])
        t2 = time.perf_counter()
        result.update(reduce(profile))
        result["trace_bytes"] = os.path.getsize(path[0])
        result["cost_s"] = {"stop": t1 - t0, "read": t2 - t1, "reduce": time.perf_counter() - t2}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
