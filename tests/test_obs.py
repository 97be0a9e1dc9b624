"""Host spans (`repro.obs`) and the named scopes of the fit program.

* spans: nested paths, counts and seconds; a stack per thread and no
  update lost between threads; a span around a first jit call is marked
  as compiling and a warm one is not; nothing is recorded inside
  traced code; the fit path's spans sit in a CPU profiler trace, inside the
  caller's annotation.
* scopes: every entry-computation fusion, dot and custom call of the fit
  program carries a stage scope, and the scopes change only metadata: with
  ``jax.named_scope`` a no-op the compiled program has the same structure.
* ``DAEFEngine.lower_fit`` is the program ``fit`` runs: compiled and called
  with the same arguments it returns bit-identical weights.
"""
import contextlib
import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import daef, fleet
from repro.engine import DAEFEngine, ExecutionPlan

CFG = daef.DAEFConfig(layer_sizes=(8, 3, 5, 6, 8), lam_hidden=0.5, lam_last=0.5)
K, M0, N = 3, 8, 64
STAGES = ("encoder", "stats", "solve", "forward", "errors")
FIT_SPANS = ("engine.fit/fit.prepare", "engine.fit/fit.place", "engine.fit/fit.dispatch")

PLANS = {
    "single": (None, (M0, N)),
    "vmap": (ExecutionPlan(mode="vmap", tenants=K), (K, M0, N)),
    "chunked": (ExecutionPlan(chunk_samples=16), (M0, N)),
}

_META = re.compile(r", metadata=\{[^}]*\}")
_NAME = re.compile(r"%[\w.\-]+")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")
# the stack-frame tables of a compiled module's text: section names, rows
_TABLE_LINE = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)$|^\d+ ")


def _data(shape, seed=0):
    return np.random.default_rng(seed).normal(size=shape).astype(np.float32)


def _count(snap, path, key="count"):
    return snap.get(path, {}).get(key, 0)


def _lowered_text(plan, shape):
    engine = DAEFEngine(CFG, plan)
    return engine.lower_fit(jax.ShapeDtypeStruct(shape, np.float32)).compile().as_text()


def _scopes(op_name: str) -> set:
    parts = set()
    for part in op_name.split("/"):
        while (m := _WRAPPED.match(part)) is not None:
            part = m.group(1)
        parts.add(part)
    return parts & set(STAGES)


def _structure(hlo_text: str) -> str:
    """The compiled module without its metadata, its stack-frame tables and
    its instruction names (numbered in order of appearance instead)."""
    lines = [line for line in hlo_text.splitlines() if not _TABLE_LINE.match(line)]
    text = _META.sub("", "\n".join(lines))
    ids: dict = {}
    text = _NAME.sub(lambda m: ids.setdefault(m.group(0), f"%i{len(ids)}"), text)
    assert text.count(" = ") > 50  # the computations are all there
    return text


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_nested_spans_record_their_path_count_and_seconds():
    for _ in range(3):
        with obs.span("t_nested.outer"):
            with obs.span("t_nested.inner", attr=1):
                time.sleep(0.002)
            with obs.span("t_nested.inner"):
                pass
    snap = obs.snapshot()
    outer, inner = snap["t_nested.outer"], snap["t_nested.outer/t_nested.inner"]
    assert outer["count"] == 3 and inner["count"] == 6
    assert "t_nested.inner" not in snap
    assert inner["seconds"] >= 3 * 0.002
    assert outer["seconds"] >= inner["seconds"]
    assert outer["compiled_count"] == inner["compiled_count"] == 0


def test_each_thread_keeps_its_own_span_stack():
    import threading

    opened, release = threading.Event(), threading.Event()

    def worker():
        with obs.span("t_thread.worker"):
            opened.set()
            release.wait(10)

    with obs.span("t_thread.main"):
        t = threading.Thread(target=worker)
        t.start()
        assert opened.wait(10)
        with obs.span("t_thread.child"):
            pass
        release.set()
        t.join(10)
    assert not t.is_alive()
    snap = obs.snapshot()
    assert snap["t_thread.worker"]["count"] == 1
    assert snap["t_thread.main/t_thread.child"]["count"] == 1
    assert not [p for p in snap if "/t_thread.worker" in p]


def test_spans_from_many_threads_lose_no_update():
    import sys
    import threading

    threads, per_thread = 16, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def worker():
            for _ in range(per_thread):
                with obs.span("t_stress.span"):
                    pass

        pool = [threading.Thread(target=worker) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in pool)
    assert obs.snapshot()["t_stress.span"]["count"] == threads * per_thread


def test_a_span_around_a_first_jit_call_is_marked_as_compiling():
    f = jax.jit(lambda v: v * 3.0 + 1.0)
    x = jnp.arange(5.0)
    with obs.span("t_compile.first"):
        f(x).block_until_ready()
    with obs.span("t_compile.second"):
        f(x).block_until_ready()
    snap = obs.snapshot()
    first, second = snap["t_compile.first"], snap["t_compile.second"]
    assert first["compiled_count"] == 1
    assert first["compiled_seconds"] == first["seconds"] > 0
    assert first["jax_seconds"] > 0
    assert second["count"] == 1 and second["compiled_count"] == 0
    assert second["compiled_seconds"] == second["jax_seconds"] == 0.0


def test_no_span_is_recorded_inside_traced_code():
    def g(v):
        with obs.span("t_traced.inside"):
            return v + 1.0

    with obs.span("t_traced.outside"):
        jax.jit(g)(1.0)
    snap = obs.snapshot()
    assert snap["t_traced.outside"]["count"] == 1
    assert not [p for p in snap if "t_traced.inside" in p]


def test_numeric_attributes_are_summed_per_path():
    for sent in (3, 4.5):
        with obs.span("t_attrs.span", sent=sent, label="x", flag=True):
            pass
    row = obs.snapshot()["t_attrs.span"]
    assert row["count"] == 2
    assert row["attrs"] == {"sent": 7.5}


def test_snapshot_is_a_copy():
    with obs.span("t_copy"):
        pass
    snap = obs.snapshot()
    snap["t_copy"]["count"] = 99
    snap["t_copy"]["attrs"]["n"] = 1
    assert obs.snapshot()["t_copy"]["count"] == 1
    assert obs.snapshot()["t_copy"]["attrs"] == {}


@pytest.mark.parametrize("name", ["single", "vmap", "chunked"])
def test_fit_records_prepare_place_and_dispatch_under_engine_fit(name):
    plan, shape = PLANS[name]
    engine = DAEFEngine(CFG, plan)
    x = _data(shape)
    kw = {"seeds": jnp.arange(K)} if len(shape) == 3 else {}
    engine.fit(x, **kw)
    before = obs.snapshot()
    jax.block_until_ready(engine.fit(x, **kw))
    after = obs.snapshot()
    for path in ("engine.fit", *FIT_SPANS):
        assert _count(after, path) - _count(before, path) == 1, path
        # warm: the second fit of a shape neither traces nor compiles
        assert (_count(after, path, "compiled_count")
                == _count(before, path, "compiled_count")), path


def test_loop_mode_places_and_dispatches_once_per_tenant():
    engine = DAEFEngine(CFG, ExecutionPlan(mode="loop", tenants=K))
    x = _data((K, M0, N))
    engine.fit(x)
    before = obs.snapshot()
    engine.fit(x)
    after = obs.snapshot()
    for path, want in zip(FIT_SPANS, (1, K, K), strict=True):
        assert _count(after, path) - _count(before, path) == want, path


def test_fit_spans_sit_in_a_profiler_trace_inside_the_callers_annotation(tmp_path):
    from jax.profiler import ProfileData

    engine = DAEFEngine(CFG)
    x = _data((M0, N))
    jax.block_until_ready(engine.fit(x))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.caller"):
            jax.block_until_ready(engine.fit(x))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("test.caller", "engine.fit", "fit.prepare",
                               "fit.place", "fit.dispatch"):
                    start = float(ev.start_ns)
                    spans.setdefault(ev.name, []).append(
                        (start, start + float(ev.duration_ns)))
    assert {name: len(v) for name, v in spans.items()} == {
        "test.caller": 1, "engine.fit": 1, "fit.prepare": 1, "fit.place": 1,
        "fit.dispatch": 1}

    def inside(inner, outer):
        (a, b), (c, d) = spans[inner][0], spans[outer][0]
        return c <= a <= b <= d

    assert inside("engine.fit", "test.caller")
    for child in ("fit.prepare", "fit.place", "fit.dispatch"):
        assert inside(child, "engine.fit")
    assert spans["fit.prepare"][0][1] <= spans["fit.place"][0][0]
    assert spans["fit.place"][0][1] <= spans["fit.dispatch"][0][0]


# ---------------------------------------------------------------------------
# scopes and lower_fit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["single", "vmap"])
def test_every_entry_op_of_the_fit_carries_a_stage_scope(name):
    text = _lowered_text(*PLANS[name])
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    ops = [line for line in entry.splitlines()
           if re.search(r" (fusion|dot|custom-call)\(", line)]
    assert any(" custom-call(" in line for line in ops)  # eigh, Cholesky
    seen = set()
    for line in ops:
        m = _OP_NAME.search(line)
        if m is None:
            # a fill the compiler made (zero biases): a fusion of one constant
            assert re.search(r" fusion\(%constant[\w.]*\)", line), line
            continue
        scopes = _scopes(m.group(1))
        assert scopes, line
        seen |= scopes
    assert seen == set(STAGES)


@pytest.mark.parametrize("name", ["single", "vmap", "chunked"])
def test_scopes_change_only_metadata(name, monkeypatch):
    plan, shape = PLANS[name]
    jax.clear_caches()
    scoped = _lowered_text(plan, shape)
    monkeypatch.setattr(jax, "named_scope", lambda _name: contextlib.nullcontext())
    jax.clear_caches()
    plain = _lowered_text(plan, shape)
    monkeypatch.undo()
    jax.clear_caches()
    encoder = re.compile(r'op_name="[^"]*[/(]encoder[)/]')
    assert encoder.search(scoped) and not encoder.search(plain)
    assert _structure(scoped) == _structure(plain)


def test_lower_fit_is_the_program_fit_runs():
    engine = DAEFEngine(CFG)
    x = _data((M0, N), seed=1)
    got = engine.fit(x)
    call = daef._fit_call(engine.config, x)
    # a compiled program is called without its static config argument
    out = engine.lower_fit(x).compile()(*call.place(call.args)[1:])
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(got), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fleet_lower_fit_is_the_program_fit_runs():
    engine = DAEFEngine(CFG, ExecutionPlan(mode="vmap", tenants=K))
    xs = _data((K, M0, N), seed=2)
    seeds = jnp.arange(K)
    got = engine.fit(xs, seeds=seeds)
    lowered = engine.lower_fit(jax.ShapeDtypeStruct(xs.shape, xs.dtype), seeds=seeds)
    call = fleet._fit_fleet_call(engine.config, xs, seeds, None, None)
    out = lowered.compile()(*call.place(call.args)[1:])
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(got.model), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lower_fit_raises_what_fit_raises():
    engine = DAEFEngine(CFG, ExecutionPlan(mode="vmap", tenants=K))
    with pytest.raises(Exception, match="tenants"):
        engine.lower_fit(jax.ShapeDtypeStruct((K + 1, M0, N), np.float32))
