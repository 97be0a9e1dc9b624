"""Streaming-training parity: chunked/streamed fits == one-shot fits.

The chunked scan core (`daef.fit_chunked`, `ExecutionPlan(chunk_samples=...)`)
and the host-iterator driver (`daef.fit_stream` / `DAEFEngine.fit_stream`)
must reproduce the one-shot gram-method fit for every execution mode
(loop / vmap / mesh) and both Gram-stats producers (einsum / fused), within the
same per-dtype tolerances as tests/test_parity.py — plus chunk-size
invariance (ragged tails, chunk == n, chunk == 1) and the iterator
semantics of ``fit_stream`` (lists, one-shot generators, per-pass callable
sources; mid-stream shape changes rejected).

Runs single-device in tier-1 (the mesh plan degenerates to a 1-device
tenant mesh) and split-for-real in CI's 8-virtual-device job.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import daef, fleet, stats_backend
from repro.engine import DAEFEngine, ExecutionPlan, PlanError
from repro.testing.producer import stats_producer
from repro.testing.proptest import given, settings, st

TOLS = {
    "float32": dict(atol=1e-4, rtol=1e-4),
    "float64": dict(atol=1e-9, rtol=1e-9),
}

M0, LATENT = 7, 3
LAYERS = (M0, LATENT, 5, M0)


def _cfg(**kw) -> daef.DAEFConfig:
    kw.setdefault("layer_sizes", LAYERS)
    kw.setdefault("lam_hidden", 0.7)
    kw.setdefault("lam_last", 0.9)
    return daef.DAEFConfig(**kw)


def _data(k: int, n: int, seed: int):
    rng = np.random.default_rng(seed)
    z = rng.normal(size=(k, LATENT, n))
    mix = rng.normal(size=(k, M0, LATENT))
    x = np.einsum("kmr,krn->kmn", mix, np.tanh(z))
    x = x + 0.1 * rng.normal(size=(k, M0, n))
    x = (x - x.mean(axis=2, keepdims=True)) / x.std(axis=2, keepdims=True)
    return jnp.asarray(x, jnp.float32)


def _assert_close(a, b, *, what: str):
    """Model equivalence at test_parity tolerances, with the encoder factors
    compared in their invariant form: the leading ``latent_dim`` columns
    (the actual encoder weights) plus the reconstructed ``U S^2 U^T`` Gram
    (the exchanged/mergeable statistic).  The *trailing* untruncated
    eigenvectors sit in near-degenerate noise eigenspaces, where a 1e-6
    accumulation-order perturbation of G legitimately rotates the basis —
    nothing the model uses or exchanges depends on that basis choice."""

    def leaves(model):
        rest = model._replace(encoder_factors=None)
        return jax.tree.leaves(rest)

    for la, lb in zip(leaves(a), leaves(b), strict=True):
        tol = TOLS[str(np.asarray(la).dtype)]
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), err_msg=what, **tol
        )
    ea, eb = a.encoder_factors, b.encoder_factors
    tol = TOLS[str(np.asarray(ea.u).dtype)]
    np.testing.assert_allclose(
        np.asarray(ea.u[..., :, :LATENT]), np.asarray(eb.u[..., :, :LATENT]),
        err_msg=f"{what}: encoder weights", **tol,
    )
    np.testing.assert_allclose(
        np.asarray(ea.s), np.asarray(eb.s), err_msg=f"{what}: encoder s", **tol
    )
    ga = np.einsum("...ir,...r,...jr->...ij", ea.u, np.asarray(ea.s) ** 2, ea.u)
    gb = np.einsum("...ir,...r,...jr->...ij", eb.u, np.asarray(eb.s) ** 2, eb.u)
    scale = max(1.0, float(np.abs(gb).max()))
    np.testing.assert_allclose(
        ga, gb, err_msg=f"{what}: encoder U S^2 U^T",
        atol=tol["atol"] * scale, rtol=tol["rtol"],
    )


def _plan(mode: str, k: int, **kw) -> ExecutionPlan:
    return ExecutionPlan(mode=mode, tenants=k, **kw)


# ---------------------------------------------------------------------------
# chunked == one-shot: every mode x both producers
# ---------------------------------------------------------------------------

# The fused producer runs the Pallas kernels in interpret mode on CPU — full
# coverage, but slow; those combos ride the slow tier (still executed by
# CI's multi-device job, which selects "slow or not slow").
BACKEND_PARAMS = [
    pytest.param(b, marks=[pytest.mark.slow] if b == "fused" else [])
    for b in stats_backend.PRODUCERS
]


LOOP_SLOW_MODES = [
    pytest.param("loop", marks=pytest.mark.slow),  # eager per-tenant traces
    "vmap",
    "mesh",
]


@pytest.mark.parametrize("backend", BACKEND_PARAMS)
@pytest.mark.parametrize("mode", LOOP_SLOW_MODES)
def test_chunked_fit_matches_oneshot(mode, backend):
    k, n = 2, 48
    cfg = _cfg()
    xs = _data(k, n, seed=0)
    seeds = jnp.arange(k)

    eng = DAEFEngine(cfg, _plan(mode, k, chunk_samples=20))  # ragged tail
    with stats_producer(backend):
        ref = DAEFEngine(cfg, _plan(mode, k)).fit(xs, seeds=seeds)
        got = eng.fit(xs, seeds=seeds)
    _assert_close(got.model, ref.model, what=f"{mode}/{backend} chunked fit")

    scores_ref = DAEFEngine(cfg, _plan(mode, k)).scores(ref, xs)
    scores_got = eng.scores(got, xs)
    np.testing.assert_allclose(
        np.asarray(scores_got), np.asarray(scores_ref), **TOLS["float32"]
    )


@pytest.mark.parametrize("backend", BACKEND_PARAMS)
@pytest.mark.parametrize("mode", ["loop", "vmap", "mesh"])
def test_fit_stream_matches_oneshot(mode, backend):
    k, n = 2, 48
    cfg = _cfg()
    xs = _data(k, n, seed=1)
    seeds = jnp.arange(k)

    eng = DAEFEngine(cfg, _plan(mode, k, chunk_samples=20))
    chunks = [np.asarray(xs[:, :, i:i + 20]) for i in range(0, n, 20)]
    with stats_producer(backend):
        ref = DAEFEngine(cfg, _plan(mode, k)).fit(xs, seeds=seeds)
        got = eng.fit_stream(chunks, seeds=seeds)
    _assert_close(got.model, ref.model, what=f"{mode}/{backend} fit_stream")


@pytest.mark.parametrize(
    "mode",
    [pytest.param("loop", marks=pytest.mark.slow), "vmap",
     pytest.param("mesh", marks=pytest.mark.slow)],
)
def test_chunked_partial_fit_matches_oneshot(mode):
    k = 2
    cfg = _cfg()
    xs, xs2 = _data(k, 48, seed=2), _data(k, 32, seed=3)
    seeds = jnp.arange(k)

    ref_eng = DAEFEngine(cfg, _plan(mode, k))
    ch_eng = DAEFEngine(cfg, _plan(mode, k, chunk_samples=17))
    ref = ref_eng.partial_fit(ref_eng.fit(xs, seeds=seeds), xs2)
    got = ch_eng.partial_fit(ch_eng.fit(xs, seeds=seeds), xs2)
    _assert_close(got.model, ref.model, what=f"{mode} chunked partial_fit")


@pytest.mark.parametrize(
    "mode",
    [pytest.param("loop", marks=pytest.mark.slow), "vmap",
     pytest.param("mesh", marks=pytest.mark.slow)],
)
def test_merge_under_chunked_plan(mode):
    """Federated merge of two chunk-trained fleets == merge of one-shot
    fleets (the knowledge itself is parity-checked by the fit tests)."""
    k = 2
    cfg = _cfg()
    xa, xb = _data(k, 40, seed=4), _data(k, 40, seed=5)
    seeds = jnp.asarray([7, 7])

    ref_eng = DAEFEngine(cfg, _plan(mode, k))
    ch_eng = DAEFEngine(cfg, _plan(mode, k, chunk_samples=16))
    ref = ref_eng.merge(ref_eng.fit(xa, seeds=seeds), ref_eng.fit(xb, seeds=seeds))
    got = ch_eng.merge(ch_eng.fit(xa, seeds=seeds), ch_eng.fit(xb, seeds=seeds))
    _assert_close(got.model, ref.model, what=f"{mode} chunked merge")


# ---------------------------------------------------------------------------
# chunk-size invariance
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(
    chunk=st.sampled_from([1, 7, 17, 48, 64]),
    data_seed=st.integers(0, 5),
)
def test_chunk_size_invariance(chunk, data_seed):
    """Any chunk width reproduces the one-shot fit: chunk == 1, widths that
    do not divide n (padded+masked ragged tail), chunk == n, chunk > n."""
    n = 48
    cfg = _cfg()
    x = _data(1, n, seed=data_seed)[0]
    ref = daef.fit(cfg, x)
    got = daef.fit_chunked(cfg, x, chunk_samples=chunk)
    _assert_close(got, ref, what=f"chunk={chunk}")


def test_chunk_equals_n_is_bit_exact():
    """A single full-width chunk takes the identical contraction path (an
    all-ones mask multiply), so the statistics match bit for bit."""
    n = 48
    cfg = _cfg()
    x = _data(1, n, seed=9)[0]
    ref = daef.fit(cfg, x)
    got = daef.fit_chunked(cfg, x, chunk_samples=n)
    for la, lb in zip(jax.tree.leaves(got), jax.tree.leaves(ref), strict=True):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# fit_stream iterator semantics
# ---------------------------------------------------------------------------

def test_fit_stream_source_kinds():
    """Lists, one-shot generators and per-pass callables all agree with the
    in-memory fit; a generator is snapshotted (multi-pass safe)."""
    n = 44
    cfg = _cfg()
    x = _data(1, n, seed=6)[0]
    ref = daef.fit(cfg, x)
    host = np.asarray(x)

    as_list = [host[:, i:i + 16] for i in range(0, n, 16)]
    as_gen = (host[:, i:i + 16] for i in range(0, n, 16))
    calls = []

    def as_callable():
        calls.append(1)
        return (host[:, i:i + 16] for i in range(0, n, 16))

    for src, what in ((as_list, "list"), (as_gen, "generator"),
                      (as_callable, "callable")):
        got = daef.fit_stream(cfg, src)
        _assert_close(got, ref, what=f"fit_stream {what}")
    # one pass per layer (2 decoder solves here) + encoder + errors = 4
    assert len(calls) == len(LAYERS) - 2 + 2


def test_fit_stream_ragged_tail_masked_exactly():
    n = 45  # 16 + 16 + 13: ragged tail
    cfg = _cfg()
    x = _data(1, n, seed=7)[0]
    ref = daef.fit(cfg, x)
    got = daef.fit_stream(cfg, [np.asarray(x[:, i:i + 16]) for i in range(0, n, 16)])
    _assert_close(got, ref, what="ragged tail")
    assert got.train_errors.shape == (n,)


def test_fit_stream_rejects_bad_streams():
    cfg = _cfg()
    x = np.asarray(_data(1, 48, seed=8)[0])
    with pytest.raises(ValueError, match="empty chunk stream"):
        daef.fit_stream(cfg, [])
    with pytest.raises(ValueError, match="mid-stream"):
        daef.fit_stream(cfg, [x[:, :16], x[:, 16:24], x[:, 24:48]])
    with pytest.raises(ValueError, match="wider final"):
        daef.fit_stream(cfg, [x[:, :16], x[:, 16:48]])
    with pytest.raises(ValueError, match="does not match"):
        daef.fit_stream(cfg, [x[:3, :16]])
    with pytest.raises(ValueError, match="gram"):
        daef.fit_stream(dataclasses.replace(cfg, method="svd"), [x[:, :16]])
    with pytest.raises(ValueError, match="gram"):
        daef.fit_chunked(dataclasses.replace(cfg, method="svd"), x,
                         chunk_samples=16)
    with pytest.raises(ValueError, match="chunk_samples"):
        daef.fit_chunked(cfg, x, chunk_samples=0)


def test_fleet_fit_stream_rejects_tenant_mismatch():
    cfg = _cfg()
    xs = np.asarray(_data(2, 32, seed=9))
    eng = DAEFEngine(cfg, _plan("vmap", 2, chunk_samples=16))
    with pytest.raises(ValueError, match="tenants"):
        eng.fit_stream([xs[:, :, :16], xs[:1, :, 16:32]])
    with pytest.raises(PlanError, match="fleet chunks"):
        DAEFEngine(cfg, _plan("loop", 2, chunk_samples=16)).fit_stream(
            [xs[0, :, :16]]
        )
    # a stream whose K disagrees with the plan from the FIRST chunk must be
    # rejected, not silently train a smaller fleet
    big = DAEFEngine(cfg, _plan("vmap", 4, chunk_samples=16))
    with pytest.raises(ValueError, match="tenants"):
        big.fit_stream([xs[:, :, :16], xs[:, :, 16:32]])


def test_config_gram_solver_threads_through_fit():
    """DAEFConfig.gram_solver selects the weight-solve route everywhere:
    'eigh' reproduces the pre-Cholesky path and agrees with the default at
    parity tolerances for plain, chunked and streamed fits."""
    x = _data(1, 48, seed=11)[0]
    ref = daef.fit(_cfg(), x)
    for maker in (
        lambda c: daef.fit(c, x),
        lambda c: daef.fit_chunked(c, x, chunk_samples=20),
        lambda c: daef.fit_stream(c, [np.asarray(x[:, i:i + 20])
                                      for i in range(0, 48, 20)]),
    ):
        got = maker(_cfg(gram_solver="eigh"))
        _assert_close(got, ref, what="gram_solver='eigh'")
    with pytest.raises(ValueError, match="gram_solver"):
        _cfg(gram_solver="lu")


# ---------------------------------------------------------------------------
# plan validation
# ---------------------------------------------------------------------------

def test_plan_chunk_samples_validation():
    with pytest.raises(PlanError, match="positive int"):
        ExecutionPlan(chunk_samples=0)
    with pytest.raises(PlanError, match="positive int"):
        ExecutionPlan(chunk_samples=2.5)
    with pytest.raises(PlanError, match="sample axis"):
        ExecutionPlan(mode="mesh", tenants=1, mesh_axes=("data",),
                      chunk_samples=8)
    with pytest.raises(PlanError, match="method='gram'"):
        DAEFEngine(_cfg(method="svd"), ExecutionPlan(chunk_samples=8))
    with pytest.raises(PlanError, match="n_partitions"):
        DAEFEngine(_cfg(), ExecutionPlan(tenants=1, chunk_samples=8)).fit(
            _data(1, 32, seed=0)[0], n_partitions=2
        )
    with pytest.raises(PlanError, match="method='gram'"):
        DAEFEngine(_cfg(method="svd"), ExecutionPlan(tenants=1)).fit_stream(
            [np.zeros((M0, 8), np.float32)]
        )


# ---------------------------------------------------------------------------
# the streamed fleet reaches the tenant-batched accumulating dispatch
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_chunked_routes_through_batched_acc(monkeypatch):
    """The fleet's chunked fit must fold per-layer stats through ONE
    tenant-batched accumulating dispatch per chunk (`gram_stats_acc`'s
    custom_vmap rule -> `gram_stats_acc_batched`), not K per-tenant folds."""
    calls = []
    orig = stats_backend.gram_stats_acc_batched

    def spy(g, m, xa, fsq, fd):
        calls.append((tuple(xa.shape), stats_backend.producer()))
        return orig(g, m, xa, fsq, fd)

    monkeypatch.setattr(stats_backend, "gram_stats_acc_batched", spy)
    k, n, chunk = 3, 36, 12
    xs = _data(k, n, seed=10)
    cfg = _cfg()
    for backend in stats_backend.PRODUCERS:
        with stats_producer(backend):
            calls.clear()
            fl = fleet._fit_fleet_chunked(
                cfg, xs, chunk_samples=chunk, seeds=jnp.arange(k)
            )
            assert calls, f"{backend}: batched accumulator was not dispatched"
            # chunk axis padded to the lane floor by the kernel wrapper, but
            # the tenant-batched layout [K, ., chunk] must be intact
            assert all(c[0][0] == k and c[1] == backend for c in calls)
            ref = fleet._fit_fleet(cfg, xs, seeds=jnp.arange(k))
            _assert_close(fl.model, ref.model,
                          what=f"{backend} batched-acc chunked fleet")


# ---------------------------------------------------------------------------
# retrace hygiene: trace count must be flat in the number of chunks
# ---------------------------------------------------------------------------

def test_chunked_fit_trace_count_flat_in_chunks():
    """The chunked scan re-uses one traced step regardless of how many
    chunks the stream is cut into: cold trace counts for 4 chunks and for
    8 chunks of the same (k, n) must match, and a warm re-run is free."""
    from repro.analysis import retrace

    k, n = 2, 128
    cfg = _cfg()
    xs = _data(k, n, seed=21)
    seeds = jnp.arange(k)

    jax.clear_caches()
    with retrace.trace_guard(what="chunk=32 cold") as four:
        fleet._fit_fleet_chunked(cfg, xs, chunk_samples=32, seeds=seeds)

    jax.clear_caches()
    with retrace.trace_guard(what="chunk=16 cold") as eight:
        fleet._fit_fleet_chunked(cfg, xs, chunk_samples=16, seeds=seeds)

    assert four.traces == eight.traces, (
        f"trace count grew with chunk count: {four.traces} vs "
        f"{eight.traces} ({eight.traced_names})"
    )
    # Same shapes again: everything must come out of the cache.
    with retrace.trace_guard(max_traces=0, what="chunk=16 warm"):
        fleet._fit_fleet_chunked(cfg, xs, chunk_samples=16, seeds=seeds)
