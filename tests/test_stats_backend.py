"""Gram-stats producers: the fused Pallas kernels and the unfused einsum
path must be interchangeable everywhere stats are produced — single-model
fit, vmapped fleet, mesh-sharded fleet/core, federated fit and incremental
updates — at the per-dtype tolerances test_parity.py establishes for
execution-path parity.  (Same data, same randomness, two producers, each
selected the way the library learns it: through the autotune cache.)
"""
import dataclasses
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import daef, federated, fleet, fleet_sharded, rolann, stats_backend
from repro.core import activations
from repro.engine import DAEFEngine, ExecutionPlan
from repro.kernels import autotune
from repro.kernels.rolann_stats import rolann_stats, rolann_stats_batched
from repro.testing.producer import stats_producer
from repro.testing.proptest import given, settings, st

# Same bar as tests/test_parity.py's execution-path parity.
TOLS = {
    "float32": dict(atol=1e-4, rtol=1e-4),
    "float64": dict(atol=1e-9, rtol=1e-9),
}

M0, LATENT = 7, 3
LAYERS = (M0, LATENT, 5, M0)


def _cfg(method: str = "gram"):
    return daef.DAEFConfig(
        layer_sizes=LAYERS, lam_hidden=0.7, lam_last=0.9, method=method
    )


def _both(run):
    """``run()`` under the einsum producer, then under the fused one."""
    out = []
    for name in ("einsum", "fused"):
        with stats_producer(name):
            out.append(run())
    return out


def _data(k: int, n: int, seed: int, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    z = rng.normal(size=(k, LATENT, n))
    mix = rng.normal(size=(k, M0, LATENT))
    x = np.einsum("kmr,krn->kmn", mix, np.tanh(z))
    x = x + 0.1 * rng.normal(size=(k, M0, n))
    x = (x - x.mean(axis=2, keepdims=True)) / x.std(axis=2, keepdims=True)
    return jnp.asarray(x, dtype)


def _assert_trees_close(a, b, *, what: str):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        tol = TOLS[str(np.asarray(la).dtype)]
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), err_msg=what, **tol
        )


# ---------------------------------------------------------------------------
# the one decision: producer() follows the autotune cache per platform
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case,want,warning", [
    ("measured", "fused", None),
    ("unmeasured", "einsum", "no preferred_backend"),
    ("unknown", "einsum", "unknown preferred_backend"),
    ("corrupt", "einsum", "unreadable"),
])
def test_producer_follows_cache_per_platform(case, want, warning, tmp_path,
                                             monkeypatch):
    """A measured verdict for the running platform is obeyed; one for another
    platform only, an unknown name or an unreadable file leaves einsum, and
    says so."""
    plat = jax.default_backend()
    other = "tpu" if plat != "tpu" else "cpu"
    path = tmp_path / "cache.json"
    platforms = {other: {"preferred_backend": "fused"}}
    if case == "measured":
        platforms[plat] = {"preferred_backend": "fused"}
    elif case == "unknown":
        platforms[plat] = {"preferred_backend": "cuda_graphs"}
    if case == "corrupt":
        path.write_text("{not json")
    else:
        path.write_text(json.dumps({"version": 1, "platforms": platforms}))
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    autotune.clear_cache()
    try:
        if warning is None:
            with warnings.catch_warnings():
                warnings.simplefilter("error", RuntimeWarning)
                got = stats_backend.producer()
        else:
            with pytest.warns(RuntimeWarning, match=warning):
                got = stats_backend.producer()
    finally:
        autotune.clear_cache()
    assert got == want


def test_stats_producer_selects_and_restores():
    before = stats_backend.producer()
    for name in stats_backend.PRODUCERS:
        with stats_producer(name):
            assert stats_backend.producer() == name
        assert stats_backend.producer() == before
    with pytest.raises(ValueError, match="unknown producer"):
        with stats_producer("bogus"):
            pass


# ---------------------------------------------------------------------------
# gram_stats dispatch parity (the primitive both pipelines consume)
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(
    m=st.integers(min_value=2, max_value=24),
    n=st.integers(min_value=4, max_value=400),
    o=st.integers(min_value=1, max_value=5),
    seed=st.integers(min_value=0, max_value=99),
)
def test_gram_stats_backend_parity(m, n, o, seed):
    rng = np.random.default_rng(seed)
    xa = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    fsq = jnp.asarray(rng.uniform(0.05, 1.0, (o, n)), jnp.float32)
    fd = jnp.asarray(rng.normal(size=(o, n)), jnp.float32)
    ge, me = stats_backend.einsum_stats(xa, fsq, fd)
    gf, mf = rolann_stats(xa, fsq, fd)
    assert ge.dtype == gf.dtype and me.dtype == mf.dtype
    scale = max(1.0, float(jnp.abs(ge).max()))
    np.testing.assert_allclose(np.asarray(gf), np.asarray(ge), atol=2e-4 * scale)
    np.testing.assert_allclose(np.asarray(mf), np.asarray(me), atol=2e-4 * scale)


def test_gram_stats_batched_backend_parity():
    rng = np.random.default_rng(0)
    xa = jnp.asarray(rng.normal(size=(3, 6, 160)), jnp.float32)
    fsq = jnp.asarray(rng.uniform(0.05, 1.0, (3, 4, 160)), jnp.float32)
    fd = jnp.asarray(rng.normal(size=(3, 4, 160)), jnp.float32)
    ge, me = stats_backend.einsum_stats(xa, fsq, fd)
    gf, mf = rolann_stats_batched(xa, fsq, fd)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(ge), atol=2e-4)
    np.testing.assert_allclose(np.asarray(mf), np.asarray(me), atol=2e-4)


def test_compute_stats_backend_parity():
    act = activations.get("logsig", invertible_required=True)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(M0, 80)), jnp.float32)
    d = jnp.asarray(rng.uniform(0.1, 0.9, (4, 80)), jnp.float32)
    with stats_producer("einsum"):
        se = rolann.compute_stats(x, d, act)
    with stats_producer("fused"):
        sf = rolann.compute_stats(x, d, act)
        fe = rolann.compute_factors_via_gram(x, d, act)
    _assert_trees_close(se, sf, what="compute_stats einsum vs fused")
    np.testing.assert_allclose(  # factor round-trip carries the same Gram
        np.asarray(rolann.factors_to_stats(fe).g), np.asarray(se.g), atol=2e-4
    )


# ---------------------------------------------------------------------------
# pipeline parity: fit / predict / scores / merge, loop == vmap == sharded
# ---------------------------------------------------------------------------

@settings(max_examples=3, deadline=None)
@given(data_seed=st.integers(0, 7))
def test_fit_predict_scores_backend_parity(data_seed):
    k, n = 2, 72
    cfg = _cfg()
    xs = _data(k, n, data_seed)
    seeds = jnp.arange(k)
    tol = TOLS["float32"]
    d = len(jax.devices())
    while d > 1 and k % d:
        d //= 2
    mesh = fleet_sharded.tenant_mesh(d)

    def run():
        models = [daef.fit(dataclasses.replace(cfg, seed=i), xs[i])
                  for i in range(k)]
        fl = fleet.fleet_fit(cfg, xs, seeds=seeds)
        # mesh-sharded fleet (1-shard mesh in tier-1; split for real in
        # CI's multi-device job)
        fs = fleet_sharded.sharded_fleet_fit(cfg, np.asarray(xs), mesh,
                                             seeds=seeds)
        return models, fl, fleet.fleet_scores(cfg, fl, xs), fs

    (me, fe, se, _), (mf, ff, sf, fs) = _both(run)

    # loop (single-model core)
    for i in range(k):
        _assert_trees_close(me[i], mf[i],
                            what=f"daef.fit backend parity, tenant {i}")
        np.testing.assert_allclose(
            np.asarray(daef.predict(cfg, mf[i], xs[i])),
            np.asarray(daef.predict(cfg, me[i], xs[i])), **tol,
        )
        np.testing.assert_allclose(
            np.asarray(daef.reconstruction_error(cfg, mf[i], xs[i])),
            np.asarray(daef.reconstruction_error(cfg, me[i], xs[i])), **tol,
        )

    # vmap fleet
    _assert_trees_close(fe.model, ff.model, what="fleet_fit backend parity")
    np.testing.assert_allclose(np.asarray(sf), np.asarray(se), **tol)
    _assert_trees_close(fs.model, fe.model, what="sharded fused vs vmap einsum")


def test_merge_and_partial_fit_backend_parity():
    k = 2
    cfg = _cfg()
    xa, xb = _data(k, 64, 1), _data(k, 64, 101)
    seeds = jnp.arange(k)

    def run():
        fa, fb = (fleet.fleet_fit(cfg, x, seeds=seeds) for x in (xa, xb))
        return (fleet.fleet_merge(cfg, fa, fb).model,
                fleet.fleet_partial_fit(cfg, fa, xb).model)

    (merged_e, part_e), (merged_f, part_f) = _both(run)
    _assert_trees_close(merged_f, merged_e, what="fleet_merge backend parity")
    _assert_trees_close(part_f, part_e, what="fleet_partial_fit backend parity")


def test_merge_tree_backend_parity():
    k, group = 4, 2
    cfg = _cfg()
    xs = _data(k, 48, 9)
    seeds = jnp.repeat(jnp.arange(k // group), group)

    def run():
        fl = fleet.fleet_fit(cfg, xs, seeds=seeds)
        return fleet_sharded.fleet_merge_tree(cfg, fl, group).model

    te, tf = _both(run)
    _assert_trees_close(tf, te, what="merge_tree backend parity")


def test_federated_fit_backend_parity():
    cfg = _cfg()
    x = _data(1, 96, 17)[0]
    parts = [x[:, :48], x[:, 48:]]
    fe, ff = _both(lambda: federated.federated_fit(cfg, parts))
    _assert_trees_close(ff, fe, what="federated_fit backend parity")


def test_svd_method_ignores_backend_but_accepts_it():
    """method='svd' computes factors directly (no Gram) — a fit under the
    fused producer must still work and match einsum exactly there."""
    cfg = _cfg(method="svd")
    x = _data(1, 64, 3)[0]
    me, mf = _both(lambda: daef.fit(cfg, x))
    _assert_trees_close(mf, me, what="svd method backend-independence")


def test_vmapped_gram_stats_routes_through_batched(monkeypatch):
    """The fleet engine's tenant vmap must collapse gram_stats into ONE
    tenant-batched dispatch (the custom_vmap rule) — for the fused producer
    a single rolann_stats_batched kernel launch, not Pallas' generic
    per-tenant batching rule — and agree with the per-tenant loop."""
    calls = []
    orig = stats_backend.gram_stats_batched

    def spy(xa, fsq, fd):
        calls.append((tuple(xa.shape), stats_backend.producer()))
        return orig(xa, fsq, fd)

    monkeypatch.setattr(stats_backend, "gram_stats_batched", spy)
    rng = np.random.default_rng(0)
    xa = jnp.asarray(rng.normal(size=(5, 6, 40)), jnp.float32)
    fsq = jnp.asarray(rng.uniform(0.1, 1.0, (5, 3, 40)), jnp.float32)
    fd = jnp.asarray(rng.normal(size=(5, 3, 40)), jnp.float32)
    for name in stats_backend.PRODUCERS:
        with stats_producer(name):
            calls.clear()
            g, m = jax.vmap(stats_backend.gram_stats)(xa, fsq, fd)
            assert calls, f"{name}: batched variant was not dispatched"
            assert calls[0] == ((5, 6, 40), name)
            for i in range(5):
                gi, mi = stats_backend.gram_stats(xa[i], fsq[i], fd[i])
                np.testing.assert_allclose(np.asarray(g[i]), np.asarray(gi),
                                           atol=1e-5, rtol=1e-5)
                np.testing.assert_allclose(np.asarray(m[i]), np.asarray(mi),
                                           atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# the choice is made at trace time, so a compiled program holds one producer
# ---------------------------------------------------------------------------

def _kernel_launches(engine, xs) -> int:
    text = engine.lower_fit(xs, seeds=jnp.arange(xs.shape[0])).compile().as_text()
    return text.count("rolann_stats")


def test_compiled_fit_follows_the_producer_it_was_traced_under():
    """The stale-trace guard: a fleet fit compiled under a fused cache holds
    the rolann_stats kernels; after switching back to an einsum cache the
    recompiled program holds none, and both fit the same model."""
    k = 4
    xs = _data(k, 48, 23)
    engine = DAEFEngine(_cfg(), ExecutionPlan(mode="vmap", tenants=k))
    fits, launches = {}, {}
    for name in ("fused", "einsum"):
        with stats_producer(name):
            launches[name] = _kernel_launches(engine, xs)
            fits[name] = engine.fit(xs, seeds=jnp.arange(k))
    assert launches["fused"] > 0, "fused producer compiled no rolann_stats launch"
    assert launches["einsum"] == 0, "einsum program kept a stale fused trace"
    _assert_trees_close(fits["fused"].model, fits["einsum"].model,
                        what="fit under each compiled producer")


def test_engine_keeps_the_config_it_was_given():
    """No producer is folded into the config: the engine holds the very
    config object it received, whatever producer is active."""
    cfg = _cfg()
    for name in stats_backend.PRODUCERS:
        with stats_producer(name):
            assert DAEFEngine(cfg).config is cfg
            assert DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=2)).config is cfg
