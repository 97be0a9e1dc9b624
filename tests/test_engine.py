"""Facade parity: the repro.engine API == the direct module-level calls.

The engine is pure dispatch — every ``ExecutionPlan`` mode must reproduce
the direct-call results bit-for-bit (same kernels) or within the
tests/test_parity.py tolerances (different execution order), under both
Gram-stats producers; plan validation must be loud and actionable.  These
tests are the acceptance bar for the API redesign: if they pass, rewriting a
caller from the old entry points onto the facade is a no-op.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import daef, federated, fleet, fleet_sharded, sharded
from repro.engine import (
    DAEFEngine,
    ExecutionPlan,
    FederationSession,
    PlanError,
    deprecation,
)
from repro.testing.producer import stats_producer

# Same bar as tests/test_parity.py's execution-path parity.
TOLS = {
    "float32": dict(atol=1e-4, rtol=1e-4),
    "float64": dict(atol=1e-9, rtol=1e-9),
}

M0, LATENT = 7, 3
LAYERS = (M0, LATENT, 5, M0)
MODES = ("loop", "vmap", "mesh")


def _cfg(method: str = "gram") -> daef.DAEFConfig:
    return daef.DAEFConfig(
        layer_sizes=LAYERS, lam_hidden=0.7, lam_last=0.9, method=method,
    )


def _data(k: int, n: int, seed: int, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    z = rng.normal(size=(k, LATENT, n))
    mix = rng.normal(size=(k, M0, LATENT))
    x = np.einsum("kmr,krn->kmn", mix, np.tanh(z))
    x = x + 0.1 * rng.normal(size=(k, M0, n))
    x = (x - x.mean(axis=2, keepdims=True)) / x.std(axis=2, keepdims=True)
    return jnp.asarray(x, dtype)


def _assert_trees_close(a, b, *, what: str, atol=None, rtol=None):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        la, lb = np.asarray(la), np.asarray(lb)
        if not np.issubdtype(la.dtype, np.floating):
            np.testing.assert_array_equal(la, lb, err_msg=what)
            continue
        tol = TOLS[str(la.dtype)]
        if atol is not None:
            tol = dict(atol=atol, rtol=rtol if rtol is not None else atol)
        np.testing.assert_allclose(la, lb, err_msg=what, **tol)


# ---------------------------------------------------------------------------
# fit / predict / scores parity, all modes x both producers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["einsum", "fused"])
@pytest.mark.parametrize("mode", MODES)
def test_fit_predict_scores_parity(mode, backend):
    k, n = 4, 64
    cfg = _cfg("gram")
    xs = _data(k, n, seed=0)
    seeds = jnp.arange(k)
    engine = DAEFEngine(cfg, ExecutionPlan(mode=mode, tenants=k))

    with stats_producer(backend):
        fl = engine.fit(xs, seeds=seeds)
        refs = [daef.fit(dataclasses.replace(cfg, seed=i), xs[i])
                for i in range(k)]
    assert isinstance(fl, fleet.DAEFFleet) and fl.size == k
    recon = engine.predict(fl, xs)
    scores = engine.scores(fl, xs)

    for i in range(k):
        cfg_i = dataclasses.replace(cfg, seed=i)
        ref = refs[i]
        _assert_trees_close(
            engine.get_model(fl, i), ref, what=f"{mode} fit, tenant {i}"
        )
        tol = TOLS["float32"]
        np.testing.assert_allclose(
            np.asarray(recon[i]), np.asarray(daef.predict(cfg_i, ref, xs[i])),
            err_msg=f"{mode} predict", **tol,
        )
        np.testing.assert_allclose(
            np.asarray(scores[i]),
            np.asarray(daef.reconstruction_error(cfg_i, ref, xs[i])),
            err_msg=f"{mode} scores", **tol,
        )


@pytest.mark.parametrize("mode", MODES)
def test_fit_parity_svd_method(mode):
    k, n = 4, 64
    cfg = _cfg("svd")
    xs = _data(k, n, seed=3)
    engine = DAEFEngine(cfg, ExecutionPlan(mode=mode, tenants=k))
    fl = engine.fit(xs, seeds=jnp.arange(k))
    for i in range(k):
        ref = daef.fit(dataclasses.replace(cfg, seed=i), xs[i])
        _assert_trees_close(
            engine.get_model(fl, i), ref, what=f"{mode} svd fit, tenant {i}"
        )


def test_scores_mask_padding_all_modes():
    k, n = 4, 32
    cfg = _cfg()
    xs = _data(k, n, seed=5)
    n_valid = jnp.asarray([n, 1, n // 2, n - 1])
    ref = None
    for mode in MODES:
        engine = DAEFEngine(cfg, ExecutionPlan(mode=mode, tenants=k))
        fl = engine.fit(xs)
        s = np.asarray(engine.scores(fl, xs, n_valid=n_valid))
        for t in range(k):
            assert np.isfinite(s[t, : int(n_valid[t])]).all()
            assert np.isnan(s[t, int(n_valid[t]):]).all()
        ref = s if ref is None else ref
        np.testing.assert_allclose(
            np.nan_to_num(s), np.nan_to_num(ref), **TOLS["float32"]
        )


# ---------------------------------------------------------------------------
# single-model plans (tenants=1), incl. the data-sharded mesh path
# ---------------------------------------------------------------------------

def test_single_model_modes_match_direct_fit():
    n = 64
    x = _data(1, n, seed=7)[0]
    cfg = _cfg()
    ref = daef.fit(cfg, x, n_partitions=2)
    for mode in ("loop", "vmap"):
        engine = DAEFEngine(cfg, ExecutionPlan(mode=mode, tenants=1))
        model = engine.fit(x, n_partitions=2)
        assert isinstance(model, daef.DAEFModel)
        _assert_trees_close(model, ref, what=f"single-model {mode}")
        np.testing.assert_allclose(
            np.asarray(engine.scores(model, x)),
            np.asarray(daef.reconstruction_error(cfg, ref, x)),
            **TOLS["float32"],
        )
    # incremental
    x2 = _data(1, 32, seed=8)[0]
    engine = DAEFEngine(cfg)
    upd = engine.partial_fit(engine.fit(x), x2)
    _assert_trees_close(
        upd, daef.partial_fit(cfg, daef.fit(cfg, x), x2),
        what="single partial_fit", atol=0,
    )


@pytest.mark.slow
def test_data_sharded_mesh_plan_matches_fit_on_mesh():
    cfg = _cfg()
    x = _data(1, 64, seed=9)[0]
    engine = DAEFEngine(cfg, ExecutionPlan(mode="mesh", mesh_axes=("data",)))
    model = engine.fit(x)
    assert isinstance(model, daef.DAEFModel)
    ref = sharded._fit_on_mesh(cfg, x, engine.mesh, data_axes=("data",))
    _assert_trees_close(model, ref, what="data-sharded mesh fit", atol=0)
    np.testing.assert_allclose(
        np.asarray(engine.scores(model, x)),
        np.asarray(daef.reconstruction_error(cfg, model, x)),
        **TOLS["float32"],
    )


# ---------------------------------------------------------------------------
# merge / reduce / federation rounds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_merge_parity(mode):
    k = 4
    cfg = _cfg()
    xa, xb = _data(k, 48, seed=1), _data(k, 48, seed=101)
    engine = DAEFEngine(cfg, ExecutionPlan(mode=mode, tenants=k))
    fa, fb = engine.fit(xa, seeds=jnp.arange(k)), engine.fit(xb, seeds=jnp.arange(k))
    merged = engine.merge(fa, fb)
    for i in range(k):
        ref = daef.merge_models(
            dataclasses.replace(cfg, seed=i),
            engine.get_model(fa, i), engine.get_model(fb, i),
        )
        _assert_trees_close(
            engine.get_model(merged, i), ref, what=f"{mode} merge, tenant {i}"
        )


@pytest.mark.parametrize("mode", MODES)
def test_merge_rejects_mismatched_seeds_in_every_mode(mode):
    """The shared-randomness guard must hold in ALL modes — loop included
    (it is the parity baseline, not a validation escape hatch)."""
    k = 2
    cfg = _cfg()
    xs = _data(k, 32, seed=2)
    engine = DAEFEngine(cfg, ExecutionPlan(mode=mode, tenants=k))
    fa = engine.fit(xs, seeds=jnp.arange(k))
    fb = engine.fit(xs, seeds=jnp.arange(k) + 100)
    with pytest.raises(ValueError, match="different per-tenant seeds"):
        engine.merge(fa, fb)


def test_for_tenants_serves_reduced_fleet():
    k, group = 8, 4
    cfg = _cfg()
    xs = _data(k, 40, seed=4)
    engine = DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=k,
                                           merge="pairwise"))
    fl = engine.fit(xs, seeds=jnp.repeat(jnp.arange(k // group), group))
    sites = engine.reduce(fl, group)
    with pytest.raises(PlanError, match="fleet has 2 tenants"):
        engine.scores(sites, xs[: k // group])
    derived = engine.for_tenants(sites.size)
    assert derived.plan.tenants == sites.size
    assert derived.plan.mode == "vmap" and derived.plan.merge == "pairwise"
    s = derived.scores(sites, xs[: k // group])
    assert s.shape == (k // group, 40)
    mus = derived.thresholds(sites, rule="q90")
    assert derived.classify(s, mus).shape == s.shape
    # mesh plans drop a no-longer-dividing device count instead of erroring
    mesh_eng = DAEFEngine(cfg, ExecutionPlan(mode="mesh", tenants=k))
    assert mesh_eng.for_tenants(3).plan.tenants == 3


@pytest.mark.parametrize("merge", ["sequential", "pairwise", "tree"])
def test_reduce_matches_sequential_reduction(merge):
    k, group = 8, 4
    cfg = _cfg()
    xs = _data(k, 48, seed=11)
    seeds = jnp.repeat(jnp.arange(k // group), group)
    engine = DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=k, merge=merge))
    fl = engine.fit(xs, seeds=seeds)
    red = engine.reduce(fl, group)
    assert red.size == k // group
    for i in range(k // group):
        cfg_i = dataclasses.replace(cfg, seed=i)
        ref = functools.reduce(
            lambda a, b: daef.merge_models(cfg_i, a, b),
            [fleet.get_model(fl, i * group + j) for j in range(group)],
        )
        # deeper reductions accumulate float error over log2(group) rounds
        _assert_trees_close(
            fleet.get_model(red, i), ref, what=f"reduce[{merge}] group {i}",
            atol=1e-4 * group, rtol=1e-3,
        )


@pytest.mark.parametrize("merge", ["sequential", "pairwise", "tree"])
def test_session_round_parity(merge):
    cfg = _cfg()
    x = _data(1, 96, seed=13)[0]
    parts = [x[:, :24], x[:, 24:48], x[:, 48:72], x[:, 72:]]
    session = DAEFEngine(cfg, ExecutionPlan(merge=merge)).session()
    assert isinstance(session, FederationSession)
    agg = session.round(parts)
    assert session.rounds_run == 1

    if merge == "sequential":
        # the exact layer-synchronized protocol, bit-for-bit
        ref = federated._federated_fit(cfg, parts)
        _assert_trees_close(agg, ref, what="session sequential", atol=0)
    else:
        # broker protocol: local fits + (tree) reduction of the knowledge
        locals_ = [daef.fit(cfg, p) for p in parts]
        ref = functools.reduce(
            lambda a, b: daef.merge_models(cfg, a, b), locals_
        )
        _assert_trees_close(agg, ref, what=f"session {merge}",
                            atol=5e-4, rtol=1e-3)


def test_session_accumulates_across_rounds():
    cfg = _cfg()
    xa = _data(1, 48, seed=17)[0]
    xb = _data(1, 48, seed=18)[0]
    session = DAEFEngine(cfg, ExecutionPlan(merge="sequential")).session()
    first = session.round([xa[:, :24], xa[:, 24:]])
    second = session.round([xb[:, :24], xb[:, 24:]])
    assert session.rounds_run == 2
    ref = daef.merge_models(
        cfg,
        federated._federated_fit(cfg, [xa[:, :24], xa[:, 24:]]),
        federated._federated_fit(cfg, [xb[:, :24], xb[:, 24:]]),
    )
    _assert_trees_close(second, ref, what="two-round session", atol=0)
    session.reset()
    assert session.rounds_run == 0 and session.model is None
    _assert_trees_close(session.round([xa[:, :24], xa[:, 24:]]), first,
                        what="post-reset round", atol=0)


# ---------------------------------------------------------------------------
# Gram-stats producers through the engine
# ---------------------------------------------------------------------------

def test_backend_parity_through_engine():
    """fused == einsum through the facade (vmap plan), test_parity bar."""
    k = 4
    xs = _data(k, 56, seed=19)
    engine = DAEFEngine(_cfg(), ExecutionPlan(mode="vmap", tenants=k))
    fls = {}
    for backend in ("einsum", "fused"):
        with stats_producer(backend):
            fls[backend] = engine.fit(xs, seeds=jnp.arange(k))
    _assert_trees_close(fls["einsum"].model, fls["fused"].model,
                        what="backend parity via engine")


# ---------------------------------------------------------------------------
# actionable plan / input errors
# ---------------------------------------------------------------------------

def test_plan_validation_errors():
    with pytest.raises(PlanError, match="unknown ExecutionPlan mode"):
        ExecutionPlan(mode="warp")
    with pytest.raises(PlanError, match="unknown ExecutionPlan merge"):
        ExecutionPlan(merge="blend")
    with pytest.raises(PlanError, match="positive int"):
        ExecutionPlan(tenants=0)
    with pytest.raises(PlanError, match="bad mesh size"):
        ExecutionPlan(mode="mesh", tenants=5, mesh_devices=3)
    with pytest.raises(PlanError, match="only applies to mode='mesh'"):
        ExecutionPlan(mode="vmap", mesh_devices=2)
    with pytest.raises(PlanError, match="SINGLE model"):
        ExecutionPlan(mode="mesh", tenants=4, mesh_axes=("data",))


def test_engine_input_errors():
    cfg = _cfg()
    xs = _data(4, 32, seed=21)
    engine = DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=4))
    with pytest.raises(PlanError, match="tenants=4"):
        engine.fit(xs[:2])  # tenant count mismatch
    with pytest.raises(PlanError, match="feature dim"):
        engine.fit(xs[:, :3, :])
    with pytest.raises(PlanError, match="stack the per-tenant data"):
        engine.fit(xs[0])  # 2-D input under a K=4 plan
    with pytest.raises(PlanError, match="expected"):
        engine.fit(xs[0, 0])  # 1-D input
    fl = engine.fit(xs)
    single = DAEFEngine(cfg)
    with pytest.raises(PlanError, match="declares tenants=1"):
        single.scores(fleet.get_model(fl, 0), xs)  # 3-D batch, K=1 plan
    with pytest.raises(PlanError, match="fleet has 4 tenants"):
        single.scores(fl, xs)  # fleet state under a single-model plan
    with pytest.raises(PlanError, match="got a single DAEFModel"):
        engine.scores(fleet.get_model(fl, 0), xs)  # model state, K=4 plan
    one = DAEFEngine(cfg, ExecutionPlan(tenants=1))
    m1 = one.fit(xs[0])
    f1 = one.fit(xs[:1])
    with pytest.raises(PlanError, match="cannot mix"):
        one.merge(m1, f1)  # DAEFModel x 1-tenant DAEFFleet
    with pytest.raises(PlanError, match="cannot mix"):
        one.merge(f1, m1)
    if len(jax.devices()) < 64:
        with pytest.raises(PlanError, match="exceeds"):
            DAEFEngine(cfg, ExecutionPlan(mode="mesh", tenants=64,
                                          mesh_devices=64))


def test_reduce_and_session_tree_errors():
    cfg = _cfg()
    k = 4
    xs = _data(k, 32, seed=23)
    # non-power-of-two tree merge is a clear error...
    engine = DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=6, merge="tree"))
    fl6 = engine.fit(_data(6, 32, seed=24), seeds=jnp.zeros(6, jnp.int32))
    with pytest.raises(PlanError, match="power-of-two"):
        engine.reduce(fl6, 3)
    # ...and sequential handles the same group size fine
    seq = DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=6,
                                        merge="sequential"))
    red = seq.reduce(fl6, 3)
    assert red.size == 2
    # group must divide the fleet
    eng4 = DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=k, merge="tree"))
    fl = eng4.fit(xs, seeds=jnp.zeros(k, jnp.int32))
    with pytest.raises(PlanError, match="divide"):
        eng4.reduce(fl, 3)
    # session tree round: non-power-of-two node count
    x = _data(1, 48, seed=25)[0]
    sess = DAEFEngine(cfg, ExecutionPlan(merge="tree")).session()
    with pytest.raises(PlanError, match="power-of-two"):
        sess.round([x[:, :16], x[:, 16:32], x[:, 32:]])
    with pytest.raises(PlanError, match="equal sample counts"):
        sess.round([x[:, :8], x[:, 8:]])
    with pytest.raises(PlanError, match="at least one"):
        sess.round([])


# ---------------------------------------------------------------------------
# save / load
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["gram", "svd"])
def test_save_load_roundtrip(method, tmp_path):
    cfg = _cfg(method)
    k = 4
    xs = _data(k, 40, seed=27)
    engine = DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=k))
    fl = engine.fit(xs, seeds=jnp.arange(k))
    path = engine.save(fl, str(tmp_path / "fleet"))
    restored = engine.load(path)
    _assert_trees_close(fl, restored, what="fleet save/load", atol=0)

    single = DAEFEngine(cfg)
    model = single.fit(xs[0])
    path = single.save(model, str(tmp_path / "model"))
    _assert_trees_close(model, single.load(path), what="model save/load",
                        atol=0)

    # structurally incompatible engine -> actionable error
    other = DAEFEngine(
        daef.DAEFConfig(layer_sizes=(M0, 3, M0), method=method)
    )
    with pytest.raises(PlanError, match="does not match"):
        other.load(path)


def test_mesh_engine_load_replaces_on_mesh(tmp_path):
    cfg = _cfg()
    k = 4
    xs = _data(k, 40, seed=29)
    engine = DAEFEngine(cfg, ExecutionPlan(mode="mesh", tenants=k))
    fl = engine.fit(xs)
    path = engine.save(fl, str(tmp_path / "fleet"))
    restored = engine.load(path)
    _assert_trees_close(fl, restored, what="mesh save/load", atol=0)
    from jax.sharding import NamedSharding

    sh = restored.seeds.sharding
    assert isinstance(sh, NamedSharding)
    assert fleet_sharded.TENANT_AXIS in sh.mesh.shape


# ---------------------------------------------------------------------------
# deprecation shims: delegate to the engine, warn once, zero behavior change
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_deprecated_entry_points_delegate_and_warn_once():
    cfg = _cfg()
    k = 4
    xs = _data(k, 40, seed=31)
    seeds = jnp.arange(k)
    engine = DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=k))
    want = engine.fit(xs, seeds=seeds)

    deprecation._WARNED.discard("fleet.fleet_fit")
    with pytest.warns(DeprecationWarning, match="fleet.fleet_fit"):
        got = fleet.fleet_fit(cfg, xs, seeds=seeds)  # repro-lint: disable=RPR001
    _assert_trees_close(got, want, what="fleet_fit shim", atol=0)
    import warnings as _w

    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter("always")
        fleet.fleet_fit(cfg, xs, seeds=seeds)  # repro-lint: disable=RPR001
    assert not [w for w in rec if issubclass(w.category, DeprecationWarning)]

    mesh = fleet_sharded.tenant_mesh(len(jax.devices()) if k % len(jax.devices()) == 0 else 1)
    deprecation._WARNED.discard("fleet_sharded.sharded_fleet_fit")
    with pytest.warns(DeprecationWarning, match="sharded_fleet_fit"):
        got = fleet_sharded.sharded_fleet_fit(  # repro-lint: disable=RPR001
            cfg, np.asarray(xs), mesh, seeds=seeds)
    _assert_trees_close(got, want, what="sharded_fleet_fit shim")

    x = _data(1, 48, seed=33)[0]
    parts = [x[:, :24], x[:, 24:]]
    deprecation._WARNED.discard("federated.federated_fit")
    with pytest.warns(DeprecationWarning, match="federated_fit"):
        got = federated.federated_fit(cfg, parts)  # repro-lint: disable=RPR001
    want_fed = federated._federated_fit(cfg, parts)
    _assert_trees_close(got, want_fed, what="federated_fit shim", atol=0)

    deprecation._WARNED.discard("sharded.fit_on_mesh")
    mesh1 = DAEFEngine(cfg, ExecutionPlan(mode="mesh", mesh_axes=("data",))).mesh
    with pytest.warns(DeprecationWarning, match="fit_on_mesh"):
        got = sharded.fit_on_mesh(cfg, x, mesh1, data_axes=("data",))  # repro-lint: disable=RPR001
    want_mesh = sharded._fit_on_mesh(cfg, x, mesh1, data_axes=("data",))
    _assert_trees_close(got, want_mesh, what="fit_on_mesh shim", atol=0)
