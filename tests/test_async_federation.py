"""Continual (async) federation: ledger, staleness, refresh and parity.

The acceptance bar for ``ExecutionPlan(federation="async")``: with every
site reporting every round and ``max_staleness=0`` the async session must
reproduce the sequential broker merge of the same contributions (across
loop/vmap/mesh modes and both Gram-stats producers); stragglers must be excluded
exactly at the staleness bound and re-enter with their full accumulated
contribution; the masked on-mesh tree must agree with the host reduction
over the same subset.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import daef, federated, fleet, fleet_sharded
from repro.engine import DAEFEngine, ExecutionPlan, PlanError
from repro.testing.producer import stats_producer

M0, LATENT = 7, 3
LAYERS = (M0, LATENT, 5, M0)
MODES = ("loop", "vmap", "mesh")
# Execution-order parity bar (same as test_parity / test_engine rounds).
PARITY = dict(atol=5e-4, rtol=1e-3)


def _cfg(method: str = "gram") -> daef.DAEFConfig:
    return daef.DAEFConfig(
        layer_sizes=LAYERS, lam_hidden=0.7, lam_last=0.9, method=method,
    )


def _blocks(sites: int, rounds: int, n: int = 48, seed: int = 0):
    """Per-site per-round [M0, n] blocks from one generative process."""
    rng = np.random.default_rng(seed)
    mix = rng.normal(size=(M0, LATENT))

    def draw():
        z = np.tanh(rng.normal(size=(LATENT, n)))
        x = mix @ z + 0.1 * rng.normal(size=(M0, n))
        return jnp.asarray(
            (x - x.mean(axis=1, keepdims=True)) / x.std(axis=1, keepdims=True),
            jnp.float32,
        )

    return [[draw() for _ in range(rounds)] for _ in range(sites)]


def _reference(cfg, site_blocks):
    """The sequential broker merge of the same contributions: each site's
    per-round fits chained with merge_models, then reduced across sites."""
    site_models = []
    for blocks in site_blocks:
        m = daef.fit(cfg, blocks[0])
        for b in blocks[1:]:
            m = daef.merge_models(cfg, m, daef.fit(cfg, b))
        site_models.append(m)
    return functools.reduce(
        functools.partial(daef.merge_models, cfg), site_models
    )


def _assert_models_close(a, b, *, what: str):
    for wa, wb in zip(a.weights, b.weights, strict=True):
        np.testing.assert_allclose(wa, wb, err_msg=f"{what}: weights",
                                   **PARITY)
    for ba, bb in zip(a.biases, b.biases, strict=True):
        np.testing.assert_allclose(ba, bb, err_msg=f"{what}: biases",
                                   **PARITY)


# ---------------------------------------------------------------------------
# Sync-parity invariant: all sites, max_staleness=0 == sequential merge
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["einsum", "fused"])
@pytest.mark.parametrize("mode", MODES)
def test_async_sync_parity(mode, backend):
    cfg = _cfg()
    site_blocks = _blocks(sites=3, rounds=2)
    plan = ExecutionPlan(mode=mode, federation="async", merge="sequential")
    with stats_producer(backend):
        session = DAEFEngine(cfg, plan).session()
        for r in range(2):
            model = session.round([blocks[r] for blocks in site_blocks])
        ref = _reference(cfg, site_blocks)
    _assert_models_close(model, ref, what=f"async {mode}/{backend}")
    x = site_blocks[0][0]
    np.testing.assert_allclose(
        daef.predict(cfg, model, x), daef.predict(cfg, ref, x), **PARITY
    )


@pytest.mark.parametrize("merge", ["sequential", "pairwise", "tree"])
def test_async_merge_strategies_agree(merge):
    # 3 sites: the masked tree must pad the non-power-of-two round itself.
    cfg = _cfg()
    site_blocks = _blocks(sites=3, rounds=2, seed=1)
    plan = ExecutionPlan(federation="async", merge=merge)
    session = DAEFEngine(cfg, plan).session()
    for r in range(2):
        model = session.round([blocks[r] for blocks in site_blocks])
    _assert_models_close(model, _reference(cfg, site_blocks),
                         what=f"async merge={merge}")


def test_async_tree_requires_gram():
    cfg = _cfg(method="svd")
    plan = ExecutionPlan(federation="async", merge="tree")
    session = DAEFEngine(cfg, plan).session()
    parts = [b[0] for b in _blocks(sites=2, rounds=1)]
    with pytest.raises(PlanError, match="gram"):
        session.round(parts)


# ---------------------------------------------------------------------------
# Round shapes: empty, single-site, bad parts
# ---------------------------------------------------------------------------

def test_sync_empty_round_raises():
    session = DAEFEngine(_cfg()).session()
    with pytest.raises(PlanError, match="async"):
        session.round([])


def test_async_empty_round_is_refresh_only():
    cfg = _cfg()
    session = DAEFEngine(
        cfg, ExecutionPlan(federation="async")
    ).session()
    assert session.round({}) is None          # nothing ever reported
    assert session.rounds_run == 1
    x = _blocks(1, 1)[0][0]
    model = session.round({"a": x})
    before = [np.asarray(w) for w in model.weights]
    model2 = session.round({})                # tick: "a" now stale (bound 0)
    # No fresh site -> the previous live model is kept, not discarded.
    for w0, w1 in zip(before, model2.weights, strict=True):
        np.testing.assert_array_equal(w0, np.asarray(w1))
    assert session.staleness("a") == 1 and not session.is_fresh("a")


def test_async_single_site_round_matches_fit():
    cfg = _cfg()
    x = _blocks(1, 1, n=64)[0][0]
    session = DAEFEngine(
        cfg, ExecutionPlan(federation="async")
    ).session()
    model = session.round({"solo": x})
    _assert_models_close(model, daef.fit(cfg, x), what="single site")
    assert session.sites == {"solo": 0}


def test_round_rejects_non_iterable_parts():
    session = DAEFEngine(_cfg()).session()
    with pytest.raises(PlanError, match="sequence|mapping"):
        session.round(42)
    with pytest.raises(PlanError, match="features"):
        session.round({"a": jnp.zeros((M0 + 1, 8))})


# ---------------------------------------------------------------------------
# Staleness bound, dropout, delta-replay rejoin, mid-session join
# ---------------------------------------------------------------------------

def test_staleness_bound_excludes_and_replays():
    cfg = _cfg()
    site_blocks = _blocks(sites=2, rounds=3, seed=2)
    a, b = site_blocks
    plan = ExecutionPlan(federation="async", merge="sequential",
                         max_staleness=0)
    session = DAEFEngine(cfg, plan).session()

    session.round({"a": a[0], "b": b[0]})
    model = session.round({"a": a[1]})         # b misses the round
    assert session.staleness("b") == 1 and not session.is_fresh("b")
    # Live model excludes b entirely: equals an a-only accumulation.
    _assert_models_close(model, _reference(cfg, [a[:2]]),
                         what="stale site excluded")

    # b returns: its FULL accumulated contribution re-enters in one delta.
    model = session.round({"a": a[2], "b": jnp.concatenate(b[1:], axis=1)})
    assert session.is_fresh("b")
    ref = _reference(cfg, [a, [b[0], jnp.concatenate(b[1:], axis=1)]])
    _assert_models_close(model, ref, what="delta replay rejoin")


def test_max_staleness_keeps_lagging_site():
    cfg = _cfg()
    (a, b) = _blocks(sites=2, rounds=2, seed=3)
    plan = ExecutionPlan(federation="async", merge="sequential",
                         max_staleness=1)
    session = DAEFEngine(cfg, plan).session()
    session.round({"a": a[0], "b": b[0]})
    model = session.round({"a": a[1]})         # b lags one round: still fresh
    assert session.staleness("b") == 1 and session.is_fresh("b")
    _assert_models_close(model, _reference(cfg, [a, b[:1]]),
                         what="lagging site within bound")


def test_site_joins_mid_session():
    cfg = _cfg()
    (a, b, c) = _blocks(sites=3, rounds=2, seed=4)
    plan = ExecutionPlan(federation="async", merge="pairwise")
    session = DAEFEngine(cfg, plan).session()
    session.round({"a": a[0], "b": b[0]})
    model = session.round({"a": a[1], "b": b[1], "c": c[0]})  # c joins late
    assert set(session.sites) == {"a", "b", "c"}
    _assert_models_close(model, _reference(cfg, [a, b, c[:1]]),
                         what="mid-session join")
    session.reset()
    assert session.model is None and session.sites == {}


# ---------------------------------------------------------------------------
# Masked tree reduction: subset parity with the host reduce
# ---------------------------------------------------------------------------

def test_merge_state_tree_masked_subset_parity():
    cfg = _cfg()
    parts = [b[0] for b in _blocks(sites=4, rounds=1, seed=5)]
    models = [daef.fit(cfg, p) for p in parts]
    states = [
        (m.encoder_factors, m.layer_knowledge, np.asarray(m.train_errors))
        for m in models
    ]
    enc_b, knw_b = jax.tree.map(
        lambda *leaves: jnp.stack(leaves), *[(s[0], s[1]) for s in states]
    )
    mask = np.array([1.0, 0.0, 1.0, 1.0], np.float32)
    enc_t, knw_t = fleet_sharded.merge_state_tree(cfg, enc_b, knw_b, mask)
    subset = [states[i] for i in (0, 2, 3)]
    enc_h, knw_h, _ = federated.merge_exchange_states(cfg, subset)
    for kt, kh in zip(knw_t, knw_h, strict=True):
        np.testing.assert_allclose(kt.g, kh.g, **PARITY)
        np.testing.assert_allclose(kt.m, kh.m, **PARITY)
    # Same total Gram either way -> same factors up to float error.
    gt = enc_t.u @ jnp.diag(enc_t.s**2) @ enc_t.u.T
    gh = enc_h.u @ jnp.diag(enc_h.s**2) @ enc_h.u.T
    np.testing.assert_allclose(gt, gh, atol=1e-3, rtol=1e-3)


def test_merge_state_tree_rejects_all_zero_mask():
    cfg = _cfg()
    parts = [b[0] for b in _blocks(sites=2, rounds=1, seed=6)]
    models = [daef.fit(cfg, p) for p in parts]
    enc_b, knw_b = jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *[(m.encoder_factors, m.layer_knowledge) for m in models],
    )
    with pytest.raises(ValueError, match="mask"):
        fleet_sharded.merge_state_tree(
            cfg, enc_b, knw_b, np.zeros(2, np.float32)
        )


# ---------------------------------------------------------------------------
# fleet_merge_tree constraint + merge after reduce
# ---------------------------------------------------------------------------

def test_fleet_merge_tree_pow2_error_names_the_alternatives():
    cfg = _cfg()
    xs = jnp.stack([b[0] for b in _blocks(sites=6, rounds=1, seed=7)])
    fl = fleet._fit_fleet(cfg, xs, seeds=None, lam_hidden=None,
                          lam_last=None)
    with pytest.raises(ValueError, match="power of two") as e:
        fleet_sharded.fleet_merge_tree(cfg, fl, 3)
    assert "merge_state_tree" in str(e.value)
    assert "sequential" in str(e.value)


def test_merge_after_reduce_commutes():
    # reduce-then-merge == merge-then-reduce (the statistics just add).
    cfg = _cfg()
    xa = jnp.stack([b[0] for b in _blocks(sites=4, rounds=1, seed=8)])
    xb = jnp.stack([b[0] for b in _blocks(sites=4, rounds=1, seed=9)])
    engine = DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=4,
                                           merge="pairwise"))
    fa, fb = engine.fit(xa), engine.fit(xb)
    reduced_then_merged = engine.for_tenants(2).merge(
        engine.reduce(fa, 2), engine.reduce(fb, 2)
    )
    merged_then_reduced = engine.reduce(engine.merge(fa, fb), 2)
    for wa, wb in zip(
        reduced_then_merged.model.weights, merged_then_reduced.model.weights
    , strict=True):
        np.testing.assert_allclose(wa, wb, **PARITY)


# ---------------------------------------------------------------------------
# Plan validation
# ---------------------------------------------------------------------------

def test_plan_validation():
    with pytest.raises(PlanError, match="federation"):
        ExecutionPlan(federation="eventually")
    with pytest.raises(PlanError, match="max_staleness"):
        ExecutionPlan(federation="async", max_staleness=-1)
    with pytest.raises(PlanError, match="async"):
        ExecutionPlan(max_staleness=2)       # sync has no staleness bound
    plan = ExecutionPlan(federation="async", max_staleness=3)
    assert plan.async_federation and not ExecutionPlan().async_federation
