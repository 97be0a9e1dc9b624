"""Differential parity harness: loop == vmap fleet == mesh-sharded fleet.

DAEF's fleet story only holds at scale if every execution path is
numerically interchangeable: the eager per-model loop (`daef.fit` /
`daef.merge_models`), the vmap-batched fleet engine (`core/fleet.py`) and
the mesh-sharded fleet (`core/fleet_sharded.py`) must produce the same
models, reconstructions, scores and federated merges, for BOTH knowledge
representations ("gram" and "svd"), within explicit per-dtype tolerances.

The property sweeps run on whatever devices exist: single-device in the
tier-1 suite (the sharded path degenerates to a 1-shard mesh, still
exercising placement + shard_map), truly split in CI's multi-device job
(XLA_FLAGS=--xla_force_host_platform_device_count=8) and in
tests/test_fleet_sharded.py's subprocess harness.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import daef, fleet, fleet_sharded, rolann
from repro.testing.proptest import given, settings, st

# Explicit parity tolerances per dtype (acceptance bar: <= 1e-4 for f32).
# float64 runs only when jax_enable_x64 is on (it is not in tier-1).
TOLS = {
    "float32": dict(atol=1e-4, rtol=1e-4),
    "float64": dict(atol=1e-9, rtol=1e-9),
}

M0, LATENT = 7, 3
LAYERS = (M0, LATENT, 5, M0)


def _cfg(method: str) -> daef.DAEFConfig:
    return daef.DAEFConfig(
        layer_sizes=LAYERS, lam_hidden=0.7, lam_last=0.9, method=method
    )


def _data(k: int, n: int, seed: int, dtype=jnp.float32):
    """Standardized low-rank-plus-noise tenant data [k, M0, n]."""
    rng = np.random.default_rng(seed)
    z = rng.normal(size=(k, LATENT, n))
    mix = rng.normal(size=(k, M0, LATENT))
    x = np.einsum("kmr,krn->kmn", mix, np.tanh(z))
    x = x + 0.1 * rng.normal(size=(k, M0, n))
    x = (x - x.mean(axis=2, keepdims=True)) / x.std(axis=2, keepdims=True)
    return jnp.asarray(x, dtype)


def _mesh(k: int):
    """The largest tenant mesh the current process can shard k tenants over."""
    d = len(jax.devices())
    while d > 1 and k % d:
        d //= 2
    return fleet_sharded.tenant_mesh(d)


def _invariant(model: daef.DAEFModel) -> daef.DAEFModel:
    """``model`` with its factor-form ROLANN knowledge (``method="svd"``
    only; Gram-form knowledge passes unchanged) compared as ``U S² Uᵀ``,
    ``S`` and ``M`` instead of ``U`` itself.

    An ELM-AE layer's augmented input can carry singular values at the
    float32 noise floor (~1e-6 of the largest).  Their singular vectors
    are not determined one by one, and the per-model and the batched
    (vmapped) SVD pick different bases of the same span.  ``U S² Uᵀ``
    weights each column by its ``s²`` and so hides them.  The weight solve
    does not: it weights them by ``1/(s² + λ)``, but depends only on their
    span, which both SVDs agree on.  The solved weights, compared as they
    are, stay the binding check of those directions."""
    know = tuple(
        (rolann.factors_to_stats(k), k.s)
        if isinstance(k, rolann.RolannFactors) else k
        for k in model.layer_knowledge
    )
    return model._replace(layer_knowledge=know)


def _assert_models_close(a: daef.DAEFModel, b: daef.DAEFModel, *, what: str):
    a, b = _invariant(a), _invariant(b)
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        tol = TOLS[str(np.asarray(la).dtype)]
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), err_msg=what, **tol
        )


# ---------------------------------------------------------------------------
# fit / predict / scores
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(method=st.sampled_from(["gram", "svd"]), data_seed=st.integers(0, 7))
def test_fit_predict_scores_parity(method, data_seed):
    k, n = 4, 96
    cfg = _cfg(method)
    xs = _data(k, n, data_seed)
    seeds = jnp.arange(k)
    tol = TOLS[str(np.asarray(xs).dtype)]

    loop = [daef.fit(dataclasses.replace(cfg, seed=i), xs[i]) for i in range(k)]
    fv = fleet.fleet_fit(cfg, xs, seeds=seeds)
    mesh = _mesh(k)
    fs = fleet_sharded.sharded_fleet_fit(cfg, xs, mesh, seeds=seeds)

    recon_v = fleet.fleet_predict(cfg, fv, xs)
    recon_s = fleet_sharded.sharded_fleet_predict(cfg, fs, np.asarray(xs), mesh=mesh)
    scores_v = fleet.fleet_scores(cfg, fv, xs)
    scores_s = fleet_sharded.sharded_fleet_scores(cfg, fs, np.asarray(xs), mesh=mesh)

    for i in range(k):
        _assert_models_close(
            fleet.get_model(fv, i), loop[i], what=f"vmap vs loop, tenant {i}"
        )
        _assert_models_close(
            fleet.get_model(fs, i), loop[i], what=f"sharded vs loop, tenant {i}"
        )
        recon_l = daef.predict(cfg, loop[i], xs[i])
        scores_l = daef.reconstruction_error(cfg, loop[i], xs[i])
        np.testing.assert_allclose(np.asarray(recon_v[i]), np.asarray(recon_l), **tol)
        np.testing.assert_allclose(np.asarray(recon_s[i]), np.asarray(recon_l), **tol)
        np.testing.assert_allclose(np.asarray(scores_v[i]), np.asarray(scores_l), **tol)
        np.testing.assert_allclose(np.asarray(scores_s[i]), np.asarray(scores_l), **tol)


# ---------------------------------------------------------------------------
# federated merge
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(method=st.sampled_from(["gram", "svd"]), data_seed=st.integers(0, 7))
def test_merge_parity(method, data_seed):
    k = 4
    cfg = _cfg(method)
    xa, xb = _data(k, 64, data_seed), _data(k, 64, data_seed + 100)
    seeds = jnp.arange(k)

    fa, fb = fleet.fleet_fit(cfg, xa, seeds=seeds), fleet.fleet_fit(cfg, xb, seeds=seeds)
    merged_v = fleet.fleet_merge(cfg, fa, fb)

    mesh = _mesh(k)
    sa = fleet_sharded.shard_fleet(fa, mesh)
    sb = fleet_sharded.shard_fleet(fb, mesh)
    merged_s = fleet.fleet_merge(cfg, sa, sb)

    for i in range(k):
        ref = daef.merge_models(
            dataclasses.replace(cfg, seed=i),
            fleet.get_model(fa, i),
            fleet.get_model(fb, i),
        )
        _assert_models_close(
            fleet.get_model(merged_v, i), ref, what=f"vmap merge, tenant {i}"
        )
        _assert_models_close(
            fleet.get_model(merged_s, i), ref, what=f"sharded merge, tenant {i}"
        )


@pytest.mark.parametrize("method", ["gram", "svd"])
@pytest.mark.parametrize("group", [2, 4, 8])
def test_merge_tree_matches_sequential_reduction(method, group):
    """fleet_merge_tree == left-to-right functools.reduce of daef.merge_models
    per group, incl. group_size == K (the single-logical-model case)."""
    k = 8
    cfg = _cfg(method)
    xs = _data(k, 64, seed=11)
    seeds = jnp.repeat(jnp.arange(k // group), group)
    fl = fleet.fleet_fit(cfg, xs, seeds=seeds)

    tree = fleet_sharded.fleet_merge_tree(cfg, fl, group, mesh=_mesh(k))
    assert tree.size == k // group

    for i in range(k // group):
        cfg_i = dataclasses.replace(cfg, seed=i)
        ref = functools.reduce(
            lambda a, b: daef.merge_models(cfg_i, a, b),
            [fleet.get_model(fl, i * group + j) for j in range(group)],
        )
        got = fleet.get_model(tree, i)
        # Deeper reductions accumulate float error across log2(group) merge
        # rounds; scale the f32 bar accordingly (2e-4 at g=2 .. 8e-4 at g=8).
        for la, lb in zip(jax.tree.leaves(got), jax.tree.leaves(ref), strict=True):
            np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb),
                atol=1e-4 * group, rtol=1e-3,
                err_msg=f"merge_tree group {i} (size {group})",
            )


def test_merge_tree_validates_groups():
    k = 4
    cfg = _cfg("gram")
    fl = fleet.fleet_fit(cfg, _data(k, 48, seed=0), seeds=jnp.arange(k))
    with pytest.raises(ValueError, match="share a seed"):
        fleet_sharded.fleet_merge_tree(cfg, fl, 2)
    with pytest.raises(ValueError, match="power of two"):
        fleet_sharded.fleet_merge_tree(cfg, fl, 3)
    with pytest.raises(ValueError, match="divide"):
        fleet_sharded.fleet_merge_tree(cfg, fl, 8)
    same = fleet.fleet_fit(cfg, _data(k, 48, seed=0), seeds=7)
    assert fleet_sharded.fleet_merge_tree(cfg, same, 1) is same
    lam = fleet.DAEFFleet(
        model=same.model, seeds=same.seeds,
        lam_hidden=jnp.linspace(0.1, 0.9, k), lam_last=same.lam_last,
    )
    with pytest.raises(ValueError, match="lam_hidden"):
        fleet_sharded.fleet_merge_tree(cfg, lam, 2)


def test_merge_tree_equals_pairwise_step():
    """group_size=2 is exactly the existing fleet_merge_pairwise semantics."""
    k = 6  # non-power-of-two fleet size, power-of-two group
    cfg = _cfg("gram")
    seeds = jnp.asarray([0, 0, 1, 1, 2, 2])
    fl = fleet.fleet_fit(cfg, _data(k, 48, seed=3), seeds=seeds)
    tree = fleet_sharded.fleet_merge_tree(cfg, fl, 2)
    pair = fleet.fleet_merge_pairwise(cfg, fl)
    assert tree.size == pair.size == 3
    for i in range(3):
        _assert_models_close(
            fleet.get_model(tree, i), fleet.get_model(pair, i),
            what=f"tree vs pairwise, site {i}",
        )
