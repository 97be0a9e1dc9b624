"""The lane-batched Jacobi eigensolver (``core/eigh.py``) and its route.

The solver is checked against float64 ``numpy.linalg.eigh``: eigenvalues
within 1e-5 of the largest, ``VᵀV = I`` and ``A V = V Λ`` to 1e-5, in
ascending order.  Where eigenvalues repeat, the eigenvectors are not
determined one by one, so those cases are checked in invariant form only
(``V Λ Vᵀ`` and the projector onto each cluster).  The route tests read the
compiled program: which eigh a fit took shows as ``jacobi_eigh`` ops or as
an eigh custom call (LAPACK ``syevd`` here).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import daef, dsvd, eigh
from repro.data import synthetic
from repro.engine import DAEFEngine, ExecutionPlan

TOL = 1e-5
CARDIO = (21, 4, 8, 12, 16, 21)
jacobi = jax.jit(eigh.jacobi)


def _grams(seed: int, batch: int, n: int, samples: int) -> np.ndarray:
    x = np.random.default_rng(seed).normal(size=(batch, n, samples))
    return (x @ x.transpose(0, 2, 1) / samples).astype(np.float32)


def _check_decomposition(a: np.ndarray, w, v):
    a64 = a.astype(np.float64)
    w, v = np.asarray(w, np.float64), np.asarray(v, np.float64)
    ref = np.linalg.eigvalsh(a64)
    scale = np.abs(ref).max(axis=-1, keepdims=True)
    scale = np.where(scale > 0, scale, 1.0)
    n = a.shape[-1]
    assert np.all(np.diff(w, axis=-1) >= 0), "eigenvalues not ascending"
    assert np.abs(w - ref).max(initial=0) <= TOL * scale.max()
    np.testing.assert_allclose(np.swapaxes(v, -1, -2) @ v,
                               np.broadcast_to(np.eye(n), v.shape), atol=TOL)
    resid = np.abs(a64 @ v - v * w[..., None, :]) / scale[..., None]
    assert resid.max(initial=0) <= TOL


@pytest.mark.parametrize("n", [1, 2, 9, 21, 29, 62])
@pytest.mark.parametrize("batch", [1, 8, 130])
def test_jacobi_matches_float64_eigh(n, batch):
    a = _grams(n * 1000 + batch, batch, n, 3 * n + 2)
    w, v, sweeps = jacobi(jnp.asarray(a))
    _check_decomposition(a, w, v)
    assert 0 < int(sweeps) < eigh.MAX_SWEEPS or n == 1


@pytest.mark.parametrize("n", [9, 21, 29, 62])
def test_jacobi_rank_deficient_grams(n):
    """Fewer samples than features: a cluster of zero eigenvalues."""
    a = _grams(n, 16, n, max(1, n // 3))
    w, v, sweeps = jacobi(jnp.asarray(a))
    _check_decomposition(a, w, v)
    assert int(sweeps) < eigh.MAX_SWEEPS


def _clusters(kind: str, n: int) -> np.ndarray:
    """Repeated-eigenvalue stacks: the identity, a matrix with tied
    diagonal entries, and a rotated one with a triple and a double
    eigenvalue."""
    rng = np.random.default_rng(n)
    q, _ = np.linalg.qr(rng.normal(size=(n, n)))
    vals = np.repeat([1.0, 2.0, 5.0], [3, 2, n - 5]) if n >= 6 else np.ones(n)
    return {
        "identity": np.eye(n, dtype=np.float32),
        "tied_diagonal": np.diag(np.repeat([3.0, 7.0], [n // 2, n - n // 2])
                                 ).astype(np.float32),
        "rotated_clusters": ((q * vals) @ q.T).astype(np.float32),
    }[kind]


@pytest.mark.parametrize("kind", ["identity", "tied_diagonal", "rotated_clusters"])
@pytest.mark.parametrize("n", [2, 9, 21])
def test_jacobi_repeated_eigenvalues_invariants(kind, n):
    a = np.stack([_clusters(kind, n)] * 8)
    w, v, sweeps = jacobi(jnp.asarray(a))
    w, v = np.asarray(w, np.float64), np.asarray(v, np.float64)
    ref_w, ref_v = np.linalg.eigh(a.astype(np.float64))
    scale = np.abs(ref_w).max()
    assert np.abs(w - ref_w).max() <= TOL * scale
    np.testing.assert_allclose((v * w[:, None, :]) @ np.swapaxes(v, 1, 2), a,
                               atol=TOL * scale)
    # The projector onto each cluster of equal eigenvalues.
    for value in np.unique(np.round(ref_w[0], 4)):
        cols = np.abs(ref_w[0] - value) < 1e-3
        got = v[:, :, cols] @ np.swapaxes(v[:, :, cols], 1, 2)
        ref = ref_v[:, :, cols] @ np.swapaxes(ref_v[:, :, cols], 1, 2)
        np.testing.assert_allclose(got, ref, atol=TOL)
    assert int(sweeps) < eigh.MAX_SWEEPS


def test_jacobi_converges_below_cap_on_cardio_grams():
    """Seeded replicas of the cardio dataset (Table 1's shape), one Gram
    per tenant as the fleet fit forms it: the cap never binds."""
    x = np.stack([synthetic.make_dataset("cardio", seed=s).x_normal
                  for s in range(eigh.B0 * 4)])
    g = jnp.einsum("kmn,kjn->kmj", x, x)
    w, v, sweeps = jacobi(g)
    _check_decomposition(np.asarray(g), w, v)
    assert int(sweeps) <= eigh.MAX_SWEEPS // 2


def test_jacobi_direct_jit_and_vmap_agree():
    a = jnp.asarray(_grams(3, max(eigh.B0, 16), 21, 60))
    direct = eigh.jacobi(a)
    jitted = jacobi(a)
    w_vm, v_vm = jax.vmap(eigh.eigh)(a)
    for got in (jitted[:2], (w_vm, v_vm)):
        np.testing.assert_allclose(got[0], direct.eigenvalues, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(got[1], direct.eigenvectors, atol=1e-6)
    assert int(direct.sweeps) == int(jitted.sweeps)


def test_jacobi_handles_leading_batch_dims():
    a = _grams(4, 12, 9, 30).reshape(3, 4, 9, 9)
    w, v, _ = jacobi(jnp.asarray(a))
    assert w.shape == (3, 4, 9) and v.shape == (3, 4, 9, 9)
    _check_decomposition(a.reshape(12, 9, 9), np.reshape(w, (12, 9)),
                         np.reshape(v, (12, 9, 9)))


def _route(text: str) -> tuple[bool, bool]:
    """(any ``jacobi_eigh`` op, any eigh custom call) in a compiled program."""
    custom = re.search(r"custom_call_target=\"[^\"]*(syevd|Eigh)", text)
    return "jacobi_eigh" in text, custom is not None


@pytest.mark.parametrize("batch, n, want_jacobi", [
    (eigh.B0, 21, True),
    (eigh.B0 - 1, 21, False),
    (eigh.B0, eigh.N_MAX, True),
    (eigh.B0, eigh.N_MAX + 1, False),
])
def test_vmapped_eigh_routes_by_stack_shape(batch, n, want_jacobi):
    a = jax.ShapeDtypeStruct((batch, n, n), jnp.float32)
    text = jax.jit(jax.vmap(eigh.eigh)).lower(a).compile().as_text()
    assert _route(text) == (want_jacobi, not want_jacobi)


def test_gram_to_factors_stack_matches_per_matrix():
    """A stack given whole reaches the batched route and gives the factors
    of each Gram; one Gram keeps ``jnp.linalg.eigh``."""
    g = jnp.asarray(_grams(5, 2 * eigh.B0, 9, 40).reshape(2, eigh.B0, 9, 9))
    f = dsvd.gram_to_factors(g)
    assert f.u.shape == (2, eigh.B0, 9, 9) and f.s.shape == (2, eigh.B0, 9)
    one = dsvd.gram_to_factors(g[1, 3])
    np.testing.assert_allclose(f.s[1, 3], one.s, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(f.u[1, 3], one.u, atol=1e-4)
    text = jax.jit(dsvd.gram_to_factors).lower(g).compile().as_text()
    assert _route(text) == (True, False)


def _cardio_cfg() -> daef.DAEFConfig:
    return daef.DAEFConfig(layer_sizes=CARDIO, lam_hidden=0.9, lam_last=0.9,
                           stats_backend="einsum")


def test_fleet_fit_program_takes_jacobi_under_encoder():
    k, n = eigh.B0 * 2, 64
    engine = DAEFEngine(_cardio_cfg(), ExecutionPlan(mode="vmap", tenants=k))
    text = engine.lower_fit(jax.ShapeDtypeStruct((k, CARDIO[0], n), jnp.float32),
                            seeds=np.arange(k)).compile().as_text()
    assert _route(text) == (True, False)
    ops = [op for op in re.findall(r'op_name="([^"]*)"', text) if "jacobi_eigh" in op]
    assert ops and all("encoder" in op.split("jacobi_eigh")[0] for op in ops)


def test_fleet_fit_on_jacobi_route_matches_per_model_fits():
    """A vmap fleet of ``B0`` tenants (the Jacobi route) against per-model
    fits (``jnp.linalg.eigh``): the models, with the encoder factors in
    invariant form (the trailing eigenvectors of near-tied noise
    eigenvalues are not determined one by one in float32), and the
    scores."""
    k, m0, n = eigh.B0, 7, 96
    rng = np.random.default_rng(0)
    x = np.einsum("kmr,krn->kmn", rng.normal(size=(k, m0, 3)),
                  np.tanh(rng.normal(size=(k, 3, n))))
    x = x + 0.1 * rng.normal(size=x.shape)
    x = ((x - x.mean(2, keepdims=True)) / x.std(2, keepdims=True)).astype(np.float32)
    cfg = daef.DAEFConfig(layer_sizes=(m0, 3, 5, m0), lam_hidden=0.7, lam_last=0.9)
    engine = DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=k))
    fleet_state = engine.fit(x, seeds=np.arange(k))
    scores = engine.scores(fleet_state, x)
    for i in (0, 1, k // 2, k - 1):
        one_cfg = daef.DAEFConfig(layer_sizes=(m0, 3, 5, m0), lam_hidden=0.7,
                                  lam_last=0.9, seed=i)
        ref = daef.fit(one_cfg, x[i])
        got = engine.get_model(fleet_state, i)
        for a, b in zip(jax.tree.leaves(got._replace(encoder_factors=None)),
                        jax.tree.leaves(ref._replace(encoder_factors=None))):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
        ea, eb = got.encoder_factors, ref.encoder_factors
        np.testing.assert_allclose(ea.s, eb.s, atol=1e-4, rtol=1e-4)
        ga = (np.asarray(ea.u) * np.asarray(ea.s) ** 2) @ np.asarray(ea.u).T
        gb = (np.asarray(eb.u) * np.asarray(eb.s) ** 2) @ np.asarray(eb.u).T
        np.testing.assert_allclose(ga, gb, atol=1e-4 * np.abs(gb).max(), rtol=1e-4)
        np.testing.assert_allclose(
            scores[i], daef.reconstruction_error(one_cfg, ref, x[i]),
            atol=1e-4, rtol=1e-4)


def test_one_model_fit_program_keeps_its_eigh():
    text = daef.lower_fit(_cardio_cfg(), jax.ShapeDtypeStruct(
        (CARDIO[0], 64), jnp.float32)).compile().as_text()
    assert _route(text) == (False, True)
