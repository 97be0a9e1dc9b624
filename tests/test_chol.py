"""The lane-batched Cholesky solve (``core/chol.py``) and its route.

The kernel (interpreted here) is checked against a float64 solve: the
solutions within 1e-5 of the largest entry, for Grams of ``3 n`` samples
plus the identity (a decoder's ``G + lam I``).  The route tests read the
traced or compiled program: which solver a stack took shows as a
``pallas_call`` under the ``chol_solve`` scope or as XLA's ``cholesky``
(LAPACK ``potrf`` here).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import chol, daef, rolann
from repro.engine import DAEFEngine, ExecutionPlan
from repro.kernels.chol_solve import chol_solve

TOL = 1e-5
CARDIO = (21, 4, 8, 12, 16, 21)
kernel = jax.jit(lambda a, b: chol_solve(a, b, interpret=True))


def _systems(seed: int, batch: int, n: int, r: int):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(batch, n, 3 * n))
    a = x @ x.transpose(0, 2, 1) / (3 * n) + np.eye(n)
    return a.astype(np.float32), rng.normal(size=(batch, n, r)).astype(np.float32)


def _check_solution(a, b, x):
    ref = np.linalg.solve(a.astype(np.float64), b.astype(np.float64))
    assert np.abs(np.asarray(x, np.float64) - ref).max() <= TOL * np.abs(ref).max()


@pytest.mark.parametrize("r", [1, 21])
@pytest.mark.parametrize("n", [9, 13, 17, 33, 63])
@pytest.mark.parametrize("batch", [5, 130])
def test_kernel_matches_float64_solve(batch, n, r):
    a, b = _systems(n * 1000 + batch + r, batch, n, r)
    x = kernel(jnp.asarray(a), jnp.asarray(b))
    assert x.shape == (batch, n, r)
    _check_solution(a, b, x)


def test_kernel_spans_several_grid_blocks():
    """More than one block of 1,024 systems: the padded tail is solved as
    identity systems and cut off."""
    a, b = _systems(7, 1100, 9, 2)
    _check_solution(a, b, kernel(jnp.asarray(a), jnp.asarray(b)))


def test_kernel_reads_symmetric_part():
    """Like ``jnp.linalg.cholesky``, the kernel solves with ``(a + a^T)/2``."""
    a, b = _systems(8, 130, 9, 1)
    skew = np.triu(np.ones((9, 9), np.float32), 1) * 1e-3
    x = kernel(jnp.asarray(a + skew - skew.T), jnp.asarray(b))
    _check_solution(a, b, x)


def test_non_spd_system_gives_non_finite_solution():
    a, b = _systems(9, 130, 9, 1)
    a[3] = -a[3]
    a[70, 4, 4] = np.nan
    x = np.asarray(kernel(jnp.asarray(a), jnp.asarray(b)))
    bad = ~np.isfinite(x).all(axis=(1, 2))
    assert bad[3] and bad[70] and bad.sum() == 2
    _check_solution(np.delete(a, [3, 70], 0), np.delete(b, [3, 70], 0),
                    np.delete(x, [3, 70], 0))


def _route(fn, *args) -> tuple[bool, bool]:
    """(the kernel, XLA's cholesky) in the traced program of ``fn``."""
    text = str(jax.make_jaxpr(fn)(*args))
    return "pallas_call" in text, "cholesky" in text


@pytest.mark.parametrize("batch, n, dtype, want_kernel", [
    (chol.B0, 17, jnp.float32, True),
    (chol.B0 - 1, 17, jnp.float32, False),
    (chol.B0, chol.N_MAX, jnp.float32, True),
    (chol.B0, chol.N_MAX + 1, jnp.float32, False),
    (chol.B0, 17, jnp.bfloat16, False),
    (chol.B0, 17, jnp.float64, False),
])
def test_vmapped_solve_routes_by_stack_shape_and_dtype(batch, n, dtype, want_kernel):
    with jax.enable_x64(dtype == jnp.float64):
        a = jax.ShapeDtypeStruct((batch, n, n), dtype)
        b = jax.ShapeDtypeStruct((batch, n), dtype)
        assert _route(jax.vmap(chol.chol_solve), a, b) == (want_kernel, not want_kernel)


def test_one_system_keeps_xla_cholesky():
    a, b = _systems(1, 1, 9, 21)
    assert _route(chol.chol_solve, a[0], b[0]) == (False, True)
    np.testing.assert_allclose(chol.chol_solve(a[0], b[0]),
                               np.linalg.solve(a[0], b[0]), rtol=1e-4, atol=1e-5)


def test_nested_vmaps_reach_the_kernel_as_one_stack():
    """Outputs inside tenants: 16 x 8 systems, each axis below ``B0`` alone,
    are one stack of ``B0``; the vector and matrix right-hand sides agree
    with per-system solves."""
    k, o, n = 16, chol.B0 // 16, 9
    a, b = _systems(2, k * o, n, 3)
    a, b = a.reshape(k, o, n, n), b.reshape(k, o, n, 3)
    nested = jax.vmap(jax.vmap(chol.chol_solve))
    assert _route(nested, a, b[..., 0]) == (True, False)
    assert str(jax.make_jaxpr(nested)(a, b)).count("pallas_call") == 1
    for rhs in (b[..., 0], b):
        x = np.asarray(jax.jit(nested)(a, rhs))
        ref = np.linalg.solve(a.astype(np.float64),
                              rhs if rhs.ndim == 4 else rhs[..., None])
        assert np.abs(x - ref.reshape(x.shape)).max() <= TOL * np.abs(ref).max()


def test_auto_solver_rescues_non_spd_system_on_kernel_route():
    """A stack of ``B0`` models, one of whose ``G + lam I`` is indefinite:
    the kernel's solution of that one is non-finite, and ``"auto"`` hands
    back the eigh route's weights for it and the kernel's for the rest."""
    k, m = chol.B0, 6
    rng = np.random.default_rng(4)
    x = rng.normal(size=(k, m, 40)).astype(np.float32)
    g = np.einsum("kin,kjn->kij", x, x)
    g[5] = -g[5]
    stats = rolann.RolannStats(g=jnp.asarray(g),
                               m=jnp.asarray(rng.normal(size=(k, 3, m)), jnp.float32))

    def solve(route):
        return jax.jit(jax.vmap(lambda s: rolann.solve(s, 0.5, gram_solver=route)))

    chol_w, _ = solve("chol")(stats)
    auto_w, auto_b = solve("auto")(stats)
    eigh_w, eigh_b = solve("eigh")(stats)
    assert not np.isfinite(chol_w[5]).all()
    assert np.isfinite(auto_w).all()
    np.testing.assert_allclose(auto_w[5], eigh_w[5], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(auto_b[5], eigh_b[5], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.delete(auto_w, 5, 0), np.delete(chol_w, 5, 0))
    assert _route(jax.vmap(lambda s: rolann.solve(s, 0.5)), stats) == (True, False)


def _compiled_route(text: str) -> tuple[bool, bool]:
    """(the ``chol_solve`` scope, a LAPACK ``potrf`` custom call) in a
    compiled program."""
    return (re.search(r'op_name="[^"]*\bchol_solve/', text) is not None,
            re.search(r'custom_call_target="[^"]*potrf', text) is not None)


def test_fleet_fit_on_kernel_route_matches_per_model_fits():
    """64 tenants: the ELM-AE layer's per-output stack (64 x 4 systems)
    takes the kernel, the shared last layer (64) and the encoder keep XLA's
    routes; the models and scores match per-model fits."""
    k, m0, n = 64, 9, 80
    sizes = (m0, 4, 6, m0)
    rng = np.random.default_rng(0)
    x = np.einsum("kmr,krn->kmn", rng.normal(size=(k, m0, 3)),
                  np.tanh(rng.normal(size=(k, 3, n))))
    x = x + 0.1 * rng.normal(size=x.shape)
    x = ((x - x.mean(2, keepdims=True)) / x.std(2, keepdims=True)).astype(np.float32)
    cfg = daef.DAEFConfig(layer_sizes=sizes, lam_hidden=0.7, lam_last=0.9)
    engine = DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=k))
    text = engine.lower_fit(jax.ShapeDtypeStruct(x.shape, jnp.float32),
                            seeds=np.arange(k)).compile().as_text()
    assert _compiled_route(text) == (True, True)
    fleet_state = engine.fit(x, seeds=np.arange(k))
    scores = engine.scores(fleet_state, x)
    for i in (0, 1, k // 2, k - 1):
        one_cfg = daef.DAEFConfig(layer_sizes=sizes, lam_hidden=0.7,
                                  lam_last=0.9, seed=i)
        ref = daef.fit(one_cfg, x[i])
        got = engine.get_model(fleet_state, i)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref), strict=True):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(
            scores[i], daef.reconstruction_error(one_cfg, ref, x[i]),
            atol=1e-4, rtol=1e-4)


def test_one_model_fit_program_keeps_xla_cholesky():
    cfg = daef.DAEFConfig(layer_sizes=CARDIO, lam_hidden=0.9, lam_last=0.9,
                          stats_backend="einsum")
    text = daef.lower_fit(cfg, jax.ShapeDtypeStruct(
        (CARDIO[0], 64), jnp.float32)).compile().as_text()
    assert _compiled_route(text) == (False, True)
