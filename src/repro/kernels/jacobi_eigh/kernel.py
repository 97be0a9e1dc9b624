"""Pallas kernel: cyclic Jacobi on a stack of small symmetric matrices.

``jacobi_eigh`` lays a stack [batch, n, n] out as [n, n, rows, lanes]
(``n`` made even): entry ``(i, j)`` of every matrix is one ``[rows, lanes]``
slab, a whole vector register on a TPU, so every rotation is element-wise
float32 on the VPU with no cross-lane work.  The grid runs over blocks of at most 8 rows (1,024
matrices); each block's matrix and eigenvectors stay in VMEM for all its
sweeps, so the loop costs no HBM traffic and no launch per step.

One sweep is ``n - 1`` parallel steps in round-robin order.  In every step
position ``k`` pairs with ``k + n/2``; each pair's rotation annihilates
``a[k, k + n/2]`` (Golub & Van Loan, sym.schur2), and is skipped where that
entry already meets ``|a_pq| <= eps * sqrt(|a_pp * a_qq|)``.  Entries move
as ``x - (g x - sigma x_partner)`` with ``g = 1 - c = s^2 / (1 + c)``
(Rutishauser's form), so rounding falls on the small correction.  After the
step every position except 0 walks one place round the ring
``1 .. n/2-1, n-1 .. n/2`` (``_shift``), which brings every pair
together once per sweep.  Sweeps stop when every matrix of the block meets
the test, or after ``max_sweeps``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: A float32 register: 8 rows (sublanes) of 128 lanes.  A grid block holds
#: at most one register's worth of matrices, 1,024.
BLOCK_ROWS = 8
LANES = 128


def _shift(x, m: int):
    """The round-robin move along the leading axis (2m positions):
    position 0 stays, ``[m]`` comes next, ``[1, m-1)`` move up one, the
    bottom half moves down one and ``[m-1]`` closes the ring."""
    if m == 1:
        return x
    return jnp.concatenate([x[0:1], x[m:m + 1], x[1:m - 1], x[m + 1:2 * m],
                            x[m - 1:m]], axis=0)


def _dest(i, m: int):
    """Where ``_shift`` puts position ``i`` (a traced int32 scalar)."""
    if m == 1:
        return i
    return jnp.where(i == 0, 0, jnp.where(
        i < m - 1, i + 1, jnp.where(
            i == m - 1, 2 * m - 1, jnp.where(i == m, 1, i - 1))))


def _swap(x, m: int):
    """Each position's partner along the leading axis: halves swapped."""
    return jnp.concatenate([x[m:], x[:m]], axis=0)


def _diag(a, offset: int = 0):
    """``a[i, (i + offset) % n]`` for every ``i`` as [n, rows, lanes]: a
    masked sum over the column axis, in whole registers."""
    n = a.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    j = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    return jnp.sum(jnp.where(j == (i + offset) % n, a, 0), axis=1)


def _kernel(a_in, w_out, v_out, sweeps_out, a_s, b_s, g_s, sg_s, d_s, o_s,
            *, eps: float, max_sweeps: int):
    n = a_in.shape[0]
    m = n // 2
    slab = a_in.shape[2:]
    a_s[...] = a_in[...]
    eye = (jax.lax.broadcasted_iota(jnp.int32, v_out.shape, 0)
           == jax.lax.broadcasted_iota(jnp.int32, v_out.shape, 1))
    v_out[...] = eye.astype(v_out.dtype)

    def unconverged():
        """Whether any entry of the block fails the convergence test (> 0)."""
        d_s[...] = jnp.abs(_diag(a_s[...]))

        def row(i, worst):
            bad = jnp.abs(a_s[i]) > eps * jnp.sqrt(d_s[i][None] * d_s[...])
            j = jax.lax.broadcasted_iota(jnp.int32, bad.shape, 0)
            bad = (bad & (j != i)).astype(jnp.float32)
            return jnp.maximum(worst, jnp.max(bad, axis=0))

        return jnp.max(jax.lax.fori_loop(0, n, row, jnp.zeros(slab, jnp.float32)))

    def coefficients():
        """Each pair's rotation, spread over both its positions: ``g_s``
        (1 - c), ``sg_s`` (the partner's coefficient), ``d_s`` (the new
        diagonal) and ``o_s`` (the pair's new off-diagonal entry)."""
        a = a_s[...]
        diag = _diag(a)
        app, aqq, apq = diag[:m], diag[m:], _diag(a, m)[:m]
        rot = jnp.abs(apq) > eps * jnp.sqrt(jnp.abs(app * aqq))
        d = aqq - app
        t = 2 * apq * jnp.where(d < 0, -1.0, 1.0) / (
            jnp.abs(d) + jnp.hypot(d, 2 * apq))
        t = jnp.where(rot, t, 0.0)
        c = jax.lax.rsqrt(1 + t * t)
        s = t * c
        g = s * s / (1 + c)
        o = jnp.where(rot, 0.0, apq)
        g_s[...] = jnp.concatenate([g, g])
        sg_s[...] = jnp.concatenate([-s, s])
        d_s[...] = jnp.concatenate([app - t * apq, aqq + t * apq])
        o_s[...] = jnp.concatenate([o, o])

    def rotate_row(i, _):
        p = jnp.where(i < m, i + m, i - m)
        gj, sj = g_s[...], sg_s[...]
        row = a_s[i]
        b = row - (g_s[i][None] * row - sg_s[i][None] * a_s[p])
        b = b - (gj * b - sj * _swap(b, m))
        di = _dest(i, m)
        b_s[di] = _shift(b, m)
        b_s[di, di] = d_s[i]
        b_s[di, _dest(p, m)] = o_s[i]
        return 0

    def rotate_v(r, _):
        gj, sj = g_s[...], sg_s[...]
        row = v_out[r]
        v_out[r] = _shift(row - (gj * row - sj * _swap(row, m)), m)
        return 0

    def step(_, carry):
        coefficients()
        jax.lax.fori_loop(0, n, rotate_row, 0)
        jax.lax.fori_loop(0, n, rotate_v, 0)
        a_s[...] = b_s[...]
        return carry

    def sweep(state):
        k, _ = state
        jax.lax.fori_loop(0, n - 1, step, 0)
        return k + 1, unconverged()

    k, _ = jax.lax.while_loop(
        lambda s: (s[0] < max_sweeps) & (s[1] > 0), sweep,
        (jnp.int32(0), unconverged()))
    w_out[...] = _diag(a_s[...])
    sweeps_out[...] = jnp.full(sweeps_out.shape, k, jnp.int32)


def _pallas_jacobi(a, *, max_sweeps: int, interpret: bool):
    """Eigenvalues (unsorted) [n, rows, lanes], eigenvectors as columns
    [n, n, rows, lanes] and the sweeps of each block [rows, lanes] of a
    stack ``a`` [n, n, rows, lanes], ``n`` even; ``rows`` is at most
    :data:`BLOCK_ROWS` or a multiple of it."""
    n, _, rows, lanes = a.shape
    block = min(rows, BLOCK_ROWS)
    dt = a.dtype
    mat = pl.BlockSpec((n, n, block, lanes), lambda b: (0, 0, b, 0))
    vec = pl.BlockSpec((n, block, lanes), lambda b: (0, b, 0))
    kernel = functools.partial(_kernel, eps=float(jnp.finfo(dt).eps),
                               max_sweeps=max_sweeps)
    return pl.pallas_call(
        kernel,
        grid=(rows // block,),
        in_specs=[mat],
        out_specs=[vec, mat, pl.BlockSpec((block, lanes), lambda b: (b, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, rows, lanes), dt),
                   jax.ShapeDtypeStruct((n, n, rows, lanes), dt),
                   jax.ShapeDtypeStruct((rows, lanes), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((n, n, block, lanes), dt)] * 2
        + [pltpu.VMEM((n, block, lanes), dt)] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=100 * 2**20),
        interpret=interpret,
        name="jacobi_eigh",
    )(a)


def _sorted(w, v):
    """Eigenvalues ascending and their columns with them, by one-hot sums
    over each entry's rank (ties keep their order): element-wise, with no
    sort or gather.  ``w`` [n, *batch], ``v`` [n, n, *batch]."""
    n = w.shape[0]
    lower = np.arange(n)[:, None] > np.arange(n)[None, :]
    lower = lower.reshape(n, n, *(1,) * (w.ndim - 1))
    before = (w[None, :] < w[:, None]) | ((w[None, :] == w[:, None]) & lower)
    rank = jnp.sum(before, axis=1)                           # [n, *batch]
    onehot = rank[None, :] == jnp.arange(n).reshape(n, *(1,) * w.ndim)
    onehot = onehot.astype(w.dtype)                           # [k, i, *batch]
    return (jnp.sum(onehot * w[None, :], axis=1),
            jnp.sum(onehot[None, :, :] * v[:, None, :], axis=2))


def jacobi_eigh(a, *, max_sweeps: int, interpret: bool):
    """Eigenvalues ascending [batch, n], eigenvectors as columns
    [batch, n, n] (as ``jnp.linalg.eigh`` gives them) and the most sweeps
    any block took, for a stack ``a`` [batch, n, n] of symmetric matrices.

    The stack is laid out as [n', n', rows, lanes], with ``n'`` the even
    size (an odd ``n`` gets an inert index at position 0, which the
    round-robin never moves) and the batch padded with zero matrices to
    whole rows of :data:`LANES`."""
    batch, n, _ = a.shape
    m = (n + 1) // 2
    cut = 2 * m - n
    lanes = min(LANES, max(batch, 1))
    rows = -(-batch // lanes)
    if rows > BLOCK_ROWS:
        rows = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    x = jnp.moveaxis(a, 0, -1)                                # [n, n, batch]
    x = jnp.pad(x, ((cut, 0), (cut, 0), (0, rows * lanes - batch)))
    w, v, sweeps = _pallas_jacobi(x.reshape(2 * m, 2 * m, rows, lanes),
                                  max_sweeps=max_sweeps, interpret=interpret)
    w, v = _sorted(w[cut:], v[cut:, cut:])
    w = jnp.moveaxis(w.reshape(n, rows * lanes)[:, :batch], -1, 0)
    v = jnp.moveaxis(v.reshape(n, n, rows * lanes)[..., :batch], -1, 0)
    return w, v, jnp.max(sweeps)
