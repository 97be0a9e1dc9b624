"""Pallas kernel: Cholesky solves of a stack of small SPD systems.

``chol_solve`` lays a stack of systems ``a x = b`` ([batch, n, n] and
[batch, n, r]) out as ``a`` [n, n, rows, lanes] and ``b`` [n, r, rows,
lanes]: entry ``(i, j)`` of every matrix is one ``[rows, lanes]`` slab, a
whole vector register on a TPU, as in ``kernels/jacobi_eigh``.  Every step
is element-wise float32 on the VPU with no cross-lane work and no matrix
product, so no bfloat16 MXU pass enters.  The grid runs over blocks of at
most 8 rows (1,024 systems); each block's factor stays in VMEM, so ``L``
never reaches HBM.

Column ``k`` of a right-looking Cholesky factorization takes the pivot
``a[k, k]``, scales row ``k`` of the trailing matrix (its column ``k``, by
symmetry) by ``1 / sqrt(pivot)`` into ``L[:, k]``, and subtracts
``L[i, k] L[:, k]`` from every later row ``i``.  The forward substitution
``L y = b`` rides along in the same loop (``y_k`` is final once ``L[k, k]``
is), and the back substitution ``L^T x = y`` runs column by column after
it.  Row ``k`` of the scratch ends up holding ``L[:, k]``.  A pivot that is
not positive and finite marks its system, whose solution is then NaN, as
XLA's Cholesky leaves a failed factorization non-finite.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: A float32 register: 8 rows (sublanes) of 128 lanes.  A grid block holds
#: at most one register's worth of systems, 1,024.
BLOCK_ROWS = 8
LANES = 128
#: The scoped VMEM the kernel may ask for (a v5e core has 128 MiB).
VMEM_LIMIT = 100 * 2**20


def vmem_bytes(n: int, r: int) -> int:
    """VMEM a block of :data:`BLOCK_ROWS` rows of ``n x n`` systems with
    ``r`` right-hand sides takes: ``a`` and ``b`` in and ``x`` out, each
    double-buffered, and the factor and pivot scratch."""
    return 4 * BLOCK_ROWS * LANES * (3 * n * n + 4 * n * r + n)


def _kernel(a_in, b_in, x_out, l_s, d_s):
    n = a_in.shape[0]
    slab = a_in.shape[2:]

    def copy(i, _):
        l_s[i] = a_in[i]
        return 0

    jax.lax.fori_loop(0, n, copy, 0)
    x_out[...] = b_in[...]
    col_index = jax.lax.broadcasted_iota(jnp.int32, (n, *slab), 0)

    def factor(k, bad):
        pivot = l_s[k, k]
        ok = (pivot > 0) & (pivot < jnp.inf)
        inv = 1.0 / jnp.sqrt(pivot)
        col = jnp.where(col_index >= k, l_s[k] * inv, 0.0)
        y = x_out[k] * inv
        l_s[k] = col
        x_out[k] = y
        d_s[k] = inv

        def update(i, _):
            lik = l_s[k, i]
            l_s[i] = l_s[i] - lik * col
            x_out[i] = x_out[i] - lik * y
            return 0

        jax.lax.fori_loop(k + 1, n, update, 0)
        return jnp.maximum(bad, jnp.where(ok, 0.0, 1.0))

    bad = jax.lax.fori_loop(0, n, factor, jnp.zeros(slab, jnp.float32))

    def back(t, _):
        k = n - 1 - t
        xk = x_out[k] * d_s[k]
        x_out[k] = jnp.where(bad > 0, jnp.nan, xk)

        def update(i, _):
            x_out[i] = x_out[i] - l_s[i, k] * xk
            return 0

        jax.lax.fori_loop(0, k, update, 0)
        return 0

    jax.lax.fori_loop(0, n, back, 0)


def _pallas_chol_solve(a, b, *, interpret: bool):
    """Solutions [n, r, rows, lanes] of the stack ``a`` [n, n, rows,
    lanes], ``b`` [n, r, rows, lanes]; ``rows`` is at most
    :data:`BLOCK_ROWS` or a multiple of it."""
    n, r, rows, lanes = b.shape
    block = min(rows, BLOCK_ROWS)
    mat = pl.BlockSpec((n, n, block, lanes), lambda i: (0, 0, i, 0))
    rhs = pl.BlockSpec((n, r, block, lanes), lambda i: (0, 0, i, 0))
    return pl.pallas_call(
        _kernel,
        grid=(rows // block,),
        in_specs=[mat, rhs],
        out_specs=rhs,
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        scratch_shapes=[pltpu.VMEM((n, n, block, lanes), a.dtype),
                        pltpu.VMEM((n, block, lanes), a.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="chol_solve",
    )(a, b)


def chol_solve(a, b, *, interpret: bool):
    """Solutions [batch, n, r] of ``a x = b`` for a stack ``a`` [batch, n,
    n] of symmetric positive definite matrices and ``b`` [batch, n, r].

    ``a`` is symmetrized as ``(a + a^T) / 2``, as ``jnp.linalg.cholesky``
    does, and the batch is padded with identity systems to whole rows of
    :data:`LANES`."""
    batch, n, _ = a.shape
    r = b.shape[-1]
    lanes = min(LANES, max(batch, 1))
    rows = -(-batch // lanes)
    if rows > BLOCK_ROWS:
        rows = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    pad = rows * lanes - batch
    eye = jnp.broadcast_to(jnp.eye(n, dtype=a.dtype)[..., None], (n, n, pad))
    at = jnp.moveaxis(a + jnp.swapaxes(a, 1, 2), 0, -1) / 2
    at = jnp.concatenate([at, eye], axis=-1)
    bt = jnp.pad(jnp.moveaxis(b, 0, -1), ((0, 0), (0, 0), (0, pad)))
    x = _pallas_chol_solve(at.reshape(n, n, rows, lanes),
                           bt.reshape(n, r, rows, lanes), interpret=interpret)
    return jnp.moveaxis(x.reshape(n, r, rows * lanes)[..., :batch], -1, 0)
