"""Symmetric eigendecomposition for the encoder, routed by batch shape.

``eigh(a)`` is ``jnp.linalg.eigh`` for one matrix.  Under ``jax.vmap`` (the
fleet engine's tenant axis) a ``custom_vmap`` rule sees the whole stack and
picks one of two solvers from its shape:

* a stack of at least :data:`B0` matrices of size at most :data:`N_MAX`
  goes to :func:`jacobi`, a cyclic Jacobi solver that rotates every matrix
  of the stack at once;
* anything else (one matrix, a small stack, wide matrices) keeps
  ``jnp.linalg.eigh``: XLA's TPU eigh or LAPACK's ``syevd``.

XLA's batched TPU eigh handles one small matrix at a time (about 40 us per
21 x 21 matrix on a v5e, PERF.md section 6), while the arithmetic is a few
hundred thousand flops.  :func:`jacobi` (the Pallas kernel in
``kernels/jacobi_eigh``) holds the stack as ``[n, n, batch]`` with the batch
on the minor (lane) axis, in VMEM for all its sweeps, and rotates every
matrix at once in element-wise float32: no matrix product, so no bfloat16
MXU pass enters.  Its ops run under the named scope ``jacobi_eigh``.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jnp.ndarray

#: Smallest stack that :func:`eigh` sends to :func:`jacobi`, and the largest
#: matrix size it sends there: the chip sweep of batch x size against
#: ``jnp.linalg.eigh`` (``benchmarks/eigh_crossover.py``, PERF.md section 6,
#: TPU v5e).  The kernel's time hardly depends on the batch up to 1,024
#: matrices, XLA's grows with it; from 128 matrices on the kernel wins at
#: every measured size up to 64 (at 62, 11.8 against 20.8 ms), below 128
#: it loses at 62 and 64.  Above 64 a block of 1,024 matrices no longer
#: fits VMEM (64 MB a buffer at 128).
B0 = 128
N_MAX = 64

#: Sweeps after which :func:`jacobi` stops whether or not it converged.
#: Seeded Grams of the paper's widths converge in far fewer (PERF.md,
#: tests/test_eigh.py checks it).
MAX_SWEEPS = 32


class JacobiResult(NamedTuple):
    """Ascending eigenvalues [..., n], eigenvectors as columns [..., n, n]
    (as ``jnp.linalg.eigh`` gives them) and the sweeps the stack took."""

    eigenvalues: Array
    eigenvectors: Array
    sweeps: Array


def _interpret() -> bool:
    """The kernel interprets exactly when the default backend is the CPU."""
    return jax.default_backend() == "cpu"


def jacobi(a: Array) -> JacobiResult:
    """Eigendecomposition of a stack ``a`` [..., n, n] of symmetric matrices
    by cyclic Jacobi in round-robin (parallel) order
    (``kernels/jacobi_eigh``): sweeps run until every matrix meets
    ``|a_pq| <= eps * sqrt(|a_pp * a_qq|)``, or for :data:`MAX_SWEEPS`.
    ``a`` is read whole, not one triangle, and should be symmetric."""
    # Imported here: Pallas costs about 1.7 s of import, which no process
    # that never takes this route should pay.
    from repro.kernels.jacobi_eigh import jacobi_eigh

    *lead, n, n2 = a.shape
    if n != n2:
        raise ValueError(f"jacobi needs square matrices, got {a.shape}")
    with jax.named_scope("jacobi_eigh"):
        w, v, sweeps = jacobi_eigh(jnp.reshape(a, (-1, n, n)),
                                   max_sweeps=MAX_SWEEPS,
                                   interpret=_interpret())
    return JacobiResult(w.reshape(*lead, n), v.reshape(*lead, n, n), sweeps)


def eigh_stack(a: Array) -> tuple[Array, Array]:
    """(eigenvalues ascending, eigenvectors as columns) of a stack
    [..., n, n]: :func:`jacobi` for a stack of at least :data:`B0` matrices
    no wider than :data:`N_MAX`, else ``jnp.linalg.eigh``."""
    *lead, n, _ = a.shape
    if math.prod(lead) >= B0 and n <= N_MAX:
        w, v, _ = jacobi(a)
        return w, v
    return tuple(jnp.linalg.eigh(a))


@jax.custom_batching.custom_vmap
def eigh(a: Array) -> tuple[Array, Array]:
    """``jnp.linalg.eigh`` of one symmetric matrix ``a`` [n, n].

    Under ``vmap`` the whole stack goes to :func:`eigh_stack`, which picks
    the solver from the stack's shape."""
    return tuple(jnp.linalg.eigh(a))


@eigh.def_vmap
def _eigh_vmap(axis_size, in_batched, a):  # noqa: ARG001
    (batched,) = in_batched
    if not batched:
        return eigh(a), (False, False)
    return eigh_stack(a), (True, True)


__all__ = ["B0", "N_MAX", "MAX_SWEEPS", "JacobiResult", "jacobi", "eigh",
           "eigh_stack"]
