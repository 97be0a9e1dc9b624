"""Cholesky solves of the decoder's systems, routed by stack shape.

``chol_solve(a, b)`` solves ``a x = b`` for one symmetric positive definite
``a`` [n, n] by ``jnp.linalg.cholesky`` and ``cho_solve``.  Under
``jax.vmap`` a ``custom_vmap`` rule sees the whole stack and hands it to
:func:`chol_solve_stack`, which picks one of two solvers from its shape and
dtype:

* a float32 stack of at least :data:`B0` systems of size at most
  :data:`N_MAX` goes to the Pallas kernel in ``kernels/chol_solve``, which
  factors and solves every system of the stack at once;
* anything else (one system, a small stack, wide systems, float64 or
  bfloat16) keeps XLA's ``cholesky`` and triangular solves.

The rule of :func:`chol_solve_stack` folds each further ``vmap`` into the
stack and routes again, so the fleet fit's per-output ``vmap`` inside its
per-tenant ``vmap`` reaches the router as one stack of K x o systems.

XLA's batched TPU Cholesky handles one small matrix at a time (about 1.8 us
per 17 x 17 matrix on a v5e, PERF.md section 6), while the arithmetic is a
few thousand flops.  The kernel holds the stack as ``[n, n, batch]`` with
the batch on the minor (lane) axis, in VMEM for the factorization and both
substitutions, in element-wise float32.  Its ops run under the named scope
``chol_solve``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import eigh

Array = jnp.ndarray

#: Smallest stack that :func:`chol_solve_stack` sends to the kernel, and the
#: largest system size it sends there.  The chip sweep of stack size x size
#: against XLA's route (``benchmarks/chol_crossover.py``, PERF.md section 6,
#: TPU v5e) has the kernel win at every stack of 16 systems or more, at every
#: size up to 63 (at 16,384 x 17, 0.145 against 31.3 ms), and at n = 17 and
#: 29 from 2 and 4 systems.  ``B0`` sits above every stack of
#: a one-model fit at the paper's widths (creditcard: 15-24 systems a
#: layer), so those programs keep XLA's route; ``N_MAX`` covers the sizes
#: swept.
B0 = 128
N_MAX = 64


def _xla_solve(a: Array, b: Array) -> Array:
    """``cholesky`` + ``cho_solve`` of one system: XLA's route."""
    chol = jnp.linalg.cholesky(a)
    return jax.scipy.linalg.cho_solve((chol, True), b)


def _takes_kernel(a: Array, b: Array) -> bool:
    *lead, n, _ = a.shape
    if not (a.dtype == b.dtype == jnp.float32 and math.prod(lead) >= B0
            and n <= N_MAX):
        return False
    # Imported here: Pallas costs about 1.7 s of import, which no process
    # that never takes this route should pay.
    from repro.kernels.chol_solve.kernel import VMEM_LIMIT, vmem_bytes

    return vmem_bytes(n, b.shape[-1] if b.ndim == a.ndim else 1) <= VMEM_LIMIT


@jax.custom_batching.custom_vmap
def chol_solve_stack(a: Array, b: Array) -> Array:
    """Solutions of ``a x = b`` for a stack ``a`` [..., n, n] and ``b``
    [..., n] or [..., n, r]: the ``chol_solve`` kernel for a float32 stack
    of at least :data:`B0` systems no wider than :data:`N_MAX`, else XLA's
    ``cholesky`` and ``cho_solve``, system by system under ``vmap``."""
    *lead, n, _ = a.shape
    if not _takes_kernel(a, b):
        solve = _xla_solve
        for _ in lead:
            solve = jax.vmap(solve)
        return solve(a, b)
    from repro.kernels.chol_solve import chol_solve as kernel

    with jax.named_scope("chol_solve"):
        a3 = a.reshape(-1, n, n)
        x = kernel(a3, b.reshape(a3.shape[0], n, -1), interpret=eigh._interpret())
        return x.reshape(b.shape)


def _stack_vmap(axis_size, in_batched, a, b):
    """The ``vmap`` rule of both functions: the new axis joins the stack's
    leading axes, and the whole stack is routed again."""
    a, b = (x if batched else jnp.broadcast_to(x, (axis_size, *x.shape))
            for batched, x in zip(in_batched, (a, b), strict=True))
    return chol_solve_stack(a, b), True


chol_solve_stack.def_vmap(_stack_vmap)


@jax.custom_batching.custom_vmap
def chol_solve(a: Array, b: Array) -> Array:
    """Solution of ``a x = b`` for one symmetric positive definite ``a``
    [n, n] and ``b`` [n] or [n, r], by ``cholesky`` and ``cho_solve``.

    Under ``vmap`` the whole stack goes to :func:`chol_solve_stack`, which
    picks the solver from the stack's shape and dtype."""
    return _xla_solve(a, b)


chol_solve.def_vmap(_stack_vmap)


__all__ = ["B0", "N_MAX", "chol_solve", "chol_solve_stack"]
