"""Distributed truncated SVD (DSVD) — the DAEF encoder (paper §4.1).

The encoder weights are ``W1 = U_m1``, the first ``m1`` left singular vectors
of the data matrix ``X in R^{m0 x n}``.  Distributed across P partitions
``X = [X^1 | ... | X^P]`` the paper computes (Eq. 2, after Iwen & Ong 2016):

    [U, S, V] = SVD([U^1 S^1 | ... | U^P S^P])

where ``U^p, S^p`` come from the local SVD of ``X^p``.  ``V`` is never formed
— only ``U^p S^p`` products are exchanged, which preserves privacy.

As with ROLANN, ``U S^2 U^T = X X^T``: the Gram-sum path (``psum`` of local
``X^p X^p^T`` followed by one ``eigh``) is mathematically identical and is our
beyond-paper fast path.  ``factors_to_gram`` takes factors back to their
Gram, so factors merged by a tree under ``method="gram"`` are summed as
Grams and factored once, at the root (``fleet_sharded._tree_merge``).
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core import eigh

Array = jnp.ndarray


class SvdFactors(NamedTuple):
    """Truncated left factorization: u [m, r], s [r]."""

    u: Array
    s: Array


def canonicalize_signs(u: Array) -> Array:
    """Fix the SVD sign ambiguity: flip each column of U so its
    largest-magnitude entry is positive.  The encoder uses U directly as
    weights (W1 = U_m1), so without this the "gram" and "svd" paths — and any
    two BLAS implementations — would produce sign-flipped (equally valid but
    non-comparable) models."""
    idx = jnp.argmax(jnp.abs(u), axis=0)
    signs = jnp.sign(u[idx, jnp.arange(u.shape[1])])
    signs = jnp.where(signs == 0, 1.0, signs)
    return u * signs[None, :]


def local_svd(x: Array, rank: int | None = None) -> SvdFactors:
    """Local SVD of one partition x [m, n_p]; keep at most ``rank`` factors.

    Note: for the *merge* to be exact, locals must keep full rank
    (r = min(m, n_p)); rank-truncation before merging is the paper's
    approximation when m1 < m is requested early.  We keep full row rank by
    default and truncate only at the end.
    """
    u, s, _ = jnp.linalg.svd(x, full_matrices=False)
    if rank is not None:
        u, s = u[:, :rank], s[:rank]
    return SvdFactors(u=canonicalize_signs(u), s=s)


def merge_factors(parts: Sequence[SvdFactors]) -> SvdFactors:
    """Paper's Eq. 2: SVD of the concatenated U^p S^p blocks."""
    cat = jnp.concatenate([p.u * p.s[None, :] for p in parts], axis=1)
    u, s, _ = jnp.linalg.svd(cat, full_matrices=False)
    m = cat.shape[0]
    return SvdFactors(u=canonicalize_signs(u[:, :m]), s=s[:m])


def merge_pair(a: SvdFactors, b: SvdFactors) -> SvdFactors:
    """Incremental two-way merge (new data block arriving at a node)."""
    return merge_factors([a, b])


def gram(x: Array) -> Array:
    """Local Gram matrix X^p X^p^T — psum-able sufficient statistic."""
    return x @ x.T


def masked_gram(x: Array, mask: Array | None = None) -> Array:
    """Gram contribution of one sample chunk; ``mask`` ([n] in {0, 1}) zeroes
    padded columns exactly, so streamed fits can pad ragged chunks to a fixed
    shape.  Accumulating these per chunk == ``gram`` of the concatenation —
    the additivity the encoder's streaming pass relies on."""
    if mask is None:
        return x @ x.T
    return (x * mask.astype(x.dtype)[None, :]) @ x.T


def gram_to_factors(g: Array) -> SvdFactors:
    """eigh of the summed Gram == the merged SVD factors (fast path).

    ``g`` is one Gram [m, m] or a stack [..., m, m].  A stack, given whole
    or under ``vmap``, reaches ``eigh.eigh_stack``, which picks the solver
    from the stack's shape (``core/eigh.py``)."""
    if g.ndim > 2:
        lead = g.shape[:-2]
        f = jax.vmap(gram_to_factors)(g.reshape(-1, *g.shape[-2:]))
        return SvdFactors(u=f.u.reshape(*lead, *f.u.shape[1:]),
                          s=f.s.reshape(*lead, *f.s.shape[1:]))
    evals, evecs = eigh.eigh(g)
    evals = jnp.maximum(evals, 0.0)
    return SvdFactors(u=canonicalize_signs(evecs[:, ::-1]), s=jnp.sqrt(evals[::-1]))


def factors_to_gram(f: SvdFactors) -> Array:
    """``U diag(s^2) U^T`` [..., m, m], the Gram the factors came from
    (``X X^T``): the inverse of `gram_to_factors`, and additive like it.

    Element-wise float32 products summed over the rank axis, so no
    reduced-precision matrix-unit pass enters and the result is exactly
    symmetric (the Jacobi route of `gram_to_factors` reads it whole)."""
    us = jnp.swapaxes(f.u * f.s[..., None, :], -1, -2)     # [..., r, m]
    return jnp.sum(us[..., :, :, None] * us[..., :, None, :], axis=-3)


def truncate(f: SvdFactors, rank: int) -> SvdFactors:
    return SvdFactors(u=f.u[..., :rank], s=f.s[..., :rank])


def pad_rank(f: SvdFactors, rank: int) -> SvdFactors:
    """Zero-pad (u, s) with trailing zero factors up to ``rank``.

    Exact under both merge algebras: zero singular values contribute nothing
    to the concat-SVD (Eq. 2/8) and leave ``U S^2 U^T`` unchanged.  This is
    how ragged local factorizations (r = min(m, n_p) varies with the local
    sample count) become stackable into one fixed-shape batch — e.g. the
    async federation ledger, where site states must share a shape to ride
    the masked on-mesh tree reduction.
    """
    r = f.s.shape[-1]
    if r > rank:
        raise ValueError(
            f"cannot pad rank {r} down to {rank} — use dsvd.truncate"
        )
    if r == rank:
        return f
    pad_u = [(0, 0)] * (f.u.ndim - 1) + [(0, rank - r)]
    pad_s = [(0, 0)] * (f.s.ndim - 1) + [(0, rank - r)]
    return SvdFactors(u=jnp.pad(f.u, pad_u), s=jnp.pad(f.s, pad_s))


def dsvd(
    partitions: Sequence[Array],
    rank: int,
    *,
    method: str = "svd",
) -> SvdFactors:
    """Distributed SVD over explicit partitions (single-host simulation).

    method: "svd" — paper-faithful (local SVDs, concat, merge SVD);
            "gram" — sum of Gram matrices + one eigh (identical result).
    """
    if method == "svd":
        merged = merge_factors([local_svd(p) for p in partitions])
    elif method == "gram":
        g = sum(gram(p) for p in partitions)
        merged = gram_to_factors(g)
    else:
        raise ValueError(f"unknown DSVD method {method!r}")
    return truncate(merged, rank)
