"""ROLANN — Regularized One-Layer Neural Network (Fontenla-Romero et al. 2021).

Closed-form, incremental, distributed training of a one-layer network
``y = f(W^T x + b)`` by minimizing the MSE measured *before* the activation:

    min_w  sum_i f'(dbar_i)^2 (w^T x_i - dbar_i)^2 + lam * ||w||^2

with ``dbar = f^{-1}(d)``.  For each output neuron j the solution is

    w_j = U (S^2 + lam I)^{-1} U^T m_j,

where ``U, S = SVD(Xa F_j)``, ``F_j = diag(f'(dbar_j))``, ``m_j = Xa (f'^2 ∘ dbar_j)``
and ``Xa`` is the input matrix augmented with a row of ones (bias).

Two mathematically equivalent sufficient-statistic representations are
implemented:

* **Factors** ``(U, S, M)`` — the paper's representation.  Merging two
  partitions is ``SVD([U_a S_a | U_b S_b])`` (Eq. 8) plus ``M_a + M_b``
  (Eq. 9).  This is what federated nodes exchange in the paper.
* **Gram** ``(G, M)`` with ``G = (Xa F)(Xa F)^T = U S^2 U^T`` — merging is a
  plain sum, so on a mesh the federated aggregation is a single ``psum``.
  This is the beyond-paper fast path (see DESIGN.md §1); it yields identical
  weights because only ``U S^2 U^T`` and ``M`` enter the solution.

Conventions follow the paper: data matrices are ``[features, samples]``
(columns are samples); targets are ``[outputs, samples]``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import activations, chol, stats_backend

Array = jnp.ndarray


class RolannFactors(NamedTuple):
    """Paper-faithful incremental knowledge (U_k, S_k, M_k).

    Shapes (``out`` axis absent when ``F`` is shared, i.e. linear activation):
      u: [out, m, r]   left singular vectors of Xa F
      s: [out, r]      singular values
      m: [out, m]      the paper's M vector per output
    """

    u: Array
    s: Array
    m: Array

    @property
    def shared_f(self) -> bool:
        return self.u.ndim == 2


class RolannStats(NamedTuple):
    """Gram-form incremental knowledge (G, M); ``G = U S^2 U^T``.

      g: [out, m, m] (or [m, m] when F is shared)
      m: [out, m]
    """

    g: Array
    m: Array

    @property
    def shared_f(self) -> bool:
        return self.g.ndim == 2


def _augment(x: Array) -> Array:
    """Append the bias row of ones: [m, n] -> [m+1, n]."""
    return jnp.concatenate([x, jnp.ones((1, x.shape[1]), x.dtype)], axis=0)


def _targets(d: Array, act: activations.Activation) -> tuple[Array, Array]:
    """Return (dbar, fprime) per output/sample for targets d [out, n]."""
    d = act.clip_to_range(d)
    dbar = act.inv(d)
    fprime = act.deriv(dbar)
    return dbar, fprime


# ---------------------------------------------------------------------------
# Sufficient statistics
# ---------------------------------------------------------------------------

def compute_stats(x: Array, d: Array, act: activations.Activation) -> RolannStats:
    """Gram-form statistics for inputs x [m, n] and targets d [out, n]; the
    producer of the per-output Grams is `core.stats_backend`'s choice."""
    act = activations.get(act.name, invertible_required=True)
    with jax.named_scope("stats"):
        xa = _augment(x)  # [m+1, n]
        dbar, fp = _targets(d, act)
        fsq = fp * fp
        if act.name == "linear":
            # Shared F: one [m, m] Gram for all outputs — a single matmul XLA
            # already fuses; the per-output kernel has nothing to win here.
            m_vec = jnp.einsum("in,on->oi", xa, fsq * dbar)
            g = xa @ xa.T
        else:
            # Per-output Gram: G_j = Xa diag(fp_j^2) Xa^T.  The output axis is
            # embarrassingly parallel — shard it over the model mesh axis when
            # one is active (the paper's pool.map over cores, TPU-native).
            from repro.models import hints

            g, m_vec = stats_backend.gram_stats(xa, fsq, fsq * dbar)
            g = hints.hint(g, {0: "model"})
    return RolannStats(g=g, m=m_vec)


def init_stats(
    n_inputs: int, n_outputs: int, act: activations.Activation, dtype=jnp.float32
) -> RolannStats:
    """Zero Gram-form accumulators for a streamed fit over inputs [n_inputs, ·]
    and targets [n_outputs, ·] — the identity of ``merge_stats``.  Linear
    activations share one Gram across outputs (see ``compute_stats``)."""
    m_aug = n_inputs + 1  # bias row
    with jax.named_scope("stats"):
        if act.name == "linear":
            g = jnp.zeros((m_aug, m_aug), dtype)
        else:
            g = jnp.zeros((n_outputs, m_aug, m_aug), dtype)
        return RolannStats(g=g, m=jnp.zeros((n_outputs, m_aug), dtype))


def accumulate_stats(
    stats: RolannStats,
    x: Array,
    d: Array,
    act: activations.Activation,
    *,
    weights: Array | None = None,
) -> RolannStats:
    """Fold one sample chunk into running Gram-form statistics.

    Mathematically ``merge_stats(stats, compute_stats(x, d, act))`` — the
    paper's Eq. 6-7 statistics are additive over sample blocks — but computed
    as a single accumulating fold (`stats_backend.gram_stats_acc`): the fused
    producer aliases the running (G, M) onto the kernel outputs, so a chunked
    fit makes one HBM pass per chunk with no re-zeroing.

    ``weights`` ([n] in {0, 1}) masks padded sample columns: a zero weight
    removes the column's contribution to both G and M exactly, so ragged
    chunks can be padded to a fixed shape without biasing the statistics.
    """
    act = activations.get(act.name, invertible_required=True)
    with jax.named_scope("stats"):
        xa = _augment(x)  # [m+1, n]
        dbar, fp = _targets(d, act)
        fsq = fp * fp
        fd = fsq * dbar
        if weights is not None:
            w = weights.astype(xa.dtype)
            fsq = fsq * w[None, :]
            fd = fd * w[None, :]
        if act.name == "linear":
            # Shared F: fp == 1, so masking must hit the Gram's columns directly.
            xw = xa if weights is None else xa * w[None, :]
            g = stats.g + xw @ xa.T
            m_vec = stats.m + jnp.einsum("in,on->oi", xa, fd)
            return RolannStats(g=g, m=m_vec)
        g, m_vec = stats_backend.gram_stats_acc(stats.g, stats.m, xa, fsq, fd)
    return RolannStats(g=g, m=m_vec)


def compute_factors(x: Array, d: Array, act: activations.Activation) -> RolannFactors:
    """Paper-faithful statistics via SVD of Xa F (Eq. 6-7)."""
    act = activations.get(act.name, invertible_required=True)
    with jax.named_scope("stats"):
        xa = _augment(x)
        dbar, fp = _targets(d, act)
        m_vec = jnp.einsum("in,on->oi", xa, fp * fp * dbar)
        if act.name == "linear":
            u, s, _ = jnp.linalg.svd(xa, full_matrices=False)
            r = min(xa.shape)
            return RolannFactors(u=u[:, :r], s=s[:r], m=m_vec)

        def one(fp_j: Array) -> tuple[Array, Array]:
            u, s, _ = jnp.linalg.svd(xa * fp_j[None, :], full_matrices=False)
            return u, s

        u, s = jax.vmap(one)(fp)
    return RolannFactors(u=u, s=s, m=m_vec)


def compute_factors_via_gram(
    x: Array, d: Array, act: activations.Activation
) -> RolannFactors:
    """Paper-protocol factors (U, S, M) derived from the local Gram by eigh.

    Identical message content/privacy to ``compute_factors`` (U S^2 U^T is
    the same), but never materializes the implicit right factors of the
    [m, n_local] matrix — at pod scale (n_local ~ 256k) the direct SVD's
    workspace is hundreds of GiB while this stays O(m^2) (EXPERIMENTS §Perf).
    """
    stats = compute_stats(x, d, act)
    with jax.named_scope("stats"):
        return stats_to_factors(stats)


def stats_to_factors(stats: RolannStats) -> RolannFactors:
    """Convert Gram form to factor form via eigh (G = U S^2 U^T)."""

    def one(g: Array) -> tuple[Array, Array]:
        evals, evecs = jnp.linalg.eigh(g)
        evals = jnp.maximum(evals, 0.0)
        # eigh returns ascending order; flip to match SVD's descending.
        return evecs[:, ::-1], jnp.sqrt(evals[::-1])

    if stats.shared_f:
        u, s = one(stats.g)
    else:
        u, s = jax.vmap(one)(stats.g)
    return RolannFactors(u=u, s=s, m=stats.m)


def factors_to_stats(f: RolannFactors) -> RolannStats:
    if f.shared_f:
        g = (f.u * (f.s * f.s)[None, :]) @ f.u.T
    else:
        g = jnp.einsum("oir,or,ojr->oij", f.u, f.s * f.s, f.u)
    return RolannStats(g=g, m=f.m)


# ---------------------------------------------------------------------------
# Incremental / federated merging
# ---------------------------------------------------------------------------

def merge_stats(a: RolannStats, b: RolannStats) -> RolannStats:
    """Gram-form merge: a plain sum (maps to psum on a mesh)."""
    return RolannStats(g=a.g + b.g, m=a.m + b.m)


def mask_knowledge(knowledge, w: Array):
    """Scale a knowledge contribution by ``w`` (in {0, 1}).

    ``w = 0`` turns the contribution into the merge IDENTITY of either
    representation: zeroed (G, M) adds nothing to a Gram sum, and zeroed
    singular values make the factor columns vanish from the concat-SVD
    (Eq. 8) while M drops out of Eq. 9.  This is what lets a fixed-shape
    tree reduction run over a SUBSET of participants — masked slots ride
    along as no-ops (`fleet_sharded.merge_state_tree`).

    ``w`` broadcasts from the left: a scalar masks one contribution, a
    leading [S] vector masks a stacked batch of S contributions.
    """
    w = jnp.asarray(w)

    def scale(leaf):
        return leaf * w.reshape(w.shape + (1,) * (leaf.ndim - w.ndim))

    if isinstance(knowledge, RolannStats):
        return RolannStats(g=scale(knowledge.g), m=scale(knowledge.m))
    return RolannFactors(u=knowledge.u, s=scale(knowledge.s),
                         m=scale(knowledge.m))


def merge_factors(a: RolannFactors, b: RolannFactors) -> RolannFactors:
    """Paper's Eq. 8-9: SVD of the concatenated weighted factors.

    The result is truncated to rank m (= row dimension), which is exact:
    rank([U_a S_a | U_b S_b]) <= m.
    """

    def one(ua, sa, ub, sb):
        cat = jnp.concatenate([ua * sa[None, :], ub * sb[None, :]], axis=1)
        u, s, _ = jnp.linalg.svd(cat, full_matrices=False)
        m_dim = ua.shape[0]
        return u[:, :m_dim], s[:m_dim]

    if a.shared_f != b.shared_f:
        raise ValueError("cannot merge shared-F with per-output factors")
    if a.shared_f:
        u, s = one(a.u, a.s, b.u, b.s)
    else:
        u, s = jax.vmap(one)(a.u, a.s, b.u, b.s)
    return RolannFactors(u=u, s=s, m=a.m + b.m)


def merge_factors_list(items: list[RolannFactors]) -> RolannFactors:
    """Merge P partitions as the paper does at the aggregator node:
    one SVD of the full concatenation [U^1 S^1 | ... | U^P S^P]."""
    if not items:
        raise ValueError("empty factor list")

    def one(us_list):
        cat = jnp.concatenate(us_list, axis=-1)
        u, s, _ = jnp.linalg.svd(cat, full_matrices=False)
        m_dim = cat.shape[-2]
        return u[..., :, :m_dim], s[..., :m_dim]

    if len({f.shared_f for f in items}) != 1:
        raise ValueError("cannot merge shared-F with per-output factors")
    us = [f.u * f.s[..., None, :] for f in items]
    # One code path for both layouts: the batched SVD in `one` handles the
    # leading out axis when present and degenerates to the plain 2-D SVD for
    # shared-F factors.
    u, s = one(us)
    m = sum(f.m for f in items[1:]) + items[0].m
    return RolannFactors(u=u, s=s, m=m)


# ---------------------------------------------------------------------------
# Solving for weights
# ---------------------------------------------------------------------------

GRAM_SOLVERS = ("chol", "auto", "eigh")


def _solve_factors(knowledge: RolannFactors, lam) -> Array:
    """Factor-form augmented weights: w_aug[:, j] = U (S^2+lam)^-1 U^T m_j."""
    u, s, m = knowledge
    if knowledge.shared_f:
        proj = u.T @ m.T  # [r, out]
        return u @ (proj / (s * s + lam)[:, None])  # [m, out]
    proj = jnp.einsum("oir,oi->or", u, m)
    return jnp.einsum("oir,or->oi", u, proj / (s * s + lam)).T  # [m, out]


def _solve_stats_chol(stats: RolannStats, lam) -> Array:
    """Gram-form augmented weights by direct Cholesky: (G + lam I) w_j = m_j.

    G = U S^2 U^T with a full orthonormal eigenbasis, so this is the same
    linear system the eigh route diagonalizes — one triangular factorization
    (O(m^3/3), small constant) instead of a batched symmetric eigendecomposition.
    ``chol.chol_solve`` routes the stack of every output (and, under the
    fleet's ``vmap``, of every tenant) by its shape.
    """
    a = stats.g + lam * jnp.eye(stats.m.shape[-1], dtype=stats.g.dtype)
    if stats.shared_f:
        return chol.chol_solve(a, stats.m.T)  # [m, out]
    return jax.vmap(chol.chol_solve)(a, stats.m).T  # [m, out]


def solve(
    knowledge: RolannFactors | RolannStats,
    lam: float,
    *,
    gram_solver: str = "chol",
) -> tuple[Array, Array]:
    """Return (W [m_in, out], b [out]) from accumulated knowledge (Eq. 10).

    Gram-form knowledge (`RolannStats`) solves ``(G + lam I) w = M`` directly
    by Cholesky — ``G + lam I`` is symmetric positive definite by construction
    (G is PSD, lam > 0) — which is the post-stats hot spot: it replaces the
    batched eigh of ``stats_to_factors`` on every gram-method fit/merge.

    ``gram_solver`` selects the route for stats knowledge:

    * ``"chol"`` (default) — direct Cholesky solve;
    * ``"eigh"``           — the factorization route (eigh + factor solve),
                             kept for near-singular G (lam vanishingly small
                             relative to ||G||, where a float32 Cholesky can
                             break down) and as the parity oracle;
    * ``"auto"``           — Cholesky, rescued by the eigh route when the
                             triangular solve comes back non-finite.  The
                             rescue is a ``lax.cond``: lazy (taken branch
                             only) in straight-line jit, but under ``vmap``
                             it lowers to a select that pays BOTH routes —
                             prefer "chol" on batched hot paths.

    Factor-form knowledge (`RolannFactors`) always uses the factor solve.
    """
    if gram_solver not in GRAM_SOLVERS:
        raise ValueError(
            f"unknown gram_solver {gram_solver!r}: choose from {GRAM_SOLVERS}"
        )
    with jax.named_scope("solve"):
        if isinstance(knowledge, RolannStats) and gram_solver != "eigh":
            w_aug = _solve_stats_chol(knowledge, lam)
            if gram_solver == "auto":
                w_aug = jax.lax.cond(
                    jnp.all(jnp.isfinite(w_aug)),
                    lambda w: w,
                    lambda w: _solve_factors(stats_to_factors(knowledge), lam),
                    w_aug,
                )
            return w_aug[:-1, :], w_aug[-1, :]
        if isinstance(knowledge, RolannStats):
            knowledge = stats_to_factors(knowledge)
        w_aug = _solve_factors(knowledge, lam)
        return w_aug[:-1, :], w_aug[-1, :]


def fit(
    x: Array,
    d: Array,
    act: activations.Activation,
    lam: float,
    *,
    method: str = "gram",
    gram_solver: str = "chol",
) -> tuple[Array, Array, RolannFactors | RolannStats]:
    """One-shot ROLANN fit. Returns (W, b, knowledge).

    method: "gram" (fast path, psum-mergeable) or "svd" (paper-faithful).
    gram_solver: weight-solve route for gram knowledge (see `solve`).
    """
    if method == "gram":
        knowledge: RolannFactors | RolannStats = compute_stats(x, d, act)
    elif method == "svd":
        knowledge = compute_factors(x, d, act)
    else:
        raise ValueError(f"unknown ROLANN method {method!r}")
    w, b = solve(knowledge, lam, gram_solver=gram_solver)
    return w, b, knowledge


def predict(x: Array, w: Array, b: Array, act: activations.Activation) -> Array:
    """Apply the trained one-layer network: f(W^T x + b)."""
    return act.fn(w.T @ x + b[:, None])
