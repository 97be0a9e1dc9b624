"""TLD — Train one Layer of the Decoder via an auxiliary ELM-AE (paper Alg. 2).

To obtain the weights between decoder layers l and l+1, an auxiliary
single-hidden-layer sparse autoencoder is built:

  stage 1 (c0 -> c1):  fixed random weights W_c1 (Xavier by default) + random
                       bias b_c1;  H_c1 = f(W_c1^T H_l + b_c1 1^T)
  stage 2 (c1 -> c2):  ROLANN solves the reconstruction H_c1 -> H_l in closed
                       form; its weights transposed become the decoder layer:
                       W_{l+1} = W_c2^T.

The paper's Algorithm 2 returns a bias ``b_{l+1}`` whose provenance is
dimensionally ambiguous (see DESIGN.md §1); ``aux_bias`` selects between
``"zero"`` (no decoder bias, default) and ``"c1"`` (reuse the auxiliary random
bias, which has the right dimension m_{l+1}).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import activations, initializers, rolann, stats_backend

Array = jnp.ndarray


class LayerResult(NamedTuple):
    w: Array            # [m_l, m_{l+1}] decoder weights for layer l+1
    b: Array            # [m_{l+1}] decoder bias
    h: Array            # [m_{l+1}, n] layer output on the training data
    knowledge: rolann.RolannFactors | rolann.RolannStats  # federated state


def stage1(
    key: jax.Array,
    m_in: int,
    m_out: int,
    init: str,
    dtype=jnp.float32,
) -> tuple[Array, Array]:
    """Fixed random stage-1 parameters (shared across federated nodes)."""
    k_w, k_b = jax.random.split(key)
    w_c1 = initializers.get(init)(k_w, (m_in, m_out), dtype)
    b_c1 = jax.random.normal(k_b, (m_out,), dtype)  # N(0, 1) per the paper
    return w_c1, b_c1


def train_layer(
    key: jax.Array,
    h_l: Array,
    m_next: int,
    lam: float,
    act: activations.Activation,
    *,
    init: str = "xavier",
    aux_bias: str = "zero",
    method: str = "gram",
    gram_solver: str = "chol",
) -> LayerResult:
    """Alg. 2: train the decoder layer mapping H_l [m_l, n] -> H_{l+1}."""
    m_l = h_l.shape[0]
    with jax.named_scope("forward"):
        w_c1, b_c1 = stage1(key, m_l, m_next, init, h_l.dtype)
        h_c1 = act.fn(w_c1.T @ h_l + b_c1[:, None])  # [m_next, n]

    # ROLANN solves the reconstruction h_c1 -> h_l; rolann.fit returns W with
    # shape [inputs=m_next, outputs=m_l].  The decoder layer needs
    # W_{l+1} in R^{m_l x m_next} so that H_{l+1} = f(W_{l+1}^T H_l + b 1^T)
    # (Eq. 4); the ELM-AE transpose trick W_{l+1} = W_c2^T gives exactly that.
    w_c2, _b_c2, knowledge = rolann.fit(
        h_c1, h_l, act, lam, method=method, gram_solver=gram_solver,
    )
    with jax.named_scope("solve"):
        w_next = w_c2.T  # [m_l, m_next]
        if aux_bias == "zero":
            b_next = jnp.zeros((m_next,), h_l.dtype)
        elif aux_bias == "c1":
            b_next = b_c1
        else:
            raise ValueError(f"unknown aux_bias {aux_bias!r}")

    with jax.named_scope("forward"):
        h_next = act.fn(w_next.T @ h_l + b_next[:, None])
    return LayerResult(w=w_next, b=b_next, h=h_next, knowledge=knowledge)


def layer_knowledge_from_partition(
    key: jax.Array,
    h_l: Array,
    m_next: int,
    act: activations.Activation,
    *,
    init: str = "xavier",
    method: str = "gram",
    factorization: str = "direct_svd",
) -> rolann.RolannFactors | rolann.RolannStats:
    """Federated building block: compute ONLY the mergeable ROLANN statistics
    of this partition for the given decoder layer (stage-1 randomness is
    derived from the shared key, so all nodes agree)."""
    m_l = h_l.shape[0]
    with jax.named_scope("forward"):
        w_c1, b_c1 = stage1(key, m_l, m_next, init, h_l.dtype)
        h_c1 = act.fn(w_c1.T @ h_l + b_c1[:, None])
    if method == "gram":
        return rolann.compute_stats(h_c1, h_l, act)
    if factorization == "gram_eigh":
        return rolann.compute_factors_via_gram(h_c1, h_l, act)
    return rolann.compute_factors(h_c1, h_l, act)


def accumulate_layer_stats(
    stats: rolann.RolannStats,
    w_c1: Array,
    b_c1: Array,
    h_l: Array,
    act: activations.Activation,
    *,
    weights: Array | None = None,
) -> rolann.RolannStats:
    """Streaming building block: fold one sample chunk of layer inputs
    ``h_l`` [m_l, n_chunk] into the decoder layer's running ROLANN statistics.

    The auxiliary stage-1 projection is recomputed for the chunk (cheap: one
    matmul + activation) and the reconstruction statistics h_c1 -> h_l are
    accumulated via `rolann.accumulate_stats`; summed over all chunks this
    equals `train_layer`'s one-shot statistics, so the solved weights match
    the non-streaming fit.  ``weights`` masks padded sample columns.

    Where ``stats_backend.producer()`` is the fused one (non-linear
    activations) the whole fold is ONE ``stats_backend.fused_chunk_acc``
    dispatch — the stage-1 matmul, activation, target transform and (G, M)
    accumulate run in a single Pallas launch, so the chunk activation never
    materializes to HBM.  The einsum producer (and the linear last layer,
    which has a cheaper shared-F closed form) keeps the two-step path below.
    """
    if stats_backend.producer() == "fused" and act.name != "linear":
        with jax.named_scope("stats"):
            g, m = stats_backend.fused_chunk_acc(
                stats.g, stats.m, h_l, w_c1, b_c1, weights, act=act
            )
        return rolann.RolannStats(g=g, m=m)
    with jax.named_scope("forward"):
        h_c1 = act.fn(w_c1.T @ h_l + b_c1[:, None])
    return rolann.accumulate_stats(stats, h_c1, h_l, act, weights=weights)


def layer_from_knowledge(
    knowledge: rolann.RolannFactors | rolann.RolannStats,
    key: jax.Array,
    m_l: int,
    m_next: int,
    lam: float,
    act: activations.Activation,
    *,
    init: str = "xavier",
    aux_bias: str = "zero",
    dtype=jnp.float32,
    gram_solver: str = "chol",
) -> tuple[Array, Array]:
    """Solve the decoder layer weights from (merged) federated knowledge."""
    w_c2, _ = rolann.solve(knowledge, lam, gram_solver=gram_solver)
    with jax.named_scope("solve"):
        w_next = w_c2.T
        if aux_bias == "zero":
            b_next = jnp.zeros((m_next,), dtype)
        elif aux_bias == "c1":
            _, b_c1 = stage1(key, m_l, m_next, init, dtype)
            b_next = b_c1
        else:
            raise ValueError(f"unknown aux_bias {aux_bias!r}")
    return w_next, b_next
