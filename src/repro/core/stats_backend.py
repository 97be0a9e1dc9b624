"""The per-output Gram sufficient statistics, and which producer makes them.

DAEF's training cost is dominated by the per-layer statistics (paper Eq. 6-7
in Gram form, DESIGN.md §1):

    G[o] = Xa · diag(f'²[o]) · Xaᵀ        [o, m, m]
    M[o] = Xa · (f'²[o] ∘ d̄[o])           [o, m]

Every Gram-stats consumer in the repo (``rolann.compute_stats``, the ELM-AE
layer trainer, the vmapped fleet kernels and the mesh-sharded paths) routes
through :func:`gram_stats` and its accumulating and fused-chunk siblings.
Two producers make them:

* ``"einsum"`` — unfused XLA einsums (:func:`einsum_stats`);
* ``"fused"``  — the Pallas ``rolann_stats`` kernels: one HBM pass streams
  the sample axis through VMEM and feeds both MXU contractions per tile
  (``kernels/rolann_stats``).  On the CPU they run in interpret mode —
  numerically the same, slower than XLA.

This module alone decides which one runs: :func:`producer` returns the
winner that the autotune cache (``kernels/autotune_cache.json``, written by
``benchmarks/kernel_autotune.py``) measured on the running platform, and
``"einsum"`` where nothing was measured.  No config field, plan field,
flag or caller argument selects it.

Each entry point consults :func:`producer` while JAX traces it, so the
choice is baked into the compiled program.  That is sound because nothing
it reads changes under a cached trace: the platform is fixed for the
process, the cache file is read once (``autotune.load_cache``), and the
shapes any later shape-keyed choice would read are part of the trace key.
Code that swaps the cache file mid-process (tests, the benchmark scripts)
calls ``autotune.clear_cache()`` and ``jax.clear_caches()`` after it —
``repro.testing.producer.stats_producer`` does both.

The chunked/streamed training path additionally exposes
:func:`fused_chunk_acc` — the whole per-layer chunk fold (stage-1 matmul +
activation + target transform + (G, M) accumulate) as ONE dispatch, so the
chunk activation never round-trips through HBM under the fused producer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: The producers :func:`producer` can return.
PRODUCERS = ("einsum", "fused")

Array = jnp.ndarray


def producer() -> str:
    """The Gram-stats producer for the running platform: the autotune
    cache's measured winner, ``"einsum"`` where unmeasured or unreadable
    (see ``autotune.preferred_backend``)."""
    from repro.kernels import autotune

    return autotune.preferred_backend()


def einsum_stats(xa: Array, fsq: Array, fd: Array) -> tuple[Array, Array]:
    """The einsum producer's (G, M); ``...`` is an optional leading tenant
    axis."""
    g = jnp.einsum("...in,...on,...jn->...oij", xa, fsq, xa)
    m = jnp.einsum("...in,...on->...oi", xa, fd)
    return g, m


def _lift(axis_size, arg, batched):
    """Broadcast an unbatched ``custom_vmap`` operand to the batch axis."""
    return arg if batched else jnp.broadcast_to(arg[None], (axis_size, *arg.shape))


def _gram_stats_unbatched(xa: Array, fsq: Array, fd: Array, which: str):
    if which == "fused":
        from repro.kernels.rolann_stats import rolann_stats

        return rolann_stats(xa, fsq, fd)
    return einsum_stats(xa, fsq, fd)


@functools.lru_cache(maxsize=None)
def _gram_stats_fn(which: str):
    """The per-call ``gram_stats`` body with a custom batching rule: under
    ``vmap`` (the fleet engine's tenant axis) the whole call collapses into
    ONE tenant-batched dispatch — for the fused producer that is a single
    3-D-grid kernel launch (``rolann_stats_batched``) instead of Pallas'
    generic per-tenant batching rule, and for einsum a single ``k``-batched
    contraction."""

    @jax.custom_batching.custom_vmap
    def f(xa, fsq, fd):
        return _gram_stats_unbatched(xa, fsq, fd, which)

    @f.def_vmap
    def _batched_rule(axis_size, in_batched, xa, fsq, fd):
        args = [_lift(axis_size, a, b) for a, b in zip((xa, fsq, fd), in_batched, strict=True)]
        return gram_stats_batched(*args), (True, True)

    return f


def gram_stats(xa: Array, fsq: Array, fd: Array) -> tuple[Array, Array]:
    """(G, M) per-output statistics for xa [m, n], fsq/fd [o, n].

    Both producers accumulate in float32 on the contraction and return the
    promoted input dtype, so they agree within accumulation-order error
    (tests/test_stats_backend.py pins the tolerances).

    Vmapping this function (the fleet engine does, over the tenant axis)
    dispatches to :func:`gram_stats_batched` via a ``custom_vmap`` rule, so
    a whole tenant batch is one batched-stats call — not K per-tenant calls
    batched generically.
    """
    return _gram_stats_fn(producer())(xa, fsq, fd)


def gram_stats_batched(xa: Array, fsq: Array, fd: Array) -> tuple[Array, Array]:
    """Tenant-batched (G, M): xa [k, m, n], fsq/fd [k, o, n].

    The fused path is a single batched kernel launch (grid over (k, o,
    n_tiles)), not k separate dispatches.  This IS the fleet engine's hot
    path: ``gram_stats`` carries a ``custom_vmap`` rule that lowers the
    vmapped per-tenant call in ``fleet._fleet_fit`` to this variant.
    """
    if producer() == "fused":
        from repro.kernels.rolann_stats import rolann_stats_batched

        return rolann_stats_batched(xa, fsq, fd)
    return einsum_stats(xa, fsq, fd)


# ---------------------------------------------------------------------------
# Accumulating dispatch — the streaming/chunked training path folds each
# sample chunk into running (G, M) accumulators instead of materializing the
# full-sample statistics in one contraction.
# ---------------------------------------------------------------------------

def _gram_stats_acc_unbatched(g, m, xa, fsq, fd, which: str):
    if which == "fused":
        from repro.kernels.rolann_stats import rolann_stats_acc

        return rolann_stats_acc(g, m, xa, fsq, fd)
    dg, dm = einsum_stats(xa, fsq, fd)
    return g + dg, m + dm


@functools.lru_cache(maxsize=None)
def _gram_stats_acc_fn(which: str):
    """``gram_stats_acc`` body with the same custom batching rule as
    ``gram_stats``: vmapping the fold (the fleet engine's tenant axis)
    collapses into ONE tenant-batched accumulating dispatch — for the fused
    producer a single aliased-accumulator kernel launch
    (``rolann_stats_acc_batched``)."""

    @jax.custom_batching.custom_vmap
    def f(g, m, xa, fsq, fd):
        return _gram_stats_acc_unbatched(g, m, xa, fsq, fd, which)

    @f.def_vmap
    def _batched_rule(axis_size, in_batched, g, m, xa, fsq, fd):
        args = [_lift(axis_size, a, b)
                for a, b in zip((g, m, xa, fsq, fd), in_batched, strict=True)]
        return gram_stats_acc_batched(*args), (True, True)

    return f


def gram_stats_acc(
    g: Array, m: Array, xa: Array, fsq: Array, fd: Array
) -> tuple[Array, Array]:
    """Fold one sample chunk into running stats: (g, m) += (G, M) of the chunk.

    g [o, mm, mm], m [o, mm] are the running accumulators (mm = rows of xa);
    xa [mm, n_chunk]; fsq, fd [o, n_chunk].  The fused producer aliases the
    accumulators onto the kernel outputs — one HBM pass per chunk, no
    re-zeroing and no separate add; inside a compiled caller (a scan carry,
    or a streaming step jitted with donated accumulators) the fold reuses
    the running buffers in place.

    Vmapping this fold (the streamed fleet fit does, over the tenant axis)
    dispatches to :func:`gram_stats_acc_batched` via a ``custom_vmap`` rule —
    one batched launch per chunk for the whole fleet.
    """
    return _gram_stats_acc_fn(producer())(g, m, xa, fsq, fd)


def gram_stats_acc_batched(
    g: Array, m: Array, xa: Array, fsq: Array, fd: Array
) -> tuple[Array, Array]:
    """Tenant-batched accumulating fold: g [k, o, mm, mm], xa [k, mm, n]."""
    if producer() == "fused":
        from repro.kernels.rolann_stats import rolann_stats_acc_batched

        return rolann_stats_acc_batched(g, m, xa, fsq, fd)
    dg, dm = einsum_stats(xa, fsq, fd)
    return g + dg, m + dm


# ---------------------------------------------------------------------------
# Fused-chunk dispatch — the WHOLE per-layer chunk fold as one call.  The
# unfused chunked path computes h_c1 = f(W^T h + b) in XLA, materializes it
# to HBM, then calls gram_stats_acc; the fused producer's kernel recomputes
# the activation per output tile in VMEM and folds (G, M) in the same
# launch, eliminating the [m_c1, n] round-trip.  The einsum fallback below
# replicates rolann.accumulate_stats' math exactly (same op order, same
# masking point) so both producers agree within accumulation error.
# ---------------------------------------------------------------------------

def _fused_chunk_targets(h, act):
    """Target transform for ELM-AE chunk folds (targets ARE the layer input):
    mirrors ``rolann._targets`` + the fsq/fd construction in
    ``rolann.accumulate_stats`` — kept in lockstep for bit-compatibility."""
    d = act.clip_to_range(h)
    dbar = act.inv(d)
    fp = act.deriv(dbar)
    fsq = fp * fp
    fd = fsq * dbar
    return fsq, fd


def _fused_chunk_acc_unbatched(g, m, h, w, b, mask, act_name: str, which: str):
    if which == "fused":
        from repro.kernels.rolann_stats import rolann_fused_chunk

        return rolann_fused_chunk(g, m, h, w, b, mask, act_name=act_name)
    from repro.core import activations

    act = activations.get(act_name, invertible_required=True)
    h_c1 = act.fn(w.T @ h + b[:, None])                      # [m_c1, n]
    xa = jnp.concatenate(
        [h_c1, jnp.ones((1, h_c1.shape[1]), h_c1.dtype)], axis=0
    )
    fsq, fd = _fused_chunk_targets(h, act)
    fsq = fsq * mask[None, :]
    fd = fd * mask[None, :]
    dg, dm = einsum_stats(xa, fsq, fd)
    return g + dg, m + dm


@functools.lru_cache(maxsize=None)
def _fused_chunk_fn(act_name: str, which: str):
    """``fused_chunk_acc`` body with the family's custom batching rule:
    vmapping the chunk fold over the fleet's tenant axis collapses into ONE
    tenant-batched dispatch (a single 4-arg-grid kernel launch under the
    fused producer) instead of Pallas' generic batching."""

    @jax.custom_batching.custom_vmap
    def f(g, m, h, w, b, mask):
        return _fused_chunk_acc_unbatched(g, m, h, w, b, mask, act_name, which)

    @f.def_vmap
    def _batched_rule(axis_size, in_batched, g, m, h, w, b, mask):
        args = [
            _lift(axis_size, a, bt)
            for a, bt in zip((g, m, h, w, b, mask), in_batched, strict=True)
        ]
        return fused_chunk_acc_batched(*args, act=act_name), (True, True)

    return f


def fused_chunk_acc(
    g: Array, m: Array, h: Array, w: Array, b: Array,
    mask: Array | None = None, *, act,
) -> tuple[Array, Array]:
    """Fold one streamed chunk's layer stats in ONE dispatch.

    g [o, ma, ma], m [o, ma] running accumulators (o == rows of h, ma ==
    cols of w + 1); h [m_l, n_chunk] the chunk's layer input (ELM-AE targets
    are the input itself); w [m_l, m_c1], b [m_c1] the stage-1 encoder;
    mask [n_chunk] sample weights (None -> all ones).  ``act`` is an
    activation name or ``activations.Activation``; the linear activation has
    a cheaper shared-F closed form in ``rolann.accumulate_stats`` and is
    rejected here.

    Under the fused producer this is one Pallas launch per chunk — the
    activation never materializes to HBM.  Vmapping over a leading tenant
    axis dispatches to :func:`fused_chunk_acc_batched` (one batched launch).
    """
    act_name = act if isinstance(act, str) else act.name
    if act_name == "linear":
        raise ValueError(
            "fused_chunk_acc handles non-linear activations; the linear "
            "layer uses the shared-F path in rolann.accumulate_stats"
        )
    if mask is None:
        mask = jnp.ones((h.shape[1],), h.dtype)
    else:
        mask = jnp.asarray(mask).astype(h.dtype)
    return _fused_chunk_fn(act_name, producer())(g, m, h, w, b, mask)


def fused_chunk_acc_batched(
    g: Array, m: Array, h: Array, w: Array, b: Array,
    mask: Array | None = None, *, act,
) -> tuple[Array, Array]:
    """Tenant-batched fused chunk fold: g [k, o, ma, ma], h [k, m_l, n],
    w [k, m_l, m_c1], b [k, m_c1], mask [k, n] or None — one dispatch for a
    whole fleet's chunk (per-tenant stage-1 parameters included)."""
    act_name = act if isinstance(act, str) else act.name
    if mask is None:
        mask = jnp.ones((h.shape[0], h.shape[2]), h.dtype)
    else:
        mask = jnp.asarray(mask).astype(h.dtype)
    if producer() == "fused":
        from repro.kernels.rolann_stats import rolann_fused_chunk_batched

        return rolann_fused_chunk_batched(g, m, h, w, b, mask,
                                          act_name=act_name)
    from repro.core import activations

    act_obj = activations.get(act_name, invertible_required=True)
    z = jnp.einsum("kim,kin->kmn", w, h) + b[:, :, None]     # [k, m_c1, n]
    h_c1 = act_obj.fn(z)
    ones = jnp.ones((h_c1.shape[0], 1, h_c1.shape[2]), h_c1.dtype)
    xa = jnp.concatenate([h_c1, ones], axis=1)
    fsq, fd = _fused_chunk_targets(h, act_obj)
    fsq = fsq * mask[:, None, :]
    fd = fd * mask[:, None, :]
    dg, dm = einsum_stats(xa, fsq, fd)
    return g + dg, m + dm


__all__ = ["PRODUCERS", "producer", "einsum_stats", "gram_stats",
           "gram_stats_batched", "gram_stats_acc", "gram_stats_acc_batched",
           "fused_chunk_acc", "fused_chunk_acc_batched"]
