"""Pluggable backend for the per-output Gram sufficient statistics.

DAEF's training cost is dominated by the per-layer statistics (paper Eq. 6-7
in Gram form, DESIGN.md §1):

    G[o] = Xa · diag(f'²[o]) · Xaᵀ        [o, m, m]
    M[o] = Xa · (f'²[o] ∘ d̄[o])           [o, m]

Every Gram-stats producer in the repo (``rolann.compute_stats``, the ELM-AE
layer trainer, the vmapped fleet kernels and the mesh-sharded paths) routes
through :func:`gram_stats`, which dispatches to one of two backends:

* ``"einsum"`` — three unfused XLA einsums, the seed-state path;
* ``"fused"``  — the Pallas ``rolann_stats`` kernel: one HBM pass streams
  the sample axis through VMEM and feeds both MXU contractions per tile
  (``kernels/rolann_stats``).  On CPU the kernel runs in interpret mode —
  numerically identical, but slower than XLA; select it on CPU only to
  validate parity.  On TPU it is the hot-path win the ROADMAP asks for.
* ``"auto"`` (the default *meta*-backend) — resolves to whichever of the two
  the autotune cache (``kernels/autotune_cache.json``, written by
  ``benchmarks/kernel_autotune.py``) measured faster on the running
  platform, and to ``"einsum"`` on platforms nobody has measured (including
  CPU).  ``"auto"`` never reaches a kernel: :func:`resolve` collapses it to
  a concrete name before any dispatch.

Selection precedence: explicit ``backend=`` argument (or a non-None
``DAEFConfig.stats_backend``) > the ``REPRO_STATS_BACKEND`` environment
variable > ``"auto"``.  Public entry points (``daef.fit``, the fleet and
sharded wrappers, serve/CLI flags) resolve the environment variable *before*
their jitted kernels trace, so the resolved choice is part of every jit
cache key — the env var can never bake a stale backend into a cached trace.

The chunked/streamed training path additionally exposes
:func:`fused_chunk_acc` — the whole per-layer chunk fold (stage-1 matmul +
activation + target transform + (G, M) accumulate) as ONE dispatch, so the
chunk activation never round-trips through HBM on the fused backend.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

#: Concrete backends a kernel can dispatch to.  ``AUTO`` is deliberately NOT
#: in this tuple — it is a meta-value that :func:`resolve` collapses before
#: dispatch, so downstream code (and the batched-dispatch spy tests that
#: iterate BACKENDS) only ever sees concrete names.
BACKENDS = ("einsum", "fused")
AUTO = "auto"
ENV_VAR = "REPRO_STATS_BACKEND"
DEFAULT = AUTO

Array = jnp.ndarray


def _einsum_stats(xa: Array, fsq: Array, fd: Array) -> tuple[Array, Array]:
    """Unfused (G, M); ``...`` is an optional leading tenant axis."""
    g = jnp.einsum("...in,...on,...jn->...oij", xa, fsq, xa)
    m = jnp.einsum("...in,...on->...oi", xa, fd)
    return g, m


def _resolve_auto() -> str:
    """Measured winner for this platform from the committed autotune cache
    (einsum where unmeasured/unknown — see ``autotune.preferred_backend``)."""
    from repro.kernels import autotune

    return autotune.preferred_backend()


def resolve(backend: str | None = None) -> str:
    """Concrete backend name: explicit arg > $REPRO_STATS_BACKEND > "auto".

    ``"auto"`` (the default) consults the autotune cache's measured
    einsum-vs-fused verdict for the running platform; the return value is
    always one of :data:`BACKENDS`.
    """
    if backend is None:
        backend = os.environ.get(ENV_VAR) or DEFAULT
    if backend == AUTO:
        return _resolve_auto()
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown stats backend {backend!r}: choose from "
            f"{(*BACKENDS, AUTO)} (explicitly or via ${ENV_VAR})"
        )
    return backend


def _gram_stats_unbatched(xa: Array, fsq: Array, fd: Array, backend: str):
    if backend == "fused":
        from repro.kernels.rolann_stats import rolann_stats

        return rolann_stats(xa, fsq, fd)
    return _einsum_stats(xa, fsq, fd)


@functools.lru_cache(maxsize=None)
def _gram_stats_fn(backend: str):
    """The per-call ``gram_stats`` body with a custom batching rule: under
    ``vmap`` (the fleet engine's tenant axis) the whole call collapses into
    ONE tenant-batched dispatch — for the fused backend that is a single
    3-D-grid kernel launch (``rolann_stats_batched``) instead of Pallas'
    generic per-tenant batching rule, and for einsum a single ``k``-batched
    contraction."""

    @jax.custom_batching.custom_vmap
    def f(xa, fsq, fd):
        return _gram_stats_unbatched(xa, fsq, fd, backend)

    @f.def_vmap
    def _batched_rule(axis_size, in_batched, xa, fsq, fd):  # noqa: ARG001
        def lift(arg, batched):
            return arg if batched else jnp.broadcast_to(
                arg[None], (axis_size, *arg.shape)
            )

        xa = lift(xa, in_batched[0])
        fsq = lift(fsq, in_batched[1])
        fd = lift(fd, in_batched[2])
        return gram_stats_batched(xa, fsq, fd, backend=backend), (True, True)

    return f


def gram_stats(
    xa: Array, fsq: Array, fd: Array, *, backend: str | None = None
) -> tuple[Array, Array]:
    """(G, M) per-output statistics for xa [m, n], fsq/fd [o, n].

    Both backends accumulate in float32 on the contraction and return the
    promoted input dtype, so they agree within accumulation-order error
    (tests/test_stats_backend.py pins the tolerances).

    Vmapping this function (the fleet engine does, over the tenant axis)
    dispatches to :func:`gram_stats_batched` via a ``custom_vmap`` rule, so
    a whole tenant batch is one batched-stats call — not K per-tenant calls
    batched generically.
    """
    return _gram_stats_fn(resolve(backend))(xa, fsq, fd)


def gram_stats_batched(
    xa: Array, fsq: Array, fd: Array, *, backend: str | None = None
) -> tuple[Array, Array]:
    """Tenant-batched (G, M): xa [k, m, n], fsq/fd [k, o, n].

    The fused path is a single batched kernel launch (grid over (k, o,
    n_tiles)), not k separate dispatches.  This IS the fleet engine's hot
    path: ``gram_stats`` carries a ``custom_vmap`` rule that lowers the
    vmapped per-tenant call in ``fleet._fleet_fit`` to this variant.
    """
    backend = resolve(backend)
    if backend == "fused":
        from repro.kernels.rolann_stats import rolann_stats_batched

        return rolann_stats_batched(xa, fsq, fd)
    return _einsum_stats(xa, fsq, fd)


# ---------------------------------------------------------------------------
# Accumulating dispatch — the streaming/chunked training path folds each
# sample chunk into running (G, M) accumulators instead of materializing the
# full-sample statistics in one contraction.
# ---------------------------------------------------------------------------

def _gram_stats_acc_unbatched(g, m, xa, fsq, fd, backend: str):
    if backend == "fused":
        from repro.kernels.rolann_stats import rolann_stats_acc

        return rolann_stats_acc(g, m, xa, fsq, fd)
    dg, dm = _einsum_stats(xa, fsq, fd)
    return g + dg, m + dm


@functools.lru_cache(maxsize=None)
def _gram_stats_acc_fn(backend: str):
    """``gram_stats_acc`` body with the same custom batching rule as
    ``gram_stats``: vmapping the fold (the fleet engine's tenant axis)
    collapses into ONE tenant-batched accumulating dispatch — for the fused
    backend a single aliased-accumulator kernel launch
    (``rolann_stats_acc_batched``)."""

    @jax.custom_batching.custom_vmap
    def f(g, m, xa, fsq, fd):
        return _gram_stats_acc_unbatched(g, m, xa, fsq, fd, backend)

    @f.def_vmap
    def _batched_rule(axis_size, in_batched, g, m, xa, fsq, fd):  # noqa: ARG001
        def lift(arg, batched):
            return arg if batched else jnp.broadcast_to(
                arg[None], (axis_size, *arg.shape)
            )

        args = [lift(a, b) for a, b in zip((g, m, xa, fsq, fd), in_batched, strict=True)]
        return gram_stats_acc_batched(*args, backend=backend), (True, True)

    return f


def gram_stats_acc(
    g: Array, m: Array, xa: Array, fsq: Array, fd: Array,
    *, backend: str | None = None,
) -> tuple[Array, Array]:
    """Fold one sample chunk into running stats: (g, m) += (G, M) of the chunk.

    g [o, mm, mm], m [o, mm] are the running accumulators (mm = rows of xa);
    xa [mm, n_chunk]; fsq, fd [o, n_chunk].  The fused backend aliases the
    accumulators onto the kernel outputs — one HBM pass per chunk, no
    re-zeroing and no separate add; inside a compiled caller (a scan carry,
    or a streaming step jitted with donated accumulators) the fold reuses
    the running buffers in place.

    Vmapping this fold (the streamed fleet fit does, over the tenant axis)
    dispatches to :func:`gram_stats_acc_batched` via a ``custom_vmap`` rule —
    one batched launch per chunk for the whole fleet.
    """
    return _gram_stats_acc_fn(resolve(backend))(g, m, xa, fsq, fd)


def gram_stats_acc_batched(
    g: Array, m: Array, xa: Array, fsq: Array, fd: Array,
    *, backend: str | None = None,
) -> tuple[Array, Array]:
    """Tenant-batched accumulating fold: g [k, o, mm, mm], xa [k, mm, n]."""
    backend = resolve(backend)
    if backend == "fused":
        from repro.kernels.rolann_stats import rolann_stats_acc_batched

        return rolann_stats_acc_batched(g, m, xa, fsq, fd)
    dg, dm = _einsum_stats(xa, fsq, fd)
    return g + dg, m + dm


# ---------------------------------------------------------------------------
# Fused-chunk dispatch — the WHOLE per-layer chunk fold as one call.  The
# unfused chunked path computes h_c1 = f(W^T h + b) in XLA, materializes it
# to HBM, then calls gram_stats_acc; the fused backend's kernel recomputes
# the activation per output tile in VMEM and folds (G, M) in the same
# launch, eliminating the [m_c1, n] round-trip.  The einsum fallback below
# replicates rolann.accumulate_stats' math exactly (same op order, same
# masking point) so both backends agree within accumulation error.
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


def _fused_chunk_acc_unbatched(g, m, h, w, b, mask, act_name: str,
                               backend: str):
    if backend == "fused":
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
    dg, dm = _einsum_stats(xa, fsq, fd)
    return g + dg, m + dm


@functools.lru_cache(maxsize=None)
def _fused_chunk_fn(act_name: str, backend: str):
    """``fused_chunk_acc`` body with the family's custom batching rule:
    vmapping the chunk fold over the fleet's tenant axis collapses into ONE
    tenant-batched dispatch (a single 4-arg-grid kernel launch on the fused
    backend) instead of Pallas' generic batching."""

    @jax.custom_batching.custom_vmap
    def f(g, m, h, w, b, mask):
        return _fused_chunk_acc_unbatched(g, m, h, w, b, mask, act_name,
                                          backend)

    @f.def_vmap
    def _batched_rule(axis_size, in_batched, g, m, h, w, b, mask):  # noqa: ARG001
        def lift(arg, batched):
            return arg if batched else jnp.broadcast_to(
                arg[None], (axis_size, *arg.shape)
            )

        args = [
            lift(a, bt)
            for a, bt in zip((g, m, h, w, b, mask), in_batched, strict=True)
        ]
        return (
            fused_chunk_acc_batched(*args, act=act_name, backend=backend),
            (True, True),
        )

    return f


def fused_chunk_acc(
    g: Array, m: Array, h: Array, w: Array, b: Array,
    mask: Array | None = None, *, act, backend: str | None = None,
) -> tuple[Array, Array]:
    """Fold one streamed chunk's layer stats in ONE dispatch.

    g [o, ma, ma], m [o, ma] running accumulators (o == rows of h, ma ==
    cols of w + 1); h [m_l, n_chunk] the chunk's layer input (ELM-AE targets
    are the input itself); w [m_l, m_c1], b [m_c1] the stage-1 encoder;
    mask [n_chunk] sample weights (None -> all ones).  ``act`` is an
    activation name or ``activations.Activation``; the linear activation has
    a cheaper shared-F closed form in ``rolann.accumulate_stats`` and is
    rejected here.

    On the fused backend this is one Pallas launch per chunk — the
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
    return _fused_chunk_fn(act_name, resolve(backend))(g, m, h, w, b, mask)


def fused_chunk_acc_batched(
    g: Array, m: Array, h: Array, w: Array, b: Array,
    mask: Array | None = None, *, act, backend: str | None = None,
) -> tuple[Array, Array]:
    """Tenant-batched fused chunk fold: g [k, o, ma, ma], h [k, m_l, n],
    w [k, m_l, m_c1], b [k, m_c1], mask [k, n] or None — one dispatch for a
    whole fleet's chunk (per-tenant stage-1 parameters included)."""
    act_name = act if isinstance(act, str) else act.name
    backend = resolve(backend)
    if mask is None:
        mask = jnp.ones((h.shape[0], h.shape[2]), h.dtype)
    else:
        mask = jnp.asarray(mask).astype(h.dtype)
    if backend == "fused":
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
    dg, dm = _einsum_stats(xa, fsq, fd)
    return g + dg, m + dm


__all__ = ["AUTO", "BACKENDS", "ENV_VAR", "DEFAULT", "resolve", "gram_stats",
           "gram_stats_batched", "gram_stats_acc", "gram_stats_acc_batched",
           "fused_chunk_acc", "fused_chunk_acc_batched"]
