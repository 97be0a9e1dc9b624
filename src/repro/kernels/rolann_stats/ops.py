"""Jit'd wrappers for the fused ROLANN statistics kernel.

On the CPU backend the kernel body runs in interpret mode; on TPU it
compiles to a Mosaic kernel.  ``rolann_stats`` pads the sample axis to the
block size (zero samples contribute nothing to either G or M, so padding is
exact) and short-circuits degenerate shapes (empty sample/feature/output
axes) where there is nothing to fuse.

Dtype contract (matches ``rolann_stats_ref`` up to accumulation error): the
MXU accumulates in float32 (``preferred_element_type``), and the results are
returned in the *promoted input dtype* — bf16 in, bf16 out; f64 in (under
``jax_enable_x64``), f64 out.  The one documented deviation from the oracle
is that f64 inputs still accumulate in f32 inside the kernel, so the fused
backend trades ~1e-7 relative error for the fusion win on x64 runs.

``interpret`` resolution happens *outside* the jitted body: an explicit
argument wins, and ``None`` means "interpret exactly when the default
backend is the CPU".  On an accelerator nothing but an explicit
``interpret=True`` selects the interpreter, so a kernel can never silently
fall back to it there.  The resolved value is part of the jit cache key,
and the backend probe is cached per process.

``block_n`` resolution: ``None`` (the default) asks the shape-keyed
autotuner (`repro.kernels.autotune`) for the measured winner on this
backend, falling back to the static pow2-clamp heuristic on a cache miss.
An explicit ``block_n`` is honoured as requested — and warns if the legacy
[128, 512] clamp would have silently altered it.
"""
from __future__ import annotations

import functools
import warnings
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.autotune import next_pow2  # noqa: F401  (re-export)
from repro.kernels.rolann_stats.kernel import (
    rolann_fused_chunk_kernel,
    rolann_fused_chunk_kernel_batched,
    rolann_stats_kernel,
    rolann_stats_kernel_acc,
    rolann_stats_kernel_acc_batched,
    rolann_stats_kernel_batched,
)
from repro.kernels.rolann_stats.ref import rolann_stats_ref


def _resolve_block_n(n: int, block_n: int) -> int:
    """Clamp an explicitly requested sample-axis block to a sane size.

    The padded block never exceeds 512 (VMEM pressure), never exceeds the
    next power of two of ``n`` (no point padding 130 samples to 512), and
    the clamp window is floored at 128 lanes.  A request the clamp alters
    is WARNED about — user overrides are never silently ignored (pass
    ``block_n=None`` to get the autotuned/heuristic choice instead).
    """
    if block_n < 1:
        raise ValueError(f"block_n must be >= 1, got {block_n}")
    cap = max(128, min(next_pow2(n), 512))
    resolved = min(block_n, cap)
    if resolved != block_n:
        warnings.warn(
            f"explicit block_n={block_n} clipped to {resolved} for n={n} "
            f"(cap = max(128, min(next_pow2(n), 512)) = {cap}); pass "
            "block_n=None for the autotuned choice, or a value within the "
            "cap to silence this",
            RuntimeWarning,
            stacklevel=4,
        )
    return resolved


def _pick_block_n(kind: str, n: int, m: int, o: int,
                  block_n: int | None) -> int:
    """Host-side block resolution (pre-jit, so the result is a static jit
    argument): explicit request (clamped, warned) > autotune cache >
    static heuristic."""
    if block_n is None:
        return autotune.best_block_n(kind, n=n, m=m, o=o)
    return _resolve_block_n(n, block_n)


@functools.lru_cache(maxsize=1)
def _backend_is_cpu() -> bool:
    """One probe per process (``jax.default_backend()`` walks the backend
    registry — too heavy for every op call on a hot streaming path)."""
    return jax.default_backend() == "cpu"


def _resolve_interpret(interpret: bool | None) -> bool:
    """Explicit arg, else interpret iff the default backend is the CPU."""
    if interpret is not None:
        return bool(interpret)
    return _backend_is_cpu()


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def _rolann_stats(xa, fsq, fd, *, block_n: int, interpret: bool):
    m, n = xa.shape
    o = fsq.shape[0]
    out_dtype = jnp.result_type(xa, fsq, fd)
    if n == 0 or m == 0 or o == 0:
        return (jnp.zeros((o, m, m), out_dtype), jnp.zeros((o, m), out_dtype))
    pad = (-n) % block_n
    if pad:
        xa = jnp.pad(xa, ((0, 0), (0, pad)))
        fsq = jnp.pad(fsq, ((0, 0), (0, pad)))
        fd = jnp.pad(fd, ((0, 0), (0, pad)))
    g, mv = rolann_stats_kernel(
        xa.astype(jnp.float32),
        fsq.astype(jnp.float32),
        fd.astype(jnp.float32),
        block_n=block_n,
        interpret=interpret,
    )
    return g.astype(out_dtype), mv.astype(out_dtype)


def rolann_stats(
    xa: jnp.ndarray,
    fsq: jnp.ndarray,
    fd: jnp.ndarray,
    *,
    block_n: int | None = None,
    interpret: bool | None = None,
):
    """Fused (G, M) sufficient statistics.  xa [m, n]; fsq, fd [o, n].

    ``block_n=None`` (default) takes the autotuned block for this shape
    bucket (falling back to the static heuristic on a cache miss).
    """
    m, n = xa.shape
    return _rolann_stats(
        xa, fsq, fd,
        block_n=_pick_block_n("stats", n, m, fsq.shape[0], block_n),
        interpret=_resolve_interpret(interpret),
    )


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def _rolann_stats_batched(xa, fsq, fd, *, block_n: int, interpret: bool):
    k, m, n = xa.shape
    o = fsq.shape[1]
    out_dtype = jnp.result_type(xa, fsq, fd)
    if n == 0 or m == 0 or o == 0 or k == 0:
        return (
            jnp.zeros((k, o, m, m), out_dtype),
            jnp.zeros((k, o, m), out_dtype),
        )
    pad = (-n) % block_n
    if pad:
        xa = jnp.pad(xa, ((0, 0), (0, 0), (0, pad)))
        fsq = jnp.pad(fsq, ((0, 0), (0, 0), (0, pad)))
        fd = jnp.pad(fd, ((0, 0), (0, 0), (0, pad)))
    g, mv = rolann_stats_kernel_batched(
        xa.astype(jnp.float32),
        fsq.astype(jnp.float32),
        fd.astype(jnp.float32),
        block_n=block_n,
        interpret=interpret,
    )
    return g.astype(out_dtype), mv.astype(out_dtype)


def rolann_stats_batched(
    xa: jnp.ndarray,
    fsq: jnp.ndarray,
    fd: jnp.ndarray,
    *,
    block_n: int | None = None,
    interpret: bool | None = None,
):
    """Tenant-batched fused stats: xa [k, m, n]; fsq, fd [k, o, n].

    One kernel launch for a whole tenant batch — the vmap-free entry point
    for callers that hold a leading tenant axis.  The fleet engine's vmapped
    fit reaches this variant automatically: ``stats_backend.gram_stats``
    carries a ``custom_vmap`` rule that rewrites the vmapped per-tenant call
    into one batched launch (instead of Pallas' generic batching rule).
    """
    k, m, n = xa.shape
    return _rolann_stats_batched(
        xa, fsq, fd,
        block_n=_pick_block_n("stats_batched", n, m, fsq.shape[1], block_n),
        interpret=_resolve_interpret(interpret),
    )


# ---------------------------------------------------------------------------
# Accumulating variants — streamed/chunked fits fold each chunk into running
# (G, M) accumulators.  The accumulators are aliased onto the kernel outputs
# (no separate XLA add, no re-zeroing); callers that hold the running stats
# in a scan carry or a donated jit argument reuse the buffer in place.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("block_n", "interpret"))
def _rolann_stats_acc(g, mv, xa, fsq, fd, *, block_n: int, interpret: bool):
    m, n = xa.shape
    o = fsq.shape[0]
    if n == 0 or m == 0 or o == 0:
        return g, mv
    out_dtype = g.dtype
    pad = (-n) % block_n
    if pad:
        xa = jnp.pad(xa, ((0, 0), (0, pad)))
        fsq = jnp.pad(fsq, ((0, 0), (0, pad)))
        fd = jnp.pad(fd, ((0, 0), (0, pad)))
    g, mv = rolann_stats_kernel_acc(
        g.astype(jnp.float32),
        mv.astype(jnp.float32),
        xa.astype(jnp.float32),
        fsq.astype(jnp.float32),
        fd.astype(jnp.float32),
        block_n=block_n,
        interpret=interpret,
    )
    return g.astype(out_dtype), mv.astype(out_dtype)


def rolann_stats_acc(
    g: jnp.ndarray,
    mv: jnp.ndarray,
    xa: jnp.ndarray,
    fsq: jnp.ndarray,
    fd: jnp.ndarray,
    *,
    block_n: int | None = None,
    interpret: bool | None = None,
):
    """Fold one chunk into running stats: (g, mv) += stats(xa, fsq, fd).

    g [o, m, m], mv [o, m]; xa [m, n_chunk]; fsq, fd [o, n_chunk].  The
    kernel aliases the accumulators onto its outputs; inside a compiled
    caller (a scan carry, or a streaming step jitted with donated
    accumulators) the fold is in place — no separate add, no re-zeroing.
    """
    m, n = xa.shape
    return _rolann_stats_acc(
        g, mv, xa, fsq, fd,
        block_n=_pick_block_n("stats_acc", n, m, fsq.shape[0], block_n),
        interpret=_resolve_interpret(interpret),
    )


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def _rolann_stats_acc_batched(g, mv, xa, fsq, fd, *, block_n: int,
                              interpret: bool):
    k, m, n = xa.shape
    o = fsq.shape[1]
    if n == 0 or m == 0 or o == 0 or k == 0:
        return g, mv
    out_dtype = g.dtype
    pad = (-n) % block_n
    if pad:
        xa = jnp.pad(xa, ((0, 0), (0, 0), (0, pad)))
        fsq = jnp.pad(fsq, ((0, 0), (0, 0), (0, pad)))
        fd = jnp.pad(fd, ((0, 0), (0, 0), (0, pad)))
    g, mv = rolann_stats_kernel_acc_batched(
        g.astype(jnp.float32),
        mv.astype(jnp.float32),
        xa.astype(jnp.float32),
        fsq.astype(jnp.float32),
        fd.astype(jnp.float32),
        block_n=block_n,
        interpret=interpret,
    )
    return g.astype(out_dtype), mv.astype(out_dtype)


def rolann_stats_acc_batched(
    g: jnp.ndarray,
    mv: jnp.ndarray,
    xa: jnp.ndarray,
    fsq: jnp.ndarray,
    fd: jnp.ndarray,
    *,
    block_n: int | None = None,
    interpret: bool | None = None,
):
    """Tenant-batched accumulating fold: g [k, o, m, m], xa [k, m, n_chunk].

    One kernel launch folds a whole fleet's chunk into the running per-tenant
    stats — the streamed fleet fit reaches this through the ``custom_vmap``
    rule on ``stats_backend.gram_stats_acc``.
    """
    k, m, n = xa.shape
    return _rolann_stats_acc_batched(
        g, mv, xa, fsq, fd,
        block_n=_pick_block_n("stats_acc_batched", n, m, fsq.shape[1], block_n),
        interpret=_resolve_interpret(interpret),
    )


# ---------------------------------------------------------------------------
# Fused-chunk variants — one launch per streamed chunk that RECOMPUTES the
# layer activation (tile matmul + act) inside the kernel and folds (G, M)
# in-register, so the [m_c1, n] activation never round-trips through HBM
# between the matmul and the accumulate.  ELM-AE targets are the layer input
# itself, so the kernel reads target rows straight out of `h`.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("act_name", "block_n", "interpret"))
def _rolann_fused_chunk(g, mv, h, w, b, mask, *, act_name: str, block_n: int,
                        interpret: bool):
    m_l, n = h.shape
    if n == 0 or m_l == 0 or g.shape[0] == 0:
        return g, mv
    out_dtype = g.dtype
    pad = (-n) % block_n
    if pad:
        # Padded columns carry mask 0, so their fsq/fd contributions vanish
        # exactly — padding never changes the folded stats.
        h = jnp.pad(h, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, pad),))
    g, mv = rolann_fused_chunk_kernel(
        g.astype(jnp.float32),
        mv.astype(jnp.float32),
        h.astype(jnp.float32),
        w.astype(jnp.float32),
        b.astype(jnp.float32).reshape(-1, 1),
        mask.astype(jnp.float32).reshape(1, -1),
        act_name=act_name,
        block_n=block_n,
        interpret=interpret,
    )
    return g.astype(out_dtype), mv.astype(out_dtype)


def rolann_fused_chunk(
    g: jnp.ndarray,
    mv: jnp.ndarray,
    h: jnp.ndarray,
    w: jnp.ndarray,
    b: jnp.ndarray,
    mask: jnp.ndarray | None = None,
    *,
    act_name: str,
    block_n: int | None = None,
    interpret: bool | None = None,
):
    """Fold one streamed chunk into running stats, activation recomputed
    in-kernel.

    g [o, ma, ma], mv [o, ma] with o == m_l (ELM-AE reconstructs its input)
    and ma == m_c1 + 1; h [m_l, n_chunk] is the chunk's layer input;
    w [m_l, m_c1], b [m_c1] are the stage-1 encoder; mask [n_chunk] weights
    samples (None -> all ones; padded tail columns get mask 0 so ragged
    chunks fold exactly).  One Pallas launch per chunk — the [m_c1, n]
    activation lives only in VMEM/registers, never in HBM.
    """
    m_l, n = h.shape
    if mask is None:
        mask = jnp.ones((n,), h.dtype)
    return _rolann_fused_chunk(
        g, mv, h, w, b, mask,
        act_name=act_name,
        block_n=_pick_block_n("fused_chunk", n, m_l, g.shape[0], block_n),
        interpret=_resolve_interpret(interpret),
    )


@partial(jax.jit, static_argnames=("act_name", "block_n", "interpret"))
def _rolann_fused_chunk_batched(g, mv, h, w, b, mask, *, act_name: str,
                                block_n: int, interpret: bool):
    k, m_l, n = h.shape
    if n == 0 or m_l == 0 or k == 0 or g.shape[1] == 0:
        return g, mv
    out_dtype = g.dtype
    pad = (-n) % block_n
    if pad:
        h = jnp.pad(h, ((0, 0), (0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    g, mv = rolann_fused_chunk_kernel_batched(
        g.astype(jnp.float32),
        mv.astype(jnp.float32),
        h.astype(jnp.float32),
        w.astype(jnp.float32),
        b.astype(jnp.float32).reshape(k, -1, 1),
        mask.astype(jnp.float32).reshape(k, 1, -1),
        act_name=act_name,
        block_n=block_n,
        interpret=interpret,
    )
    return g.astype(out_dtype), mv.astype(out_dtype)


def rolann_fused_chunk_batched(
    g: jnp.ndarray,
    mv: jnp.ndarray,
    h: jnp.ndarray,
    w: jnp.ndarray,
    b: jnp.ndarray,
    mask: jnp.ndarray | None = None,
    *,
    act_name: str,
    block_n: int | None = None,
    interpret: bool | None = None,
):
    """Tenant-batched fused-chunk fold: g [k, o, ma, ma], h [k, m_l, n_chunk],
    w [k, m_l, m_c1], b [k, m_c1], mask [k, n_chunk] or None.

    One launch folds a whole fleet's chunk — the streamed fleet fit reaches
    this through the ``custom_vmap`` rule on ``stats_backend.fused_chunk_acc``.
    """
    k, m_l, n = h.shape
    if mask is None:
        mask = jnp.ones((k, n), h.dtype)
    return _rolann_fused_chunk_batched(
        g, mv, h, w, b, mask,
        act_name=act_name,
        block_n=_pick_block_n("fused_chunk_batched", n, m_l, g.shape[1],
                              block_n),
        interpret=_resolve_interpret(interpret),
    )


__all__ = [
    "rolann_fused_chunk",
    "rolann_fused_chunk_batched",
    "rolann_stats",
    "rolann_stats_acc",
    "rolann_stats_acc_batched",
    "rolann_stats_batched",
    "rolann_stats_ref",
    "next_pow2",
]
