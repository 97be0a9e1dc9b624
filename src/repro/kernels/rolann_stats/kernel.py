"""Pallas TPU kernel: fused ROLANN sufficient statistics.

One pass over the sample axis computes, per output neuron o,

    G[o] += (X_tile * fsq[o]) @ X_tile^T        (MXU)
    M[o] += fd[o] @ X_tile^T                    (MXU, rank-1 of the same tile)

instead of three separate HBM passes (scale, Gram matmul, M matvec).  The
sample axis is streamed HBM->VMEM in ``block_n`` tiles; the [m, m]
accumulator lives in VMEM scratch across the sequential ``n`` grid dimension
(arithmetic intensity ~ m FLOPs/byte vs ~1 for the unfused chain).

Grid: (outputs, n_tiles) — n iterates innermost (sequential on TPU), so the
accumulator carries correctly; outputs are independent (parallelizable /
shardable over the ``model`` mesh axis at the ops level).

Block layout (what Mosaic accepts): the last two dimensions of every block
must be multiples of (8, 128) or span the whole array axis.  A per-output
row is therefore never a ``(1, block_n)`` block of an ``[o, n]`` array —
the wrappers below give every per-output operand a unit axis
(``[o, 1, n]`` in, ``[o, 1, m]`` out), so its block is ``(1, 1, block_n)``
/ ``(1, 1, m)`` with the unit axis spanning the array.  Both contractions
are ``A @ B^T`` (contract the lane axis of both operands), so no in-kernel
transpose is needed.  The dots run at the default matmul precision, as the
einsum backend does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import activations

_NT = (((1,), (1,)), ((), ()))   # A @ B^T: contract the lane axes


def _dot_nt(a, b):
    return jax.lax.dot_general(
        a, b, _NT, preferred_element_type=jnp.float32,
    )


def _tile_deltas(x, fsq, fd):
    """This tile's (ΔG [m, m], ΔM [1, m]) for x [m, bn], fsq/fd [1, bn]."""
    return _dot_nt(x * fsq, x), _dot_nt(fd, x)


def _rows(a):
    """[..., o, n] -> [..., o, 1, n]: one unit-axis row per output."""
    return jnp.expand_dims(a, -2)


def _kernel(x_ref, fsq_ref, fd_ref, g_ref, m_ref):
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        m_ref[...] = jnp.zeros_like(m_ref)

    dg, dm = _tile_deltas(x_ref[...], fsq_ref[0], fd_ref[0])
    g_ref[0] += dg
    m_ref[0] += dm


def rolann_stats_kernel(
    xa: jnp.ndarray,       # [m, n]
    fsq: jnp.ndarray,      # [o, n]
    fd: jnp.ndarray,       # [o, n]
    *,
    block_n: int = 512,
    interpret: bool = False,
):
    m, n = xa.shape
    o = fsq.shape[0]
    block_n = min(block_n, n)
    assert n % block_n == 0, (n, block_n)
    n_tiles = n // block_n

    g, mv = pl.pallas_call(
        _kernel,
        grid=(o, n_tiles),
        in_specs=[
            pl.BlockSpec((m, block_n), lambda oi, ni: (0, ni)),
            pl.BlockSpec((1, 1, block_n), lambda oi, ni: (oi, 0, ni)),
            pl.BlockSpec((1, 1, block_n), lambda oi, ni: (oi, 0, ni)),
        ],
        out_specs=[
            pl.BlockSpec((1, m, m), lambda oi, ni: (oi, 0, 0)),
            pl.BlockSpec((1, 1, m), lambda oi, ni: (oi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((o, m, m), jnp.float32),
            jax.ShapeDtypeStruct((o, 1, m), jnp.float32),
        ],
        interpret=interpret,
    )(xa, _rows(fsq), _rows(fd))
    return g, mv[:, 0]


def _kernel_batched(x_ref, fsq_ref, fd_ref, g_ref, m_ref):
    ni = pl.program_id(2)

    @pl.when(ni == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        m_ref[...] = jnp.zeros_like(m_ref)

    dg, dm = _tile_deltas(x_ref[0], fsq_ref[0, 0], fd_ref[0, 0])
    g_ref[0, 0] += dg
    m_ref[0, 0] += dm


def rolann_stats_kernel_batched(
    xa: jnp.ndarray,       # [k, m, n]
    fsq: jnp.ndarray,      # [k, o, n]
    fd: jnp.ndarray,       # [k, o, n]
    *,
    block_n: int = 512,
    interpret: bool = False,
):
    """Tenant-batched variant: one kernel launch over a [k, ...] fleet axis.

    Same accumulator-carry contract as the unbatched kernel with the n grid
    dimension innermost; (k, o) pairs are independent, so the grid can be
    parallelized over both leading dimensions on TPU.
    """
    k, m, n = xa.shape
    o = fsq.shape[1]
    block_n = min(block_n, n)
    assert n % block_n == 0, (n, block_n)
    n_tiles = n // block_n

    g, mv = pl.pallas_call(
        _kernel_batched,
        grid=(k, o, n_tiles),
        in_specs=[
            pl.BlockSpec((1, m, block_n), lambda ki, oi, ni: (ki, 0, ni)),
            pl.BlockSpec((1, 1, 1, block_n), lambda ki, oi, ni: (ki, oi, 0, ni)),
            pl.BlockSpec((1, 1, 1, block_n), lambda ki, oi, ni: (ki, oi, 0, ni)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, m, m), lambda ki, oi, ni: (ki, oi, 0, 0)),
            pl.BlockSpec((1, 1, 1, m), lambda ki, oi, ni: (ki, oi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, o, m, m), jnp.float32),
            jax.ShapeDtypeStruct((k, o, 1, m), jnp.float32),
        ],
        interpret=interpret,
    )(xa, _rows(fsq), _rows(fd))
    return g, mv[:, :, 0]


# ---------------------------------------------------------------------------
# Accumulating variants: chunk k of a streamed fit folds into the running
# (G, M) — the accumulators are INPUTS aliased onto the outputs
# (``input_output_aliases``), so each chunk is one HBM pass with no separate
# XLA add and no re-zeroing of the [o, m, m] buffer.  Value correctness does
# not rely on the aliasing (the kernel explicitly seeds the output block from
# the input refs at the first n tile); aliasing is the memory/bandwidth win.
# ---------------------------------------------------------------------------

def _kernel_acc(g_in_ref, m_in_ref, x_ref, fsq_ref, fd_ref, g_ref, m_ref):
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _seed():
        g_ref[...] = g_in_ref[...]
        m_ref[...] = m_in_ref[...]

    dg, dm = _tile_deltas(x_ref[...], fsq_ref[0], fd_ref[0])
    g_ref[0] += dg
    m_ref[0] += dm


def rolann_stats_kernel_acc(
    g: jnp.ndarray,        # [o, m, m] running Gram accumulator
    mv: jnp.ndarray,       # [o, m]    running M accumulator
    xa: jnp.ndarray,       # [m, n]    this chunk
    fsq: jnp.ndarray,      # [o, n]
    fd: jnp.ndarray,       # [o, n]
    *,
    block_n: int = 512,
    interpret: bool = False,
):
    """Fold one sample chunk into running stats: returns (g + ΔG, mv + ΔM)."""
    m, n = xa.shape
    o = fsq.shape[0]
    block_n = min(block_n, n)
    assert n % block_n == 0, (n, block_n)
    n_tiles = n // block_n

    g, mv = pl.pallas_call(
        _kernel_acc,
        grid=(o, n_tiles),
        in_specs=[
            pl.BlockSpec((1, m, m), lambda oi, ni: (oi, 0, 0)),
            pl.BlockSpec((1, 1, m), lambda oi, ni: (oi, 0, 0)),
            pl.BlockSpec((m, block_n), lambda oi, ni: (0, ni)),
            pl.BlockSpec((1, 1, block_n), lambda oi, ni: (oi, 0, ni)),
            pl.BlockSpec((1, 1, block_n), lambda oi, ni: (oi, 0, ni)),
        ],
        out_specs=[
            pl.BlockSpec((1, m, m), lambda oi, ni: (oi, 0, 0)),
            pl.BlockSpec((1, 1, m), lambda oi, ni: (oi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((o, m, m), jnp.float32),
            jax.ShapeDtypeStruct((o, 1, m), jnp.float32),
        ],
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(g, _rows(mv), xa, _rows(fsq), _rows(fd))
    return g, mv[:, 0]


def _kernel_acc_batched(g_in_ref, m_in_ref, x_ref, fsq_ref, fd_ref, g_ref, m_ref):
    ni = pl.program_id(2)

    @pl.when(ni == 0)
    def _seed():
        g_ref[...] = g_in_ref[...]
        m_ref[...] = m_in_ref[...]

    dg, dm = _tile_deltas(x_ref[0], fsq_ref[0, 0], fd_ref[0, 0])
    g_ref[0, 0] += dg
    m_ref[0, 0] += dm


def rolann_stats_kernel_acc_batched(
    g: jnp.ndarray,        # [k, o, m, m]
    mv: jnp.ndarray,       # [k, o, m]
    xa: jnp.ndarray,       # [k, m, n]
    fsq: jnp.ndarray,      # [k, o, n]
    fd: jnp.ndarray,       # [k, o, n]
    *,
    block_n: int = 512,
    interpret: bool = False,
):
    """Tenant-batched accumulating fold: one launch for a whole fleet chunk."""
    k, m, n = xa.shape
    o = fsq.shape[1]
    block_n = min(block_n, n)
    assert n % block_n == 0, (n, block_n)
    n_tiles = n // block_n

    g, mv = pl.pallas_call(
        _kernel_acc_batched,
        grid=(k, o, n_tiles),
        in_specs=[
            pl.BlockSpec((1, 1, m, m), lambda ki, oi, ni: (ki, oi, 0, 0)),
            pl.BlockSpec((1, 1, 1, m), lambda ki, oi, ni: (ki, oi, 0, 0)),
            pl.BlockSpec((1, m, block_n), lambda ki, oi, ni: (ki, 0, ni)),
            pl.BlockSpec((1, 1, 1, block_n), lambda ki, oi, ni: (ki, oi, 0, ni)),
            pl.BlockSpec((1, 1, 1, block_n), lambda ki, oi, ni: (ki, oi, 0, ni)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, m, m), lambda ki, oi, ni: (ki, oi, 0, 0)),
            pl.BlockSpec((1, 1, 1, m), lambda ki, oi, ni: (ki, oi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, o, m, m), jnp.float32),
            jax.ShapeDtypeStruct((k, o, 1, m), jnp.float32),
        ],
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(g, _rows(mv), xa, _rows(fsq), _rows(fd))
    return g, mv[:, :, 0]


# ---------------------------------------------------------------------------
# Fused-chunk variants: one launch per streamed chunk does the WHOLE per-layer
# fold — the auxiliary stage-1 matmul + activation, the target transform
# (clip -> f^-1 -> f'), the bias-row augmentation AND the (G, M) accumulation.
# The chunk activation h_c1 = f(W_c1^T h + b_c1) lives only in registers/VMEM;
# the unfused path materializes it to HBM between the XLA matmul and the
# stats kernel, paying a [m_c1, n] round-trip per chunk per layer.
#
# The stage-1 weights arrive pre-augmented: ``wt`` = [W_c1 | 0]^T [ma, m_l]
# and ``bt`` = [b_c1; 0] [ma, 1], so ``wt @ h + bt`` is a plain NN matmul
# whose last row is then overwritten with the bias row of ones (an iota
# select — no sublane concatenate inside the kernel).
#
# Cost note: the stage-1 matmul is recomputed once per OUTPUT grid step (the
# target row changes, the activation does not) — o * 2*m_l*m_c1*block_n
# redundant FLOPs per tile.  DAEF layer widths are small (tens), so the fold
# is bandwidth-bound and trading MXU FLOPs for the eliminated HBM round-trip
# is the right side of the roofline; see docs/kernels.md.
# ---------------------------------------------------------------------------

def _fused_chunk_deltas(act, h, wt, bt, d, mask):
    """Shared body of the fused-chunk kernels: stage-1 activation with the
    bias row, target transform, and this tile's (ΔG, ΔM) contribution."""
    z = jax.lax.dot_general(                 # W_c1^T h  (MXU)
        wt, h, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    ) + bt
    row = jax.lax.broadcasted_iota(jnp.int32, z.shape, 0)
    xa = jnp.where(row == z.shape[0] - 1, 1.0, act.fn(z))   # [ma, bn]
    dbar = act.inv(act.clip_to_range(d))     # [1, bn]
    fp = act.deriv(dbar)
    fsq = fp * fp
    fd = fsq * dbar
    return _tile_deltas(xa, fsq * mask, fd * mask)   # padded columns -> 0


def _kernel_fused_chunk(g_in_ref, m_in_ref, h_ref, d_ref, wt_ref, bt_ref,
                        mask_ref, g_ref, m_ref, *, act_name: str):
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _seed():
        g_ref[...] = g_in_ref[...]
        m_ref[...] = m_in_ref[...]

    act = activations.get(act_name, invertible_required=True)
    dg, dm = _fused_chunk_deltas(act, h_ref[...], wt_ref[...], bt_ref[...],
                                 d_ref[0], mask_ref[...])
    g_ref[0] += dg
    m_ref[0] += dm


def _augment_stage1(w, b):
    """(W_c1 [.., m_l, m_c1], b_c1 [.., m_c1, 1]) -> the kernel's
    (wt [.., ma, m_l], bt [.., ma, 1]) with a zero bias row appended."""
    row = [(0, 0)] * (w.ndim - 2)
    wt = jnp.pad(jnp.swapaxes(w, -1, -2), row + [(0, 1), (0, 0)])
    bt = jnp.pad(b, row + [(0, 1), (0, 0)])
    return wt, bt


def rolann_fused_chunk_kernel(
    g: jnp.ndarray,        # [o, ma, ma] running Gram accumulator (ma = m_c1+1)
    mv: jnp.ndarray,       # [o, ma]     running M accumulator
    h: jnp.ndarray,        # [m_l, n]    chunk layer inputs (o == m_l)
    w: jnp.ndarray,        # [m_l, m_c1] stage-1 weights
    b: jnp.ndarray,        # [m_c1, 1]   stage-1 bias (column)
    mask: jnp.ndarray,     # [1, n]      1 for valid sample columns
    *,
    act_name: str,
    block_n: int = 512,
    interpret: bool = False,
):
    """One launch: recompute the chunk activation and fold (g, mv) in place.

    ``h`` is read through TWO block specs — the full [m_l, block] tile feeds
    the stage-1 matmul, and the current output's row (of the unit-axis view
    ``[m_l, 1, n]``) feeds the target transform (ELM-AE reconstructs its own
    input, so targets ARE ``h``).  The accumulators alias onto the outputs
    exactly like ``rolann_stats_kernel_acc``.
    """
    o, ma, _ = g.shape
    m_l, n = h.shape
    block_n = min(block_n, n)
    assert n % block_n == 0, (n, block_n)
    n_tiles = n // block_n
    wt, bt = _augment_stage1(w, b)

    g, mv = pl.pallas_call(
        functools.partial(_kernel_fused_chunk, act_name=act_name),
        grid=(o, n_tiles),
        in_specs=[
            pl.BlockSpec((1, ma, ma), lambda oi, ni: (oi, 0, 0)),
            pl.BlockSpec((1, 1, ma), lambda oi, ni: (oi, 0, 0)),
            pl.BlockSpec((m_l, block_n), lambda oi, ni: (0, ni)),
            pl.BlockSpec((1, 1, block_n), lambda oi, ni: (oi, 0, ni)),
            pl.BlockSpec(wt.shape, lambda oi, ni: (0, 0)),
            pl.BlockSpec(bt.shape, lambda oi, ni: (0, 0)),
            pl.BlockSpec((1, block_n), lambda oi, ni: (0, ni)),
        ],
        out_specs=[
            pl.BlockSpec((1, ma, ma), lambda oi, ni: (oi, 0, 0)),
            pl.BlockSpec((1, 1, ma), lambda oi, ni: (oi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((o, ma, ma), jnp.float32),
            jax.ShapeDtypeStruct((o, 1, ma), jnp.float32),
        ],
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(g, _rows(mv), h, _rows(h), wt, bt, mask)
    return g, mv[:, 0]


def _kernel_fused_chunk_batched(g_in_ref, m_in_ref, h_ref, d_ref, wt_ref,
                                bt_ref, mask_ref, g_ref, m_ref, *,
                                act_name: str):
    ni = pl.program_id(2)

    @pl.when(ni == 0)
    def _seed():
        g_ref[...] = g_in_ref[...]
        m_ref[...] = m_in_ref[...]

    act = activations.get(act_name, invertible_required=True)
    dg, dm = _fused_chunk_deltas(act, h_ref[0], wt_ref[0], bt_ref[0],
                                 d_ref[0, 0], mask_ref[0])
    g_ref[0, 0] += dg
    m_ref[0, 0] += dm


def rolann_fused_chunk_kernel_batched(
    g: jnp.ndarray,        # [k, o, ma, ma]
    mv: jnp.ndarray,       # [k, o, ma]
    h: jnp.ndarray,        # [k, m_l, n]
    w: jnp.ndarray,        # [k, m_l, m_c1]
    b: jnp.ndarray,        # [k, m_c1, 1]
    mask: jnp.ndarray,     # [k, 1, n]
    *,
    act_name: str,
    block_n: int = 512,
    interpret: bool = False,
):
    """Tenant-batched fused chunk fold: one launch for a whole fleet chunk
    (per-tenant stage-1 parameters included) — the ``custom_vmap`` target of
    ``stats_backend.fused_chunk_acc`` under the fleet's tenant vmap."""
    k, o, ma, _ = g.shape
    m_l, n = h.shape[1:]
    block_n = min(block_n, n)
    assert n % block_n == 0, (n, block_n)
    n_tiles = n // block_n
    wt, bt = _augment_stage1(w, b)

    g, mv = pl.pallas_call(
        functools.partial(_kernel_fused_chunk_batched, act_name=act_name),
        grid=(k, o, n_tiles),
        in_specs=[
            pl.BlockSpec((1, 1, ma, ma), lambda ki, oi, ni: (ki, oi, 0, 0)),
            pl.BlockSpec((1, 1, 1, ma), lambda ki, oi, ni: (ki, oi, 0, 0)),
            pl.BlockSpec((1, m_l, block_n), lambda ki, oi, ni: (ki, 0, ni)),
            pl.BlockSpec((1, 1, 1, block_n), lambda ki, oi, ni: (ki, oi, 0, ni)),
            pl.BlockSpec((1, *wt.shape[1:]), lambda ki, oi, ni: (ki, 0, 0)),
            pl.BlockSpec((1, *bt.shape[1:]), lambda ki, oi, ni: (ki, 0, 0)),
            pl.BlockSpec((1, 1, block_n), lambda ki, oi, ni: (ki, 0, ni)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, ma, ma), lambda ki, oi, ni: (ki, oi, 0, 0)),
            pl.BlockSpec((1, 1, 1, ma), lambda ki, oi, ni: (ki, oi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, o, ma, ma), jnp.float32),
            jax.ShapeDtypeStruct((k, o, 1, ma), jnp.float32),
        ],
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(g, _rows(mv), h, _rows(h), wt, bt, mask)
    return g, mv[:, :, 0]
