"""Attention: GQA/MQA, RoPE, qk-norm, sliding windows, chunked-flash, decode.

Three execution paths, all mask-consistent:

* ``attend_full``       — direct einsum softmax (short sequences, smoke tests)
* ``attend_chunked``    — lax.scan over Q and KV blocks with running
                          (max, sum) renormalization — the pure-JAX flash
                          attention used for long prefill so the dry-run never
                          materializes an [S, S] score tensor.  The Pallas TPU
                          kernel (repro.kernels.flash_attention) computes the
                          same thing on-chip; this is its lowering-friendly
                          twin and its oracle.
* ``decode_attend``     — one query token against a static KV cache with a
                          length mask (flash-decoding style when the cache is
                          sharded: XLA turns the masked softmax reductions
                          into partial reductions + all-reduce).

Shapes: x [B, S, d]; caches [B, S_max, H_kv, hd].
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import common
from repro.models import hints

Array = jnp.ndarray
Params = dict[str, Any]

_NEG_INF = -1e30

# Global default for attend_auto's causal block-skip (§Perf-3): opt-in via
# the launcher (--causal-skip) so models need no per-call plumbing.
DEFAULT_CAUSAL_SKIP = False


def init_attention(key, cfg: ArchConfig, dtype) -> Params:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": common.dense_init(ks[0], (d, h * hd), dtype),
        "wk": common.dense_init(ks[1], (d, hkv * hd), dtype),
        "wv": common.dense_init(ks[2], (d, hkv * hd), dtype),
        "wo": common.dense_init(ks[3], (h * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = common.init_rmsnorm(hd, dtype)
        p["k_norm"] = common.init_rmsnorm(hd, dtype)
    return p


def qkv(p: Params, cfg: ArchConfig, x: Array, positions: Array):
    """Project + rope. Returns q [B,S,H,hd], k/v [B,S,Hkv,hd]."""
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = common.rmsnorm(p["q_norm"], q)
        k = common.rmsnorm(p["k_norm"], k)
    if cfg.use_rope:
        q = common.apply_rope(q, positions, cfg.rope_theta)
        k = common.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _group(q: Array, hkv: int) -> Array:
    """[B,S,H,hd] -> [B,S,Hkv,G,hd] with G = H//Hkv query heads per KV head."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, hkv, h // hkv, hd)


def _mask(
    q_pos: Array, k_pos: Array, window: int | None, causal: bool
) -> Array:
    """[*q, *k] boolean mask; True = attend."""
    ok = jnp.ones((q_pos.shape[-1], k_pos.shape[-1]), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    return ok


def attend_full(
    q: Array, k: Array, v: Array, *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
) -> Array:
    """Direct softmax attention. q [B,Sq,H,hd]; k,v [B,Sk,Hkv,hd]."""
    b, sq, h, hd = q.shape
    hkv = k.shape[2]
    qg = _group(q, hkv)
    scale = hd**-0.5
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    q_pos = jnp.arange(sq) + q_offset
    k_pos = jnp.arange(k.shape[1])
    mask = _mask(q_pos, k_pos, window, causal)
    scores = jnp.where(mask[None, None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, h, v.shape[-1])


def _fit_block(s: int, block: int) -> int:
    """Largest divisor of ``s`` that is <= block (handles e.g. 4352 = 2^8*17)."""
    block = min(block, s)
    while s % block:
        block -= 1
    return block


def attend_chunked(
    q: Array, k: Array, v: Array, *,
    causal: bool = True,
    window: int | None = None,
    q_block: int = 2048,
    kv_block: int = 1024,
    q_offset: Array | int = 0,
) -> Array:
    """Flash-style attention via nested lax.scan over Q and KV blocks.

    Peak live score tensor: [B, Hkv, G, q_block, kv_block].
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    hkv = k.shape[2]
    v_dim = v.shape[-1]
    g = h // hkv
    q_block = _fit_block(sq, q_block)
    kv_block = _fit_block(sk, kv_block)
    nq, nk = sq // q_block, sk // kv_block
    scale = hd**-0.5

    qg = _group(q, hkv).reshape(b, nq, q_block, hkv, g, hd).swapaxes(0, 1)
    kb = k.reshape(b, nk, kv_block, hkv, hd).swapaxes(0, 1)
    vb = v.reshape(b, nk, kv_block, hkv, v_dim).swapaxes(0, 1)

    # Pin the model-axis layout of the attention compute: KV heads when they
    # divide the axis, else grouped query heads.  (Head counts that do not
    # divide the model axis go through attend_auto's sequence-parallel
    # shard_map path instead — see below.)  Without a pin, XLA's propagation
    # picks a fragmentary head sharding and replicates most of the compute.
    mesh = hints.active_mesh()
    if mesh is not None:
        choice = hints.pick_divisible(mesh, "model", (3, hkv), (4, g))
        if choice is not None:
            qg = hints.hint(qg, {1: ("pod", "data"), choice: "model"})
            kv_dims = {1: ("pod", "data")}
            if choice == 3:
                kv_dims[3] = "model"
            kb = hints.hint(kb, kv_dims)
            vb = hints.hint(vb, kv_dims)

    def q_step(_, q_blk_idx_and_q):
        qi, qblk = q_blk_idx_and_q  # qi scalar, qblk [B,qb,hkv,g,hd]
        q_pos = q_offset + qi * q_block + jnp.arange(q_block)

        def kv_step(carry, kv):
            m, l, acc = carry
            ki, kblk, vblk = kv
            k_pos = ki * kv_block + jnp.arange(kv_block)
            s_blk = (
                jnp.einsum("bqkgd,bskd->bkgqs", qblk, kblk).astype(jnp.float32)
                * scale
            )
            ok = k_pos[None, :] <= q_pos[:, None] if causal else jnp.ones(
                (q_block, kv_block), bool
            )
            if window is not None:
                ok &= k_pos[None, :] > q_pos[:, None] - window
            s_blk = jnp.where(ok[None, None, None], s_blk, _NEG_INF)
            m_new = jnp.maximum(m, s_blk.max(axis=-1))
            p = jnp.exp(s_blk - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vblk.astype(jnp.float32)
            )
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((b, hkv, g, q_block), _NEG_INF, jnp.float32),
            jnp.zeros((b, hkv, g, q_block), jnp.float32),
            jnp.zeros((b, hkv, g, q_block, v_dim), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(
            kv_step, init, (jnp.arange(nk), kb, vb)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # [b,hkv,g,qb,hd]
        return None, out.transpose(0, 3, 1, 2, 4)      # [b,qb,hkv,g,hd]

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qg))
    out = outs.swapaxes(0, 1).reshape(b, sq, h, v_dim)
    return out.astype(v.dtype)


def attend_chunked_skip(
    q: Array, k: Array, v: Array, *,
    window: int | None = None,
    q_block: int = 2048,
    kv_block: int = 1024,
) -> Array:
    """Causal flash attention that SKIPS fully-masked KV blocks.

    attend_chunked visits all nq*nk blocks and masks — half the score compute
    of a causal prefill is wasted.  Here the (qi, ki) visit list is built
    statically (ki*kv_block <= end of q block; with a window also
    ki upper-bounded), and a single lax.scan walks it, carrying per-q-block
    running (max, sum, acc) in full-sequence buffers updated in place.
    ~2x fewer score FLOPs for causal, more for windowed (§Perf).
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    hkv = k.shape[2]
    v_dim = v.shape[-1]
    g = h // hkv
    q_block = _fit_block(sq, q_block)
    kv_block = _fit_block(sk, kv_block)
    nq, nk = sq // q_block, sk // kv_block
    scale = hd**-0.5

    pairs = [
        (qi, ki)
        for qi in range(nq)
        for ki in range(nk)
        if ki * kv_block <= (qi + 1) * q_block - 1
        and (window is None or (ki + 1) * kv_block > qi * q_block - window + 1)
    ]
    qi_arr = jnp.asarray([p_[0] for p_ in pairs], jnp.int32)
    ki_arr = jnp.asarray([p_[1] for p_ in pairs], jnp.int32)

    qg = _group(q, hkv).reshape(b, nq, q_block, hkv, g, hd).swapaxes(0, 1)
    kb = k.reshape(b, nk, kv_block, hkv, hd).swapaxes(0, 1)
    vb = v.reshape(b, nk, kv_block, hkv, v_dim).swapaxes(0, 1)

    mesh = hints.active_mesh()
    if mesh is not None:
        choice = hints.pick_divisible(mesh, "model", (3, hkv), (4, g))
        if choice is not None:
            qg = hints.hint(qg, {1: ("pod", "data"), choice + 1: "model"})
            kv_dims = {1: ("pod", "data")}
            if choice == 3:
                kv_dims[3] = "model"
            kb = hints.hint(kb, kv_dims)
            vb = hints.hint(vb, kv_dims)

    def body(carry, idx):
        m_all, l_all, acc_all = carry
        qi, ki = idx
        qblk = jax.lax.dynamic_index_in_dim(qg, qi, 0, keepdims=False)
        kblk = jax.lax.dynamic_index_in_dim(kb, ki, 0, keepdims=False)
        vblk = jax.lax.dynamic_index_in_dim(vb, ki, 0, keepdims=False)
        q_pos = qi * q_block + jnp.arange(q_block)
        k_pos = ki * kv_block + jnp.arange(kv_block)
        s_blk = (
            jnp.einsum("bqkgd,bskd->bkgqs", qblk, kblk).astype(jnp.float32)
            * scale
        )
        ok = k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            ok &= k_pos[None, :] > q_pos[:, None] - window
        s_blk = jnp.where(ok[None, None, None], s_blk, _NEG_INF)

        m = jax.lax.dynamic_index_in_dim(m_all, qi, 0, keepdims=False)
        l = jax.lax.dynamic_index_in_dim(l_all, qi, 0, keepdims=False)
        acc = jax.lax.dynamic_index_in_dim(acc_all, qi, 0, keepdims=False)
        m_new = jnp.maximum(m, s_blk.max(axis=-1))
        p_ = jnp.exp(s_blk - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p_.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p_, vblk.astype(jnp.float32)
        )
        m_all = jax.lax.dynamic_update_index_in_dim(m_all, m_new, qi, 0)
        l_all = jax.lax.dynamic_update_index_in_dim(l_all, l_new, qi, 0)
        acc_all = jax.lax.dynamic_update_index_in_dim(acc_all, acc_new, qi, 0)
        return (m_all, l_all, acc_all), None

    init = (
        jnp.full((nq, b, hkv, g, q_block), _NEG_INF, jnp.float32),
        jnp.zeros((nq, b, hkv, g, q_block), jnp.float32),
        jnp.zeros((nq, b, hkv, g, q_block, v_dim), jnp.float32),
    )
    (m_all, l_all, acc_all), _ = jax.lax.scan(body, init, (qi_arr, ki_arr))
    out = acc_all / jnp.maximum(l_all, 1e-30)[..., None]   # [nq,b,hkv,g,qb,vd]
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, v_dim)
    return out.astype(v.dtype)


def attend_auto(
    q: Array, k: Array, v: Array, *,
    causal: bool = True,
    window: int | None = None,
    q_block: int = 2048,
    kv_block: int = 1024,
    causal_skip: bool | None = None,
) -> Array:
    """Chunked flash attention with mesh-aware parallelization strategy.

    * heads divide the model axis  -> head-parallel (Megatron layout), via
      the sharding hints inside attend_chunked;
    * otherwise                    -> sequence-parallel: shard_map splits the
      query sequence over the model axis, every shard attends its stripe
      against the (all-gathered) full K/V with a per-stripe position offset.
      This is what keeps e.g. 12-head qwen2 or 6-head whisper from
      replicating score compute 16x (EXPERIMENTS.md §Perf).
    """
    from jax.sharding import PartitionSpec as P

    mesh = hints.active_mesh()
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    if mesh is None:
        return attend_chunked(
            q, k, v, causal=causal, window=window,
            q_block=q_block, kv_block=kv_block,
        )
    if causal_skip is None:
        causal_skip = DEFAULT_CAUSAL_SKIP
    ext = hints.axis_extent(mesh, "model")
    heads_ok = ext and (hkv % ext == 0 or g % ext == 0)
    if heads_ok and causal and causal_skip:
        # Head-parallel + static q positions -> causal block skip applies.
        # Opt-in: ~-20% prefill compute, but the in-place accumulator
        # updates trade HBM traffic for it (EXPERIMENTS.md §Perf).
        return attend_chunked_skip(
            q, k, v, window=window, q_block=q_block, kv_block=kv_block
        )
    if heads_ok or not ext or s % ext or (s // ext) < 16:
        return attend_chunked(
            q, k, v, causal=causal, window=window,
            q_block=q_block, kv_block=kv_block,
        )

    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    s_local = s // ext

    def stripe(qs, ks, vs):
        off = jax.lax.axis_index("model") * s_local
        return attend_chunked(
            qs, ks, vs, causal=causal, window=window,
            q_block=min(q_block, s_local), kv_block=kv_block,
            q_offset=off,
        )

    b_ok = dp_spec is not None and b % hints.axis_extent(mesh, dp) == 0
    bspec = dp_spec if b_ok else None
    return jax.shard_map(
        stripe,
        mesh=mesh,
        in_specs=(
            P(bspec, "model", None, None),
            P(bspec, None, None, None),
            P(bspec, None, None, None),
        ),
        out_specs=P(bspec, "model", None, None),
        check_vma=False,
    )(q, k, v)


def decode_attend(
    q: Array, k_cache: Array, v_cache: Array, pos: Array, *,
    window: int | None = None,
) -> Array:
    """One-step decode. q [B,1,H,hd]; caches [B,S,Hkv,hd]; pos scalar index of
    the current token (cache positions > pos are masked out)."""
    b, _, h, hd = q.shape
    hkv = k_cache.shape[2]
    qg = _group(q, hkv)[:, 0]  # [B,Hkv,G,hd]
    qg = hints.hint(qg, {0: ("pod", "data"), 1: "model"})
    scale = hd**-0.5
    scores = (
        jnp.einsum("bkgd,bskd->bkgs", qg, k_cache).astype(jnp.float32) * scale
    )
    k_pos = jnp.arange(k_cache.shape[1])
    ok = k_pos <= pos
    if window is not None:
        ok &= k_pos > pos - window
    scores = jnp.where(ok[None, None, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v_cache)
    return out.reshape(b, 1, h, v_cache.shape[-1])


class KVCache(NamedTuple):
    k: Array  # [B, S_max, Hkv, hd]
    v: Array


def update_cache(cache: KVCache, k_new: Array, v_new: Array, pos: Array) -> KVCache:
    """Write one token's k/v at position pos (static cache shape)."""
    k = jax.lax.dynamic_update_slice(cache.k, k_new, (0, pos, 0, 0))
    v = jax.lax.dynamic_update_slice(cache.v, v_new, (0, pos, 0, 0))
    return KVCache(k=k, v=v)


def attention_block(
    p: Params,
    cfg: ArchConfig,
    x: Array,
    *,
    positions: Array | None = None,
    window: int | None = None,
    chunked: bool = False,
    cache: KVCache | None = None,
    cache_pos: Array | None = None,
    write_slot: Array | None = None,
):
    """Full attention sub-block (projections + attend + output projection).

    Training/prefill: cache=None -> returns (out, (k, v)).
    Decode: cache given, x is [B, 1, d] -> returns (out, new_cache).
    ``cache_pos`` is the ABSOLUTE token position (RoPE + validity masking);
    ``write_slot`` is the cache slot to write (defaults to cache_pos; ring
    caches pass pos % window).  Ring caches must pass window=None — the ring
    itself enforces the window.
    """
    b, s, _ = x.shape
    if cache is None:
        pos = positions if positions is not None else jnp.arange(s)
        q, k, v = qkv(p, cfg, x, pos)
        attend = attend_auto if chunked else attend_full
        out = attend(q, k, v, causal=True, window=window)
        return out.reshape(b, s, -1) @ p["wo"], (k, v)

    assert cache_pos is not None
    slot = write_slot if write_slot is not None else cache_pos
    pos = jnp.full((1,), cache_pos, jnp.int32)
    q, k, v = qkv(p, cfg, x, pos)
    new_cache = update_cache(cache, k, v, slot)
    out = decode_attend(q, new_cache.k, new_cache.v, cache_pos, window=window)
    return out.reshape(b, s, -1) @ p["wo"], new_cache
