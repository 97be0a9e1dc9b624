"""MoE language models: qwen2-moe (GQA attention) and deepseek-v2 (MLA).

Same scan-over-layers skeleton as models/transformer.py, with:
  * MoE FFN (models/moe.py) + router load-balance aux loss threaded through
    the scan carry;
  * optional ``first_dense_layers`` whose FFN is a dense SwiGLU of width
    ``d_ff_dense`` (DeepSeek-V2 layer 0) — kept as a separately-stacked scan;
  * MLA attention + latent cache when ``cfg.mla``.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

import os

from repro.configs.base import ArchConfig
from repro.models import attention as attn_mod
from repro.models import common, hints, mla, moe


def _seq_shard() -> bool:
    """§Perf experiment (env-gated; defaults unchanged): shard the residual
    stream's sequence dim over the model axis between blocks (Megatron-SP
    style) — norms/router/expert math are pointwise over S, attention
    gathers.  Read at call time (the ``compile_cache.enable()`` idiom),
    never at import, so tests/serving can flip it per-process;
    callers that jit the forward pass bake the resolved value into that
    trace and pass ``seq_shard=`` explicitly to override per-call."""
    return os.environ.get("REPRO_SEQ_SHARD", "0") == "1"

Array = jnp.ndarray
Params = dict[str, Any]


class MoECaches(NamedTuple):
    """Decode caches for the dense-prefix layers and the MoE layers."""

    dense: Any   # KVCache | MLACache stacked [L_dense, ...] or None
    moe: Any     # KVCache | MLACache stacked [L_moe, ...]


def _init_attn(key, cfg: ArchConfig, dtype):
    return mla.init_mla(key, cfg, dtype) if cfg.mla else attn_mod.init_attention(
        key, cfg, dtype
    )


def _init_layer(key, cfg: ArchConfig, dtype, dense_ffn: bool) -> Params:
    k_attn, k_ffn = jax.random.split(key)
    p = {
        "attn_norm": common.init_norm(cfg.norm, cfg.d_model, dtype),
        "attn": _init_attn(k_attn, cfg, dtype),
        "mlp_norm": common.init_norm(cfg.norm, cfg.d_model, dtype),
    }
    if dense_ffn:
        p["mlp"] = common.init_mlp(
            k_ffn, "swiglu", cfg.d_model, cfg.d_ff_dense or cfg.d_ff, dtype
        )
    else:
        p["moe"] = moe.init_moe_ffn(k_ffn, cfg, dtype)
    return p


def init_params(key, cfg: ArchConfig, dtype=jnp.float32) -> Params:
    k_emb, k_dense, k_moe, k_head = jax.random.split(key, 4)
    n_dense = cfg.first_dense_layers
    n_moe = cfg.n_layers - n_dense
    params: Params = {
        "embed": common.init_embedding(k_emb, cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": common.init_norm(cfg.norm, cfg.d_model, dtype),
        "moe_layers": jax.vmap(lambda k: _init_layer(k, cfg, dtype, False))(
            jax.random.split(k_moe, n_moe)
        ),
    }
    if n_dense:
        params["dense_layers"] = jax.vmap(lambda k: _init_layer(k, cfg, dtype, True))(
            jax.random.split(k_dense, n_dense)
        )
    if not cfg.tie_embeddings:
        params["lm_head"] = common.dense_init(
            k_head, (cfg.d_model, cfg.vocab_size), dtype
        )
    return params


def _attn_fwd(layer: Params, cfg: ArchConfig, h: Array, chunked: bool) -> Array:
    x = common.apply_norm(cfg.norm, layer["attn_norm"], h)
    if cfg.mla:
        out, _ = mla.mla_block(layer["attn"], cfg, x, chunked=chunked)
    else:
        out, _ = attn_mod.attention_block(
            layer["attn"], cfg, x, window=cfg.sliding_window, chunked=chunked
        )
    return out


def forward(
    params: Params,
    cfg: ArchConfig,
    tokens: Array,
    *,
    chunked_attn: bool = False,
    remat: bool = True,
    seq_shard: bool | None = None,
) -> tuple[Array, Array]:
    """Returns (hidden [B,S,d], aux_loss).

    ``seq_shard=None`` resolves ``$REPRO_SEQ_SHARD`` when this forward
    pass runs (or traces) — pass an explicit bool to pin it pre-trace.
    """
    if seq_shard is None:
        seq_shard = _seq_shard()
    h = common.embed(params["embed"], tokens)

    def dense_body(h, layer):
        h = h + _attn_fwd(layer, cfg, h, chunked_attn)
        m = common.mlp(
            layer["mlp"], "swiglu", common.apply_norm(cfg.norm, layer["mlp_norm"], h)
        )
        return h + m, None

    def moe_body(carry, layer):
        h, aux = carry
        h = h + _attn_fwd(layer, cfg, h, chunked_attn)
        if seq_shard:
            h = hints.hint(h, {0: ("pod", "data"), 1: "model"})
        y, aux_l = moe.moe_ffn(
            layer["moe"], cfg, common.apply_norm(cfg.norm, layer["mlp_norm"], h)
        )
        return (h + y, aux + aux_l), None

    maybe_ckpt = jax.checkpoint if remat else (lambda f: f)
    if "dense_layers" in params:
        h, _ = jax.lax.scan(maybe_ckpt(dense_body), h, params["dense_layers"])
    (h, aux), _ = jax.lax.scan(
        maybe_ckpt(moe_body), (h, jnp.zeros((), jnp.float32)), params["moe_layers"]
    )
    return common.apply_norm(cfg.norm, params["final_norm"], h), aux


def lm_loss(
    params: Params,
    cfg: ArchConfig,
    tokens: Array,
    *,
    chunked_attn: bool = False,
    loss_chunk: int = 1024,
) -> Array:
    h, aux = forward(params, cfg, tokens, chunked_attn=chunked_attn)
    h_in, labels = h[:, :-1], tokens[:, 1:]
    mask = jnp.ones_like(labels, jnp.float32)
    w = params["embed"]["table"] if cfg.tie_embeddings else params["lm_head"]
    xent = common.chunked_softmax_xent(
        h_in, labels, mask, w,
        chunk=min(loss_chunk, h_in.shape[1]),
        transpose=cfg.tie_embeddings,
    )
    return xent + cfg.router_aux_coef * aux


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def _init_layer_cache(cfg: ArchConfig, n_layers: int, batch: int, seq: int, dtype):
    if cfg.mla:
        s = min(seq, cfg.sliding_window) if cfg.sliding_window else seq
        return mla.MLACache(
            c_kv=jnp.zeros((n_layers, batch, s, cfg.kv_lora_rank), dtype),
            k_pe=jnp.zeros((n_layers, batch, s, cfg.qk_rope_head_dim), dtype),
        )
    s = min(seq, cfg.sliding_window) if cfg.sliding_window else seq
    shape = (n_layers, batch, s, cfg.n_kv_heads, cfg.head_dim)
    return attn_mod.KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def init_cache(cfg: ArchConfig, batch: int, seq_len: int, dtype) -> MoECaches:
    n_dense = cfg.first_dense_layers
    dense = (
        _init_layer_cache(cfg, n_dense, batch, seq_len, dtype) if n_dense else None
    )
    return MoECaches(
        dense=dense,
        moe=_init_layer_cache(cfg, cfg.n_layers - n_dense, batch, seq_len, dtype),
    )


def _decode_attn(layer, cfg: ArchConfig, h, cache_slice, pos, slot):
    x = common.apply_norm(cfg.norm, layer["attn_norm"], h)
    if cfg.mla:
        out, new_c = mla.mla_block(
            layer["attn"], cfg, x,
            cache=mla.MLACache(*cache_slice), cache_pos=pos, write_slot=slot,
        )
        return out, tuple(new_c)
    out, new_c = attn_mod.attention_block(
        layer["attn"], cfg, x,
        cache=attn_mod.KVCache(*cache_slice), cache_pos=pos, write_slot=slot,
    )
    return out, tuple(new_c)


def decode_step(
    params: Params,
    cfg: ArchConfig,
    caches: MoECaches,
    token: Array,
    pos: Array,
) -> tuple[Array, MoECaches]:
    h = common.embed(params["embed"], token)
    cache_len = (
        caches.moe.c_kv.shape[2] if cfg.mla else caches.moe.k.shape[2]
    )
    slot = pos % cache_len if cfg.sliding_window else pos

    def dense_body(h, xs):
        layer, *cache_slice = xs
        a, new_c = _decode_attn(layer, cfg, h, cache_slice, pos, slot)
        h = h + a
        h = h + common.mlp(
            layer["mlp"], "swiglu", common.apply_norm(cfg.norm, layer["mlp_norm"], h)
        )
        return h, new_c

    def moe_body(h, xs):
        layer, *cache_slice = xs
        a, new_c = _decode_attn(layer, cfg, h, cache_slice, pos, slot)
        h = h + a
        y, _ = moe.moe_ffn(
            layer["moe"], cfg, common.apply_norm(cfg.norm, layer["mlp_norm"], h)
        )
        return h + y, new_c

    new_dense = caches.dense
    if "dense_layers" in params:
        h, new_dense = jax.lax.scan(
            dense_body, h, (params["dense_layers"], *caches.dense)
        )
        new_dense = type(caches.dense)(*new_dense)
    h, new_moe = jax.lax.scan(moe_body, h, (params["moe_layers"], *caches.moe))
    new_moe = type(caches.moe)(*new_moe)

    h = common.apply_norm(cfg.norm, params["final_norm"], h)
    w = params["embed"]["table"] if cfg.tie_embeddings else params["lm_head"]
    logits = common.logits_from_hidden(
        h, params["embed"], None if cfg.tie_embeddings else w
    )
    return logits, MoECaches(dense=new_dense, moe=new_moe)
