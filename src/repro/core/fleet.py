"""Multi-tenant DAEF fleet engine: K independent models in one dispatch.

DAEF's closed-form training is cheap enough to run one model *per tenant*
(edge node, device, user) — the per-device anomaly-detector pattern.  Doing
that with `daef.fit` in a Python loop costs K traces and K dispatches; this
module instead `vmap`s the traceable cores (`daef._fit_core` /
`daef._merge_core`) over a leading tenant axis, so training, scoring and
federated aggregation of a whole fleet are each a single jitted call.

Constraints (by construction of `vmap`):
  * all tenants share ``layer_sizes`` and the other *static* config fields
    (activations, init scheme, method);
  * ``lam_hidden`` / ``lam_last`` / ``seed`` may vary per tenant — they are
    batched scalars;
  * every tenant in one call sees the same number of samples (pad and mask
    via ``fleet_scores``' ``n_valid`` for ragged serving batches).

Data convention matches `daef`: per-tenant data is [features, samples], a
fleet batch is [tenants, features, samples].
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import anomaly, daef, dsvd, elm_ae, rolann

Array = jnp.ndarray


class DAEFFleet(NamedTuple):
    """K trained DAEF models, stacked leaf-wise (leading tenant axis), plus
    the per-tenant hyperparameters needed to merge/update them later."""

    model: daef.DAEFModel   # every leaf has a leading [K] axis
    seeds: Array            # [K] int32 — per-tenant shared-randomness seeds
    lam_hidden: Array       # [K]
    lam_last: Array         # [K]

    @property
    def size(self) -> int:
        return self.seeds.shape[0]


def _per_tenant(value, default, k: int, dtype) -> Array:
    """Broadcast a scalar (or pass through a [K] array) of per-tenant values."""
    arr = jnp.asarray(default if value is None else value, dtype)
    if arr.ndim == 0:
        arr = jnp.broadcast_to(arr, (k,))
    if arr.shape != (k,):
        raise ValueError(f"per-tenant value must be scalar or [K={k}], got {arr.shape}")
    return arr


def _tenant_keys(config: daef.DAEFConfig, seed: Array) -> Array:
    return daef.layer_keys_from_seed(seed, len(config.layer_sizes))


def _prepare_fit(
    config: daef.DAEFConfig, xs, seeds, lam_hidden, lam_last
) -> tuple[Array, Array, Array]:
    """Shared fleet-fit argument validation + per-tenant broadcasting —
    one definition for the vmap (fleet_fit) and mesh-sharded
    (fleet_sharded.sharded_fleet_fit) entry points.  ``xs`` may be a host
    ndarray; only its shape/dtype are consulted."""
    if getattr(xs, "ndim", None) != 3:
        raise ValueError(
            f"fleet data must be [K, m0, n], got {getattr(xs, 'shape', None)}"
        )
    k = xs.shape[0]
    if xs.shape[1] != config.layer_sizes[0]:
        raise ValueError(
            f"input dim {xs.shape[1]} != layer_sizes[0] {config.layer_sizes[0]}"
        )
    return (
        _per_tenant(seeds, config.seed, k, jnp.int32),
        _per_tenant(lam_hidden, config.lam_hidden, k, xs.dtype),
        _per_tenant(lam_last, config.lam_last, k, xs.dtype),
    )


# ---------------------------------------------------------------------------
# jitted fleet kernels (config is static and hashable -> cached per shape)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("config", "n_partitions"))
def _fleet_fit(config, xs, seeds, lam_hidden, lam_last, *, n_partitions=1):
    def one(x, seed, lh, ll):
        with jax.named_scope("forward"):  # the stage-1 randomness
            keys = _tenant_keys(config, seed)
        return daef._fit_core(config, x, keys, lh, ll, n_partitions=n_partitions)

    return jax.vmap(one)(xs, seeds, lam_hidden, lam_last)


@partial(jax.jit, static_argnames=("config", "chunk_samples"))
def _fleet_fit_chunked_kernel(config, xs, seeds, lam_hidden, lam_last, *,
                              chunk_samples):
    """One jitted dispatch streaming a whole fleet: the chunked scan core
    vmapped over tenants — per chunk, every tenant's per-layer stats fold in
    ONE tenant-batched accumulating dispatch (`gram_stats_acc`'s custom_vmap
    rule lowers to `rolann_stats_acc_batched` on the fused backend)."""

    def one(x, seed, lh, ll):
        with jax.named_scope("forward"):  # the stage-1 randomness
            keys = _tenant_keys(config, seed)
        return daef._fit_chunked_core(config, x, keys, lh, ll,
                                      chunk=chunk_samples)

    return jax.vmap(one)(xs, seeds, lam_hidden, lam_last)


@partial(jax.jit, static_argnames=("config",))
def _fleet_predict(config, model, xs):
    return jax.vmap(partial(daef.predict, config))(model, xs)


@partial(jax.jit, static_argnames=("config",))
def _fleet_scores(config, model, xs):
    return jax.vmap(partial(daef.reconstruction_error, config))(model, xs)


@partial(jax.jit, static_argnames=("config",))
def _fleet_merge(config, model_a, model_b, seeds, lam_hidden, lam_last):
    def one(a, b, seed, lh, ll):
        keys = _tenant_keys(config, seed)
        return daef._merge_core(config, a, b, keys, lh, ll)

    return jax.vmap(one)(model_a, model_b, seeds, lam_hidden, lam_last)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def _fit_fleet(
    config: daef.DAEFConfig,
    xs: Array,
    *,
    seeds=None,
    lam_hidden=None,
    lam_last=None,
    n_partitions: int = 1,
) -> DAEFFleet:
    """Train K independent DAEF models in one jitted vmap call (the engine's
    mode="vmap" fit path; `fleet_fit` is its deprecation shim).

    xs: [K, m0, n] — tenant k trains on xs[k].
    seeds / lam_hidden / lam_last: scalar (shared) or [K] (per tenant);
    defaults come from ``config``.
    """
    with obs.span("fit.prepare"):
        call = _fit_fleet_call(config, xs, seeds, lam_hidden, lam_last,
                               n_partitions=n_partitions)
    return call.run()


def _fit_fleet_chunked(
    config: daef.DAEFConfig,
    xs: Array,
    *,
    chunk_samples: int,
    seeds=None,
    lam_hidden=None,
    lam_last=None,
) -> DAEFFleet:
    """Streaming fleet fit (the engine's ``ExecutionPlan(chunk_samples=...)``
    path): K tenants trained by the chunked `lax.scan` core in one jitted
    vmap dispatch — peak activation memory O(K * (m^2 + chunk)) instead of
    O(K * m * n)."""
    with obs.span("fit.prepare"):
        call = _fit_fleet_call(config, xs, seeds, lam_hidden, lam_last,
                               chunk_samples=chunk_samples)
    return call.run()


def _fit_fleet_call(config: daef.DAEFConfig, xs, seeds, lam_hidden, lam_last, *,
                    n_partitions: int = 1,
                    chunk_samples: int | None = None) -> daef.FitCall:
    """The program `_fit_fleet` / `_fit_fleet_chunked` run, and its
    arguments: the one-shot or the chunked core vmapped over tenants."""
    if chunk_samples is not None:
        daef._require_gram(config, "chunked fleet fit")
    seeds, lam_hidden, lam_last = _prepare_fit(
        config, xs, seeds, lam_hidden, lam_last
    )
    args = (config, xs, seeds, lam_hidden, lam_last)
    if chunk_samples is None:
        fn, kw = _fleet_fit, {"n_partitions": n_partitions}
    else:
        fn, kw = _fleet_fit_chunked_kernel, {"chunk_samples": chunk_samples}
    return daef.FitCall(fn, args, kw, daef._place_input, _as_fleet)


def _as_fleet(model: daef.DAEFModel, args: tuple) -> DAEFFleet:
    """The fleet a fit program's output makes, with the per-tenant seeds
    and lambdas it was called with (``args[-3:]``)."""
    seeds, lam_hidden, lam_last = args[-3:]
    return DAEFFleet(model=model, seeds=seeds, lam_hidden=lam_hidden,
                     lam_last=lam_last)


# ---------------------------------------------------------------------------
# Host-streaming fleet fit: fixed-shape [K, m0, chunk] host chunks feed one
# re-traced jitted step per layer with DONATED accumulators (see daef
# "Streaming / chunked training") — device memory never holds the fleet's
# full sample axis.
# ---------------------------------------------------------------------------

@partial(jax.jit, donate_argnums=(0,))
def _fleet_stream_enc_step(g, xs, mask):
    with jax.named_scope("encoder"):
        return g + jax.vmap(dsvd.masked_gram, in_axes=(0, None))(xs, mask)


@partial(jax.jit, static_argnames=("config",), donate_argnums=(1,))
def _fleet_stream_layer_step(config, stats, params, xs, mask):
    weights, biases, w_c1, b_c1 = params  # every leaf leads with [K]
    f_hl, _ = daef._acts(config)

    def one(stats_i, w_i, b_i, wc1_i, bc1_i, x_i):
        h = daef._stream_forward(config, x_i, w_i, b_i)
        return elm_ae.accumulate_layer_stats(
            stats_i, wc1_i, bc1_i, h, f_hl, weights=mask
        )

    return jax.vmap(one)(stats, weights, biases, w_c1, b_c1, xs)


@partial(jax.jit, static_argnames=("config",), donate_argnums=(1,))
def _fleet_stream_last_step(config, stats, params, xs, mask):
    weights, biases = params
    _, f_ll = daef._acts(config)

    def one(stats_i, w_i, b_i, x_i):
        h = daef._stream_forward(config, x_i, w_i, b_i)
        return rolann.accumulate_stats(stats_i, h, x_i, f_ll, weights=mask)

    return jax.vmap(one)(stats, weights, biases, xs)


@partial(jax.jit, static_argnames=("config",))
def _fleet_stream_errors_chunk(config, params, xs):
    return jax.vmap(
        lambda w, b, x: daef._errors_chunk(config, (w, b), x)
    )(*params, xs)


def _fit_fleet_stream(
    config: daef.DAEFConfig,
    batches,
    *,
    seeds=None,
    lam_hidden=None,
    lam_last=None,
    place=None,
    tenants: int | None = None,
) -> DAEFFleet:
    """Streaming fleet fit from a host chunk source of ``[K, m0, chunk]``
    arrays (an iterable, or a zero-arg callable yielding a fresh iterator
    per pass — one pass per layer plus the error pass).

    ``place`` (optional) maps every leading-[K] device input — chunks and
    initial accumulators — onto its placement (the engine passes the tenant
    sharding for mesh plans), so a mesh fleet streams without a replicated
    host staging copy.
    """
    daef._require_gram(config, "streaming fleet fit")
    factory = daef._stream_chunk_source(batches)
    f_hl, f_ll = daef._acts(config)
    sizes = config.layer_sizes
    m0 = sizes[0]
    place = place if place is not None else (lambda a: a)

    def chunks():
        k = tenants
        for x, mask, n_valid in daef._iter_padded_chunks(
            factory, 3, m0, "fleet fit_stream"
        ):
            if k is None:
                k = x.shape[0]
            elif x.shape[0] != k:
                raise ValueError(
                    f"fleet fit_stream: chunks carry {x.shape[0]} tenants "
                    f"but {k} were expected"
                    + ("" if tenants is not None else " (tenant count "
                       "changed mid-stream)")
                )
            yield place(x), mask, n_valid

    # ---- pass 1: encoder Grams ----
    g = None
    n_total = 0
    k = None
    for x, mask, n_valid in chunks():
        if g is None:
            k = x.shape[0]
            g = place(jnp.zeros((k, m0, m0), jnp.asarray(x).dtype))
        g = _fleet_stream_enc_step(g, x, mask)
        n_total += n_valid
    seeds = place(_per_tenant(seeds, config.seed, k, jnp.int32))
    lam_hidden = place(_per_tenant(lam_hidden, config.lam_hidden, k, g.dtype))
    lam_last = place(_per_tenant(lam_last, config.lam_last, k, g.dtype))
    keys = jax.vmap(lambda s: daef.layer_keys_from_seed(s, len(sizes)))(seeds)
    rank = min(m0, n_total)
    with jax.named_scope("encoder"):
        enc = dsvd.truncate(dsvd.gram_to_factors(g), rank)
        w_enc = enc.u[:, :, : config.latent_dim]
    dtype = w_enc.dtype

    weights = [w_enc]
    biases: list[Array] = []
    knowledge: list = []

    # ---- passes 2..L-1: decoder layers ----
    for li in range(2, len(sizes) - 1):
        w_c1, b_c1 = jax.vmap(
            lambda key: elm_ae.stage1(key, sizes[li - 1], sizes[li],
                                      config.init, dtype)
        )(keys[:, li])
        params = (tuple(weights), tuple(biases), w_c1, b_c1)
        stats = place(jax.tree.map(
            lambda leaf: jnp.broadcast_to(leaf, (k, *leaf.shape)),
            rolann.init_stats(sizes[li], sizes[li - 1], f_hl, dtype),
        ))
        for x, mask, _ in chunks():
            stats = _fleet_stream_layer_step(config, stats, params, x, mask)
        w_next, b_next = jax.vmap(
            lambda st, key, lh: elm_ae.layer_from_knowledge(
                st, key, sizes[li - 1], sizes[li], lh, f_hl,
                init=config.init, aux_bias=config.aux_bias, dtype=dtype,
                gram_solver=config.gram_solver,
            )
        )(stats, keys[:, li], lam_hidden)
        weights.append(w_next)
        biases.append(b_next)
        knowledge.append(stats)

    # ---- pass L: last layer ----
    params = (tuple(weights), tuple(biases))
    stats = place(jax.tree.map(
        lambda leaf: jnp.broadcast_to(leaf, (k, *leaf.shape)),
        rolann.init_stats(sizes[-2], m0, f_ll, dtype),
    ))
    for x, mask, _ in chunks():
        stats = _fleet_stream_last_step(config, stats, params, x, mask)
    w_ll, b_ll = jax.vmap(
        lambda st, ll: rolann.solve(st, ll, gram_solver=config.gram_solver)
    )(stats, lam_last)
    weights.append(w_ll)
    biases.append(b_ll)
    knowledge.append(stats)

    # ---- final pass: train errors ----
    params = (tuple(weights), tuple(biases))
    errs = []
    for x, _, n_valid in chunks():
        # np.array (a real copy): zero-copy conversion would pin each
        # chunk's device buffer alive for the whole pass
        errs.append(
            np.array(_fleet_stream_errors_chunk(config, params, x)[:, :n_valid])
        )
    train_errors = jnp.asarray(np.concatenate(errs, axis=1))

    model = daef.DAEFModel(
        weights=tuple(weights),
        biases=tuple(biases),
        encoder_factors=enc,
        layer_knowledge=tuple(knowledge),
        train_errors=place(train_errors),
    )
    return DAEFFleet(model=model, seeds=seeds, lam_hidden=lam_hidden,
                     lam_last=lam_last)


def fleet_fit(
    config: daef.DAEFConfig,
    xs: Array,
    *,
    seeds=None,
    lam_hidden=None,
    lam_last=None,
    n_partitions: int = 1,
) -> DAEFFleet:
    """DEPRECATED — use ``DAEFEngine(config, ExecutionPlan(mode="vmap",
    tenants=K)).fit(xs, ...)`` (`repro.engine`).  Thin shim, identical
    behavior."""
    from repro import engine as _engine

    _engine.deprecation.warn_once(
        "fleet.fleet_fit", "DAEFEngine(config, ExecutionPlan(mode='vmap', "
        "tenants=K)).fit(xs, ...)"
    )
    if getattr(xs, "ndim", None) != 3:
        raise ValueError(
            f"fleet data must be [K, m0, n], got {getattr(xs, 'shape', None)}"
        )
    eng = _engine.DAEFEngine(
        config, _engine.ExecutionPlan(mode="vmap", tenants=int(xs.shape[0]))
    )
    return eng.fit(xs, seeds=seeds, lam_hidden=lam_hidden, lam_last=lam_last,
                   n_partitions=n_partitions)


def fleet_predict(config: daef.DAEFConfig, fleet: DAEFFleet, xs: Array) -> Array:
    """Reconstruct xs [K, m0, n] — tenant k's model reconstructs xs[k]."""
    return _fleet_predict(config, fleet.model, xs)


def fleet_scores(
    config: daef.DAEFConfig,
    fleet: DAEFFleet,
    xs: Array,
    n_valid: Array | None = None,
) -> Array:
    """Per-sample anomaly scores [K, n] in one dispatch.

    ``n_valid`` ([K] ints) masks a padded serving batch: scores of padding
    columns (j >= n_valid[k]) come back as NaN so downstream thresholding
    can never mistake padding for a real sample.
    """
    errs = _fleet_scores(config, fleet.model, xs)
    if n_valid is None:
        return errs
    mask = jnp.arange(xs.shape[-1])[None, :] < jnp.asarray(n_valid)[:, None]
    return jnp.where(mask, errs, jnp.nan)


def _require_concrete(
    fleets: tuple[DAEFFleet, ...],
    op: str,
    remedy: str = "or call fleet_merge_unchecked (no validation) inside "
                  "traced code",
) -> None:
    """The seed/lambda compatibility guards below are *host-side* value
    checks (``jnp.array_equal`` → Python bool); on a tracer that conversion
    surfaces as an inscrutable TracerBoolConversionError deep inside jax.
    Catch it up front and name an op-appropriate escape hatch instead."""
    for fl in fleets:
        if any(isinstance(leaf, jax.core.Tracer)
               for leaf in (fl.seeds, fl.lam_hidden, fl.lam_last)):
            raise ValueError(
                f"{op} validates per-tenant seeds/lambdas with host-side "
                "checks and cannot run under jit/vmap/scan. Validate before "
                f"tracing, {remedy}."
            )


def _check_merge_compat(a: DAEFFleet, b: DAEFFleet, op: str) -> None:
    """Host-side merge-compatibility validation shared by `fleet_merge` and
    the engine's loop-mode merge: equal sizes, shared per-tenant seeds (the
    paper's stage-1 randomness requirement) and matching lambdas."""
    if a.size != b.size:
        raise ValueError(f"fleet sizes differ: {a.size} != {b.size}")
    _require_concrete((a, b), op)
    if not jnp.array_equal(a.seeds, b.seeds):
        raise ValueError(
            "cannot merge fleets trained with different per-tenant seeds: "
            "decoder knowledge is only mergeable under shared stage-1 "
            "randomness (retrain one side with matching seeds)"
        )
    if not (jnp.allclose(a.lam_hidden, b.lam_hidden)
            and jnp.allclose(a.lam_last, b.lam_last)):
        raise ValueError("cannot merge fleets with different per-tenant lambdas")


def fleet_merge(config: daef.DAEFConfig, a: DAEFFleet, b: DAEFFleet) -> DAEFFleet:
    """Pairwise-federated aggregation: tenant k of ``a`` merges with tenant k
    of ``b`` (both must have been trained with the same per-tenant seed —
    the paper's shared-randomness requirement)."""
    _check_merge_compat(a, b, "fleet_merge")
    return fleet_merge_unchecked(config, a, b)


def fleet_merge_unchecked(
    config: daef.DAEFConfig, a: DAEFFleet, b: DAEFFleet
) -> DAEFFleet:
    """`fleet_merge` without the host-side seed/lambda validation — the
    traced-code entry point (the caller asserts shared stage-1 randomness)."""
    return DAEFFleet(
        model=_fleet_merge(config, a.model, b.model, a.seeds, a.lam_hidden,
                           a.lam_last),
        seeds=a.seeds,
        lam_hidden=a.lam_hidden,
        lam_last=a.lam_last,
    )


def fleet_partial_fit(
    config: daef.DAEFConfig, fleet: DAEFFleet, xs_new: Array
) -> DAEFFleet:
    """Incremental learning for every tenant at once: fit the new blocks
    (same seeds, so the stage-1 randomness lines up) and merge."""
    update = _fit_fleet(
        config, xs_new, seeds=fleet.seeds, lam_hidden=fleet.lam_hidden,
        lam_last=fleet.lam_last,
    )
    return fleet_merge(config, fleet, update)


def fleet_merge_pairwise(config: daef.DAEFConfig, fleet: DAEFFleet) -> DAEFFleet:
    """Tree-reduction step: merge tenants (0,1), (2,3), ... into a fleet of
    K//2 models.  Adjacent tenants must share a seed (they are federated
    nodes of the same logical model)."""
    if fleet.size % 2:
        raise ValueError(f"need an even fleet size, got {fleet.size}")
    even = jax.tree.map(lambda leaf: leaf[0::2], fleet)
    odd = jax.tree.map(lambda leaf: leaf[1::2], fleet)
    return fleet_merge(config, even, odd)


def fleet_thresholds(fleet: DAEFFleet, rule: str = "extreme_iqr") -> Array:
    """Per-tenant anomaly thresholds [K] from each model's train errors."""
    return jax.vmap(lambda e: anomaly.threshold(e, rule))(fleet.model.train_errors)


def fleet_classify(scores: Array, mus: Array) -> Array:
    """Flag anomalies per tenant: scores [K, n] vs thresholds [K] -> int32
    [K, n].  NaN scores (serving-batch padding) classify as 0 (normal)."""
    return (scores > mus[:, None]).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Interop with single-model daef
# ---------------------------------------------------------------------------

def fleet_from_models(
    config: daef.DAEFConfig,
    models: list[daef.DAEFModel],
    *,
    seeds=None,
    lam_hidden=None,
    lam_last=None,
) -> DAEFFleet:
    """Stack individually trained `daef.fit` models into a fleet."""
    if not models:
        raise ValueError("empty model list")
    k = len(models)
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *models)
    return DAEFFleet(
        model=stacked,
        seeds=_per_tenant(seeds, config.seed, k, jnp.int32),
        lam_hidden=_per_tenant(lam_hidden, config.lam_hidden, k, jnp.float32),
        lam_last=_per_tenant(lam_last, config.lam_last, k, jnp.float32),
    )


def get_model(fleet: DAEFFleet, i: int) -> daef.DAEFModel:
    """Extract tenant ``i`` as a plain single-model `daef.DAEFModel`."""
    return jax.tree.map(lambda leaf: leaf[i], fleet.model)
