"""DAEF on a device mesh: federated node == data-parallel shard.

This is the TPU-native mapping of the paper's broker protocol (DESIGN.md §2):
every shard along the data mesh axes holds one partition X^p and plays one
federated node.  The aggregation collective depends on the representation:

* ``method="gram"``  — one ``psum`` of (G, M) per layer (fast path);
* ``method="svd"``   — ``all_gather`` of the local U·S blocks followed by the
  merge SVD at every node (paper-faithful; the broker "send to all" becomes
  the all-gather).

Both run inside a single ``shard_map`` and produce weights bit-identically
replicated across the mesh.  The layer loop is a Python loop: DAEF is
non-iterative and shallow (<= ~8 layers), so unrolling is the right call.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import activations, daef, dsvd, elm_ae, rolann

Array = jnp.ndarray


def _replicated(x: Array, axes) -> Array:
    """Mark a per-shard-identical value as replicated for shard_map's VMA
    check: psum(x)/P == x when every shard holds the same value, and the psum
    resets the varying-axes tracking (the factors are tiny, so the extra
    reduce is noise next to the gather itself)."""
    denom = 1.0
    for ax in axes:
        denom = denom * lax.axis_size(ax)
    return lax.psum(x, axes) / denom


def _gather_merge_svd(us: Array, axes) -> tuple[Array, Array]:
    """all_gather local U*S blocks along their column axis and re-SVD.

    us: [..., m, r] local weighted factors; returns merged (u, s) truncated
    to m columns — the on-mesh version of Eq. (2)/(8).
    """
    col_axis = us.ndim - 1
    gathered = us
    for ax in axes:
        gathered = lax.all_gather(gathered, ax, axis=col_axis, tiled=True)
    u, s, _ = jnp.linalg.svd(gathered, full_matrices=False)
    m = us.shape[-2]
    u, s = u[..., :, :m], s[..., :m]
    # Match the host path (dsvd.merge_factors): without a canonical sign the
    # encoder — which uses U *directly* as weights through a non-odd
    # activation — would be a different (sign-flipped) model on mesh than
    # off mesh.  ROLANN solves are U-sign-invariant, so canonicalizing the
    # per-output factors too is harmless.
    u = (dsvd.canonicalize_signs(u) if u.ndim == 2
         else jax.vmap(dsvd.canonicalize_signs)(u))
    return _replicated(u, tuple(axes)), _replicated(s, tuple(axes))


def _psum(tree, axes):
    for ax in axes:
        tree = lax.psum(tree, ax)
    return tree


def fit_on_mesh(
    config: daef.DAEFConfig,
    x: Array,
    mesh: Mesh,
    *,
    data_axes: Sequence[str] = ("data",),
    local_factorization: str = "gram_eigh",
) -> daef.DAEFModel:
    """DEPRECATED — use ``DAEFEngine(config, ExecutionPlan(mode="mesh",
    mesh_axes=data_axes, local_factorization=...), mesh=mesh).fit(x)``
    (`repro.engine`).  Thin shim, identical behavior."""
    from repro import engine as _engine

    _engine.deprecation.warn_once(
        "sharded.fit_on_mesh",
        "DAEFEngine(config, ExecutionPlan(mode='mesh', mesh_axes=data_axes), "
        "mesh=mesh).fit(x)",
    )
    eng = _engine.DAEFEngine(
        config,
        _engine.ExecutionPlan(
            mode="mesh", mesh_axes=tuple(data_axes),
            local_factorization=local_factorization,
        ),
        mesh=mesh,
    )
    return eng.fit(x)


def _fit_on_mesh(
    config: daef.DAEFConfig,
    x: Array,
    mesh: Mesh,
    *,
    data_axes: Sequence[str] = ("data",),
    local_factorization: str = "gram_eigh",
) -> daef.DAEFModel:
    """Fit DAEF with the sample axis sharded over ``data_axes`` of ``mesh``
    (the engine's data-sharded mode="mesh" path; `fit_on_mesh` is its
    deprecation shim).

    x: [m0, n]; n must divide evenly over the product of the data axes.
    Returns a DAEFModel whose weights are replicated and whose train_errors
    remain sharded over the data axes.
    """
    with obs.span("fit.prepare"):
        call = _fit_on_mesh_call(config, x, mesh, data_axes=data_axes,
                                 local_factorization=local_factorization)
    return call.run()


def _fit_on_mesh_call(config: daef.DAEFConfig, x, mesh: Mesh, *,
                      data_axes: Sequence[str] = ("data",),
                      local_factorization: str = "gram_eigh") -> daef.FitCall:
    """The program `_fit_on_mesh` runs, and its input, placed with the
    sample axis sharded over ``data_axes``."""
    axes = tuple(data_axes)
    fit = _mesh_fit_program(config, mesh, axes, local_factorization)
    spec = NamedSharding(mesh, P(None, axes))
    return daef.FitCall(fit, (x,), {}, lambda args: (daef.put(args[0], spec),),
                        _as_model)


def _as_model(out, args) -> daef.DAEFModel:  # noqa: ARG001
    weights, biases, (enc_u, enc_s), knowledge, errors = out
    return daef.DAEFModel(
        weights=weights,
        biases=biases,
        encoder_factors=dsvd.SvdFactors(u=enc_u, s=enc_s),
        layer_knowledge=knowledge,
        train_errors=errors,
    )


@lru_cache(maxsize=None)
def _mesh_fit_program(config: daef.DAEFConfig, mesh: Mesh, axes: tuple,
                      local_factorization: str):
    """The data-sharded fit as one program, compiled once per (config,
    mesh, axes, factorization).  Dispatched op by op, a TPU would compile
    every small op of the shard_map body on its own."""
    f_hl = activations.get(config.act_hidden, invertible_required=True)
    f_ll = activations.get(config.act_last, invertible_required=True)
    keys = config.layer_keys()
    sizes = config.layer_sizes
    use_gram = config.method == "gram"

    def node(xp: Array):
        # ---------------- encoder ----------------
        with jax.named_scope("encoder"):
            if use_gram:
                g = _psum(dsvd.gram(xp), axes)
                enc_u, enc_s = dsvd.gram_to_factors(g)
            else:
                # Local factors: eigh of the local Gram (default) carries the
                # same U·S message as the paper's direct SVD but avoids its
                # O(m * n_local) right-factor workspace.
                f = (
                    dsvd.gram_to_factors(dsvd.gram(xp))
                    if local_factorization == "gram_eigh"
                    else dsvd.local_svd(xp)
                )
                enc_u, enc_s = _gather_merge_svd(f.u * f.s[None, :], axes)
            w_enc = enc_u[:, : config.latent_dim]
            h = f_hl.fn(w_enc.T @ xp)

        weights = [w_enc]
        biases = []
        knowledge = []

        # ---------------- decoder hidden layers ----------------
        for li in range(2, len(sizes) - 1):
            with jax.named_scope(f"layer{li}"):
                local = elm_ae.layer_knowledge_from_partition(
                    keys[li], h, sizes[li], f_hl,
                    init=config.init, method=config.method,
                    factorization=local_factorization,
                )
                with jax.named_scope("stats"):
                    if use_gram:
                        merged = _psum(local, axes)
                    else:
                        u, s = _gather_merge_svd(local.u * local.s[..., None, :], axes)
                        m_vec = _psum(local.m, axes)
                        merged = rolann.RolannFactors(u=u, s=s, m=m_vec)
                w, b = elm_ae.layer_from_knowledge(
                    merged, keys[li], sizes[li - 1], sizes[li],
                    config.lam_hidden, f_hl,
                    init=config.init, aux_bias=config.aux_bias, dtype=xp.dtype,
                    gram_solver=config.gram_solver,
                )
                with jax.named_scope("forward"):
                    h = f_hl.fn(w.T @ h + b[:, None])
            weights.append(w)
            biases.append(b)
            knowledge.append(merged)

        # ---------------- last layer ----------------
        with jax.named_scope(f"layer{len(sizes) - 1}"):
            if use_gram:
                local = rolann.compute_stats(h, xp, f_ll)
            elif local_factorization == "gram_eigh":
                local = rolann.compute_factors_via_gram(h, xp, f_ll)
            else:
                local = rolann.compute_factors(h, xp, f_ll)
            with jax.named_scope("stats"):
                if use_gram:
                    merged = _psum(local, axes)
                else:
                    u, s = _gather_merge_svd(local.u * local.s[..., None, :], axes)
                    merged = rolann.RolannFactors(u=u, s=s, m=_psum(local.m, axes))
            w_ll, b_ll = rolann.solve(merged, config.lam_last,
                                      gram_solver=config.gram_solver)
        weights.append(w_ll)
        biases.append(b_ll)
        knowledge.append(merged)

        with jax.named_scope("errors"):
            recon = f_ll.fn(w_ll.T @ h + b_ll[:, None])
            errors = daef.sample_mse(recon, xp)
        return (
            tuple(weights),
            tuple(biases),
            (enc_u, enc_s),
            tuple(knowledge),
            errors,
        )

    data_spec = P(None, axes)
    rep = P()
    out_specs = (rep, rep, rep, rep, P(axes))
    # Manual collectives over the data axes only; the model axis stays
    # "auto" so XLA shards the per-output ROLANN solves across it (the
    # paper's per-core output parallelism, TPU-native — DESIGN.md §2).
    return jax.jit(jax.shard_map(
        node,
        mesh=mesh,
        in_specs=(data_spec,),
        out_specs=out_specs,
        axis_names=set(axes),
        check_vma=True,
    ))


def predict_on_mesh(
    config: daef.DAEFConfig,
    model: daef.DAEFModel,
    x: Array,
    mesh: Mesh,
    *,
    data_axes: Sequence[str] = ("data",),
) -> Array:
    """Reconstruction with samples sharded over the data axes (pure pjit)."""
    spec = NamedSharding(mesh, P(None, tuple(data_axes)))
    x = jax.device_put(x, spec)
    return jax.jit(partial(daef.predict, config), static_argnums=())(model, x)
