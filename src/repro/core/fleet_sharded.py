"""Mesh-sharded DAEF fleet: K tenant models split across D devices.

The fleet engine (core/fleet.py) made a fleet ONE pytree with a leading
tenant axis; this module shards that axis over a named mesh axis
(``"tenants"``) with ``NamedSharding(P("tenants"))`` on every leaf, so
fleets bigger than one device's memory — or its FLOPs budget — train,
score and serve with K/D tenants per device.  Because every fleet kernel
is a vmap over the tenant axis, placement is the whole story for
``fit`` / ``scores`` / ``partial_fit``: tenants never exchange data, the
jitted kernels compile to per-shard programs with zero collectives, and
``partial_fit`` donates the old fleet's buffers so steady-state serving
holds one fleet in memory, not two.

The one genuinely cross-device operation is federation.
``fleet_merge_tree`` generalizes ``fleet_merge_pairwise`` (host-side
``leaf[0::2]`` slicing, one round) to arbitrary power-of-two group
sizes, run entirely on the mesh as a ``shard_map`` tree reduction:

* groups that live inside one shard reduce with vmapped pairwise
  knowledge merges (log2 rounds of strided local slicing — device-side);
* groups that span shards reduce with a ``lax.ppermute`` butterfly:
  round r exchanges models between devices ``d`` and ``d ^ 2^r``, each
  side merging (lower-indexed block first, so the result matches the
  sequential left-to-right ``daef.merge_models`` reduction order);
* weights are re-solved from the merged knowledge once, at the root —
  not once per merge round as a naive loop over `fleet_merge` would.

Works for both knowledge representations.  Under ``method="gram"`` every
merge is a sum (the butterfly is a segmented all-reduce): the encoder
travels as its Gram ``U S^2 U^T`` (`dsvd.factors_to_gram`) and is factored
once per merged state, by one eigh at the root.  Under ``method="svd"``
merges are the paper's concat-SVD (Eq. 2/8), whose U-sign ambiguity is
harmless here: encoder factors are sign-canonicalized and the ROLANN
solve is U-sign-invariant.
"""
from __future__ import annotations

import functools
import warnings
from collections.abc import Callable
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import daef, dsvd, fleet, rolann

Array = jnp.ndarray

TENANT_AXIS = "tenants"


# ---------------------------------------------------------------------------
# Mesh + placement helpers
# ---------------------------------------------------------------------------

def tenant_mesh(n_devices: int | None = None) -> Mesh:
    """A 1-D mesh over ``n_devices`` (default: all) named ``"tenants"``."""
    avail = len(jax.devices())
    n = avail if n_devices is None else n_devices
    if not 1 <= n <= avail:
        raise ValueError(f"need 1 <= n_devices <= {avail}, got {n}")
    return jax.make_mesh((n,), (TENANT_AXIS,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def tenant_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis-sharded placement: P("tenants") splits dim 0, replicates
    the rest — valid for every DAEFFleet leaf and every [K, ...] batch."""
    return NamedSharding(mesh, P(TENANT_AXIS))


def _check_divisible(k: int, mesh: Mesh, what: str) -> None:
    d = mesh.shape[TENANT_AXIS]
    if k % d:
        raise ValueError(
            f"{what}: tenant count {k} must divide evenly over the "
            f"{d}-device '{TENANT_AXIS}' mesh axis (pad the fleet or "
            f"resize the mesh)"
        )


def shard_fleet(fl: fleet.DAEFFleet, mesh: Mesh) -> fleet.DAEFFleet:
    """Place every fleet leaf with NamedSharding(P("tenants")).

    The transfer is sharding-directed: each device receives only its K/D
    tenant slice, there is no replicated staging copy.  A fleet of
    ``jax.ShapeDtypeStruct``s comes back with the sharding on each leaf.
    """
    _check_divisible(fl.size, mesh, "shard_fleet")
    spec = tenant_sharding(mesh)
    return jax.tree.map(lambda leaf: daef.put(leaf, spec), fl)


def shard_batch(xs, mesh: Mesh) -> Array:
    """Place a [K, ...] tenant batch (host array ok) sharded over tenants.

    This is how ragged padded serving batches go on mesh: the host-built
    padded ndarray is handed to ``device_put`` with the target sharding, so
    each device pulls exactly its shard — never a full-batch host copy per
    device.
    """
    xs = np.asarray(xs) if not isinstance(xs, jax.Array) else xs
    _check_divisible(xs.shape[0], mesh, "shard_batch")
    return jax.device_put(xs, tenant_sharding(mesh))


# ---------------------------------------------------------------------------
# Sharded fit / scores / partial_fit — placement + the existing vmap kernels
# ---------------------------------------------------------------------------

def _fit_sharded(
    config: daef.DAEFConfig,
    xs,
    mesh: Mesh,
    *,
    seeds=None,
    lam_hidden=None,
    lam_last=None,
    n_partitions: int = 1,
    chunk_samples: int | None = None,
) -> fleet.DAEFFleet:
    """The vmapped fleet fit with the tenant axis sharded over ``mesh`` —
    the engine's mode="mesh" fit path (`sharded_fleet_fit` is its
    deprecation shim).

    The vmap-batched fit kernel has no cross-tenant data flow, so XLA
    compiles it into independent per-shard programs; the returned fleet's
    leaves stay sharded over tenants.  With ``chunk_samples`` the per-shard
    program is the chunked-scan streaming core (bounded activation memory
    per device) instead of the one-shot fit.
    """
    with obs.span("fit.prepare"):
        call = _fit_sharded_call(config, xs, mesh, seeds, lam_hidden, lam_last,
                                 n_partitions=n_partitions,
                                 chunk_samples=chunk_samples)
    return call.run()


def _fit_sharded_call(config: daef.DAEFConfig, xs, mesh: Mesh, seeds, lam_hidden,
                      lam_last, *, n_partitions: int = 1,
                      chunk_samples: int | None = None) -> daef.FitCall:
    """The per-shard program `_fit_sharded` runs, and its arguments; every
    argument is placed sharded over tenants."""
    seeds, lam_hidden, lam_last = fleet._prepare_fit(
        config, xs, seeds, lam_hidden, lam_last
    )
    _check_divisible(xs.shape[0], mesh, "shard_batch")
    spec = tenant_sharding(mesh)
    if chunk_samples is not None:
        fit = _per_shard(fleet._fleet_fit_chunked_kernel, config, mesh, 4,
                         chunk_samples=chunk_samples)
    else:
        fit = _per_shard(fleet._fleet_fit, config, mesh, 4,
                         n_partitions=n_partitions)
    return daef.FitCall(fit, (xs, seeds, lam_hidden, lam_last), {},
                        lambda args: tuple(daef.put(a, spec) for a in args),
                        fleet._as_fleet)


@functools.lru_cache(maxsize=None)
def _per_shard(kernel, config: daef.DAEFConfig, mesh: Mesh, n_args: int,
               donate: tuple[int, ...] = (), **static):
    """``kernel(config, *args, **static)`` run on each device's own tenant
    block, jitted once per (kernel, config, mesh, static).

    The tenant-vmapped kernels have no cross-tenant data flow, but the
    compiler cannot partition a Mosaic kernel inside them by itself: the
    split over the tenant axis is spelled out as a shard_map.  Every
    argument and output leaf leads with the tenant axis, so each stays
    K/D per device (including leaves that are equal across tenants)."""
    spec = P(TENANT_AXIS)
    fn = jax.shard_map(
        partial(kernel, config, **static),
        mesh=mesh,
        in_specs=(spec,) * n_args,
        out_specs=spec,
        axis_names={TENANT_AXIS},
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=donate)


def _fit_sharded_stream(
    config: daef.DAEFConfig,
    batches,
    mesh: Mesh,
    *,
    seeds=None,
    lam_hidden=None,
    lam_last=None,
    tenants: int | None = None,
) -> fleet.DAEFFleet:
    """Host-streaming fleet fit with the tenant axis sharded over ``mesh``:
    every chunk (and the running accumulators) is placed by sharding, so each
    device pulls only its K/D tenant slice of each chunk — the fleet's full
    sample axis never exists on any device."""
    spec = tenant_sharding(mesh)
    return fleet._fit_fleet_stream(
        config, batches, seeds=seeds, lam_hidden=lam_hidden,
        lam_last=lam_last, tenants=tenants,
        place=lambda a: jax.device_put(a, spec),
    )


def sharded_fleet_fit(
    config: daef.DAEFConfig,
    xs,
    mesh: Mesh,
    *,
    seeds=None,
    lam_hidden=None,
    lam_last=None,
    n_partitions: int = 1,
) -> fleet.DAEFFleet:
    """DEPRECATED — use ``DAEFEngine(config, ExecutionPlan(mode="mesh",
    tenants=K), mesh=mesh).fit(xs, ...)`` (`repro.engine`).  Thin shim,
    identical behavior."""
    from repro import engine as _engine

    _engine.deprecation.warn_once(
        "fleet_sharded.sharded_fleet_fit",
        "DAEFEngine(config, ExecutionPlan(mode='mesh', tenants=K), "
        "mesh=mesh).fit(xs, ...)",
    )
    if getattr(xs, "ndim", None) != 3:
        raise ValueError(
            f"fleet data must be [K, m0, n], got {getattr(xs, 'shape', None)}"
        )
    eng = _engine.DAEFEngine(
        config, _engine.ExecutionPlan(mode="mesh", tenants=int(xs.shape[0])),
        mesh=mesh,
    )
    return eng.fit(xs, seeds=seeds, lam_hidden=lam_hidden, lam_last=lam_last,
                   n_partitions=n_partitions)


def sharded_fleet_scores(
    config: daef.DAEFConfig,
    fl: fleet.DAEFFleet,
    xs,
    n_valid=None,
    *,
    mesh: Mesh,
) -> Array:
    """Per-sample anomaly scores [K, n] with tenants sharded over ``mesh``.

    ``xs`` may be a host ndarray (a freshly padded serving batch); it is
    placed by sharding before the single scoring dispatch.  Padding columns
    (j >= n_valid[k]) come back NaN exactly as in `fleet.fleet_scores`.
    """
    xs = shard_batch(xs, mesh)
    if n_valid is not None:
        n_valid = jax.device_put(jnp.asarray(n_valid), tenant_sharding(mesh))
    return fleet.fleet_scores(config, fl, xs, n_valid=n_valid)


def sharded_fleet_predict(
    config: daef.DAEFConfig, fl: fleet.DAEFFleet, xs, *, mesh: Mesh
) -> Array:
    """Reconstruct a tenant batch with the tenant axis sharded over ``mesh``."""
    return fleet.fleet_predict(config, fl, shard_batch(xs, mesh))


@partial(jax.jit, static_argnames=("config", "chunk_samples"),
         donate_argnames=("model",))
def _partial_fit_kernel(config, model, xs_new, seeds, lam_hidden, lam_last,
                        chunk_samples=None):
    def one(m, x, seed, lh, ll):
        keys = daef.layer_keys_from_seed(seed, len(config.layer_sizes))
        if chunk_samples is not None:
            upd = daef._fit_chunked_core(config, x, keys, lh, ll,
                                         chunk=chunk_samples)
        else:
            upd = daef._fit_core(config, x, keys, lh, ll)
        return daef._merge_core(config, m, upd, keys, lh, ll)

    return jax.vmap(one)(model, xs_new, seeds, lam_hidden, lam_last)


def sharded_fleet_partial_fit(
    config: daef.DAEFConfig, fl: fleet.DAEFFleet, xs_new, *, mesh: Mesh,
    chunk_samples: int | None = None,
) -> fleet.DAEFFleet:
    """Incremental update for every tenant, sharded and DONATING.

    Fit-the-block + merge runs as one jitted dispatch whose ``model``
    argument is donated: the same-shape leaves (weights, biases, encoder
    factors, knowledge) update in place on their shards, so steady-state
    incremental serving does not hold two fleets in memory.  The input
    fleet's model buffers are invalid afterwards — use the returned fleet.
    """
    if xs_new.shape[0] != fl.size:
        raise ValueError(f"update batch has {xs_new.shape[0]} tenants, fleet {fl.size}")
    if chunk_samples is not None:
        daef._require_gram(config, "chunked sharded partial_fit")
    with warnings.catch_warnings():
        # train_errors grows on merge (the absorbed block's errors are
        # appended), so that one leaf legitimately cannot reuse its donated
        # buffer; every fixed-shape leaf does.
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        update = _per_shard(_partial_fit_kernel, config, mesh, 5,
                            donate=(0,), chunk_samples=chunk_samples)
        model = update(fl.model, shard_batch(xs_new, mesh), fl.seeds,
                       fl.lam_hidden, fl.lam_last)
    return fleet.DAEFFleet(model=model, seeds=fl.seeds,
                           lam_hidden=fl.lam_hidden, lam_last=fl.lam_last)


# ---------------------------------------------------------------------------
# Cross-device tree-reduce federation
# ---------------------------------------------------------------------------

def _merge_pair_knowledge(config: daef.DAEFConfig):
    """Pairwise merge on (encoder, knowledge) — the fixed-shape part of the
    exchanged state, shared by both tree kernels.  The encoder is as
    `_encoder_state` gives it: a Gram summed under ``method="gram"``,
    factors merged by the concat-SVD (`dsvd.merge_pair`) under "svd"."""
    if config.method == "gram":
        merge_enc, merge = jnp.add, rolann.merge_stats
    else:
        merge_enc, merge = dsvd.merge_pair, rolann.merge_factors

    def pair(a, b):
        enc = merge_enc(a[0], b[0])
        knw = tuple(merge(ka, kb) for ka, kb in zip(a[1], b[1], strict=True))
        return enc, knw

    return pair


def _encoder_state(config: daef.DAEFConfig, enc: dsvd.SvdFactors):
    """The encoder as the butterfly carries it: under ``method="gram"`` its
    Gram ``U S^2 U^T`` [slots, m0, m0], which merges by sums; under "svd"
    its factors.  Named scope ``merge_local``."""
    if config.method != "gram":
        return enc
    with jax.named_scope("merge_local"):
        return dsvd.factors_to_gram(enc)


def _encoder_factors(config: daef.DAEFConfig, enc, rank: int) -> dsvd.SvdFactors:
    """`_encoder_state` undone at the root: under ``method="gram"`` one
    eigh per merged Gram (`dsvd.gram_to_factors`), kept to ``rank``, the
    rank the concat-SVD route would reach (`_merged_rank`)."""
    if config.method != "gram":
        return enc
    return dsvd.truncate(dsvd.gram_to_factors(enc), rank)


def _merged_rank(enc: dsvd.SvdFactors, levels: int) -> int:
    """Columns of the factors ``levels`` concat-SVD merges give: each merge
    of two rank-r factors keeps min(m0, 2 r)."""
    m0, r = enc.u.shape[-2:]
    return min(m0, r << levels)


def _merge_pair_state(config: daef.DAEFConfig):
    """Pairwise merge on the exchanged state (enc factors, knowledge, errors)
    — `daef.merge_knowledge` lifted to the tuple the reduction threads."""
    pair_k = _merge_pair_knowledge(config)

    def pair(a, b):
        enc, knw = pair_k((a[0], a[1]), (b[0], b[1]))
        errs = jnp.concatenate([a[2], b[2]])
        return enc, knw, errs

    return pair


def _butterfly(pair, state, n_dev: int, local_rounds: int, cross_rounds: int,
               carry=()):
    """The tree reduction both tree kernels run inside their shard_map:
    ``pair`` (a vmapped pairwise merge) over ``state``, whose leaves lead
    with this shard's slot axis; ``carry`` leaves are thinned alongside.

    Named scopes: ``merge_local`` (the rounds inside the shard),
    ``merge_exchange`` (each round's ``ppermute`` and the select of the
    lower-indexed block) and ``merge_cross`` (each round's merges after it).
    """
    # Local phase: groups inside this shard reduce by strided slicing —
    # on-device views of the local block, not host gathers of the global
    # sharded array (what fleet_merge_pairwise would do per round).
    with jax.named_scope("merge_local"):
        for _ in range(local_rounds):
            even = jax.tree.map(lambda leaf: leaf[0::2], state)
            odd = jax.tree.map(lambda leaf: leaf[1::2], state)
            state = pair(even, odd)
            carry = jax.tree.map(lambda leaf: leaf[0::2], carry)

    # Cross-device phase: butterfly-reduce groups of 2^cross_rounds adjacent
    # devices.  d ^ shift never leaves an aligned power-of-two block, so the
    # same permutation serves every group at once.
    for r in range(cross_rounds):
        shift = 1 << r
        with jax.named_scope("merge_exchange"):
            perm = [(d, d ^ shift) for d in range(n_dev)]
            other = jax.tree.map(
                lambda leaf: lax.ppermute(leaf, TENANT_AXIS, perm), state
            )
            lower_first = (lax.axis_index(TENANT_AXIS) & shift) == 0
            a = jax.tree.map(lambda x, y: jnp.where(lower_first, x, y), state, other)
            b = jax.tree.map(lambda x, y: jnp.where(lower_first, y, x), state, other)
        with jax.named_scope("merge_cross"):
            state = pair(a, b)
    return state, carry


def _tree_merge(config: daef.DAEFConfig, n_dev: int, local_rounds: int,
                cross_rounds: int, model, seeds, lam_hidden, lam_last):
    """One shard's part of `fleet_merge_tree`: the butterfly over
    (encoder, knowledge, errors), then the encoder factored (under
    ``method="gram"``) and the weights solved once from each merged state
    (named scope ``merge_solve``)."""
    enc = model.encoder_factors
    rank = _merged_rank(enc, local_rounds + cross_rounds)
    state = (_encoder_state(config, enc), model.layer_knowledge,
             model.train_errors)
    (enc, knw, errs), (seeds, lam_hidden, lam_last) = _butterfly(
        jax.vmap(_merge_pair_state(config)), state, n_dev, local_rounds,
        cross_rounds, carry=(seeds, lam_hidden, lam_last),
    )

    def solve(enc, knw, errs, seed, lh, ll):
        keys = daef.layer_keys_from_seed(seed, len(config.layer_sizes))
        return daef._model_from_knowledge(config, enc, knw, keys, lh, ll, errs)

    with jax.named_scope("merge_solve"):
        enc = _encoder_factors(config, enc, rank)
        merged = jax.vmap(solve)(enc, knw, errs, seeds, lam_hidden, lam_last)
    return merged, seeds, lam_hidden, lam_last


@functools.lru_cache(maxsize=None)
def _merge_tree_fn(config: daef.DAEFConfig, mesh: Mesh, local_rounds: int,
                   cross_rounds: int):
    """Build (and cache) the jitted shard_map tree-reduction program; its
    module is named after `_tree_merge`."""
    spec = P(TENANT_AXIS)
    fn = jax.shard_map(
        partial(_tree_merge, config, mesh.shape[TENANT_AXIS], local_rounds,
                cross_rounds),
        mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=spec,
        axis_names={TENANT_AXIS},
        check_vma=False,  # butterfly output is group-replicated, specs say sharded
    )
    return jax.jit(fn)


@partial(jax.jit, static_argnames=("stride",))
def _every_nth(tree, stride: int):
    """Device-side strided dedup of group-replicated leaves."""
    return jax.tree.map(lambda leaf: leaf[0::stride], tree)


def _validate_groups(fl: fleet.DAEFFleet, group_size: int) -> None:
    """Every group of adjacent tenants shares a seed and lambdas: a host
    read of the fleet's seeds and lambdas (nothing to read for a fleet of
    ``jax.ShapeDtypeStruct``s, which only lowers)."""
    fleet._require_concrete(
        (fl,), "fleet_merge_tree",
        remedy="and call it outside jit — it orchestrates device placement "
               "(its shard_map kernel is jitted internally)",
    )
    if any(isinstance(leaf, jax.ShapeDtypeStruct)
           for leaf in (fl.seeds, fl.lam_hidden, fl.lam_last)):
        return
    seeds = np.asarray(fl.seeds).reshape(-1, group_size)
    if not np.array_equal(seeds, np.broadcast_to(seeds[:, :1], seeds.shape)):
        raise ValueError(
            "fleet_merge_tree: every group of "
            f"{group_size} adjacent tenants must share a seed (shared "
            "stage-1 randomness) — got per-group seeds "
            f"{[list(dict.fromkeys(row)) for row in seeds.tolist()][:8]}"
        )
    for name in ("lam_hidden", "lam_last"):
        lam = np.asarray(getattr(fl, name)).reshape(-1, group_size)
        if not np.allclose(lam, lam[:, :1]):
            raise ValueError(
                f"fleet_merge_tree: {name} must match within each merge group"
            )


def _mesh_for_merge(fl: fleet.DAEFFleet, group_size: int) -> Mesh:
    """Prefer the mesh the fleet is already sharded over; otherwise the
    largest all-devices tenant mesh compatible with (K, group_size)."""
    sh = getattr(fl.seeds, "sharding", None)
    if isinstance(sh, NamedSharding) and TENANT_AXIS in sh.mesh.shape:
        return sh.mesh
    k = fl.size
    d = len(jax.devices())
    while d > 1:
        local = k // d if k % d == 0 else 0
        if local and (local % group_size == 0 or group_size % local == 0):
            break
        d //= 2
    return tenant_mesh(max(1, d))


def _exchange_bytes(config: daef.DAEFConfig, model: daef.DAEFModel, n_dev: int,
                    local_rounds: int, cross_rounds: int) -> int:
    """Bytes each device sends in the butterfly of one tree reduce: in cross
    round r its whole state, (encoder, knowledge, errors) per remaining
    slot, the encoder as `_encoder_state` carries it (an m0 x m0 Gram under
    ``method="gram"``) and the error pool of each slot 2^(local_rounds + r)
    sites long."""
    def nbytes(leaf):
        return int(np.prod(leaf.shape[1:])) * np.dtype(leaf.dtype).itemsize

    enc = jax.eval_shape(partial(_encoder_state, config), model.encoder_factors)
    fixed = sum(nbytes(leaf) for leaf in jax.tree.leaves(
        (enc, model.layer_knowledge)))
    errors = nbytes(model.train_errors)
    slots = model.train_errors.shape[0] // n_dev >> local_rounds
    return sum(slots * (fixed + (errors << (local_rounds + r)))
               for r in range(cross_rounds))


def _encoder_factorizations(config: daef.DAEFConfig, tenants: int, n_dev: int,
                            local_rounds: int, cross_rounds: int) -> int:
    """Encoder factorizations one device's tree program runs per call: under
    ``method="gram"`` one eigh per slot left at the root; under "svd" one
    concat-SVD per pairwise merge, local levels and cross levels."""
    local = tenants // n_dev
    roots = local >> local_rounds
    if config.method == "gram":
        return roots
    return local - roots + cross_rounds * roots


class MergeCall(NamedTuple):
    """One tree reduce: the compiled tree program, the fleet it reads, its
    mesh and butterfly depth — what `fleet_merge_tree` dispatches and what
    ``DAEFEngine.lower_reduce`` lowers."""

    fn: Callable
    config: daef.DAEFConfig
    fleet: fleet.DAEFFleet
    mesh: Mesh
    local_rounds: int
    cross_rounds: int

    def _args(self) -> tuple:
        fl = shard_fleet(self.fleet, self.mesh)
        return fl.model, fl.seeds, fl.lam_hidden, fl.lam_last

    def run(self) -> fleet.DAEFFleet:
        """Place the fleet, dispatch the program, keep one model per group:
        the host spans ``reduce.place``, ``reduce.dispatch`` (attributes
        ``exchange_bytes`` and ``encoder_factorizations``) and
        ``reduce.dedup``."""
        with obs.span("reduce.place"):
            args = self._args()
        shape = (self.mesh.shape[TENANT_AXIS], self.local_rounds,
                 self.cross_rounds)
        sent = _exchange_bytes(self.config, self.fleet.model, *shape)
        factored = _encoder_factorizations(self.config, self.fleet.size, *shape)
        with obs.span("reduce.dispatch", exchange_bytes=sent,
                      encoder_factorizations=factored):
            merged = fleet.DAEFFleet(*self.fn(*args))
        if self.cross_rounds:
            # Butterfly results are replicated inside each device group; keep
            # one representative per group (a compiled strided slice, still
            # on-mesh).
            with obs.span("reduce.dedup"):
                merged = _every_nth(merged, 1 << self.cross_rounds)
        return merged

    def lower(self):
        """The lowered tree program ``run`` would dispatch."""
        return self.fn.lower(*self._args())


def _merge_tree_call(config: daef.DAEFConfig, fl: fleet.DAEFFleet,
                     group_size: int, mesh: Mesh | None) -> MergeCall | None:
    """Check a tree reduce of ``fl`` (see `fleet_merge_tree`) and build its
    call; None for ``group_size`` 1, which has nothing to merge."""
    if group_size < 1 or (group_size & (group_size - 1)):
        raise ValueError(
            f"fleet_merge_tree: group_size must be a positive power of two "
            f"(the butterfly exchanges partner d ^ 2^r each round), got "
            f"{group_size} — pad each group to the next power of two with "
            "zero-masked slots and reduce via merge_state_tree, or use "
            "DAEFEngine.reduce with merge='sequential' (any group size)"
        )
    k = fl.size
    if k % group_size:
        raise ValueError(
            f"fleet_merge_tree: group_size {group_size} must divide the "
            f"fleet size {k}"
        )
    _validate_groups(fl, group_size)
    if group_size == 1:
        return None

    if mesh is None:
        mesh = _mesh_for_merge(fl, group_size)
    if TENANT_AXIS not in mesh.shape:
        raise ValueError(f"mesh has no '{TENANT_AXIS}' axis: {mesh.axis_names}")
    d = mesh.shape[TENANT_AXIS]
    _check_divisible(k, mesh, "fleet_merge_tree")
    local_k = k // d
    if group_size <= local_k:
        if local_k % group_size:
            raise ValueError(
                f"per-shard tenant count {local_k} not divisible by "
                f"group_size {group_size}"
            )
        local_rounds, cross_rounds = group_size.bit_length() - 1, 0
    else:
        if group_size % local_k or local_k & (local_k - 1):
            raise ValueError(
                f"group_size {group_size} spans shards but per-shard tenant "
                f"count {local_k} is not a power-of-two divisor of it"
            )
        local_rounds = local_k.bit_length() - 1
        cross_rounds = (group_size // local_k).bit_length() - 1
    return MergeCall(_merge_tree_fn(config, mesh, local_rounds, cross_rounds),
                     config, fl, mesh, local_rounds, cross_rounds)


def fleet_merge_tree(
    config: daef.DAEFConfig,
    fl: fleet.DAEFFleet,
    group_size: int,
    *,
    mesh: Mesh | None = None,
) -> fleet.DAEFFleet:
    """Tree-reduce K site models into K/group_size logical models on-mesh.

    Adjacent blocks of ``group_size`` tenants (a power of two) are federated
    nodes of one logical model: they must share a seed and lambdas, and they
    merge in left-to-right order, so the result matches the sequential
    ``functools.reduce(daef.merge_models, group)`` up to float error —
    with log2(group_size) merge depth and ONE weight solve instead of
    group_size - 1 of each.

    ``mesh`` defaults to the mesh the fleet is sharded over (or the largest
    compatible all-device tenant mesh).  Constraints: K and group_size must
    tile the mesh — K % D == 0 and the per-shard tenant count must divide,
    or be divisible by, group_size (automatic for powers of two).

    ``group_size`` MUST be a power of two — the butterfly pairs rank ``d``
    with ``d ^ 2^r``, which only tiles aligned power-of-two blocks.  All
    constraint violations raise ``ValueError`` here, before the shard_map.
    For other group sizes use ``DAEFEngine.reduce`` with
    ``merge='sequential'``; for a SUBSET of participants pad to a power of
    two and reduce the masked states with `merge_state_tree`.

    Host spans (`repro.obs`): ``reduce.prepare``, then ``reduce.place``,
    ``reduce.dispatch`` and ``reduce.dedup`` (`MergeCall.run`).
    """
    with obs.span("reduce.prepare"):
        call = _merge_tree_call(config, fl, group_size, mesh)
    return fl if call is None else call.run()


# ---------------------------------------------------------------------------
# Masked subset tree-reduce — partial participation on the same butterfly
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _state_tree_fn(config: daef.DAEFConfig, mesh: Mesh, local_rounds: int,
                   cross_rounds: int):
    """Build (and cache) the jitted shard_map state-reduction kernel: the
    `_merge_tree_fn` butterfly over (encoder, knowledge) only, the encoder
    factored once per remaining slot — no per-slot weight solve, no error
    pool (both live with the caller)."""
    n_dev = mesh.shape[TENANT_AXIS]
    pair = jax.vmap(_merge_pair_knowledge(config))

    def body(enc, knowledge):
        rank = _merged_rank(enc, local_rounds + cross_rounds)
        state = (_encoder_state(config, enc), knowledge)
        enc, knowledge = _butterfly(pair, state, n_dev, local_rounds,
                                    cross_rounds)[0]
        return _encoder_factors(config, enc, rank), knowledge

    spec = P(TENANT_AXIS)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec),
        out_specs=spec,
        axis_names={TENANT_AXIS},
        check_vma=False,  # butterfly output is replicated, specs say sharded
    )
    return jax.jit(fn)


def merge_state_tree(
    config: daef.DAEFConfig,
    enc: dsvd.SvdFactors,
    knowledge: tuple,
    mask,
    *,
    mesh: Mesh | None = None,
) -> tuple[dsvd.SvdFactors, tuple]:
    """Tree-reduce a stacked batch of federated states over a SUBSET mask.

    This is `fleet_merge_tree`'s butterfly generalized to partial
    participation: ``enc`` / ``knowledge`` carry a leading slot axis of S
    stacked site states (S a power of two — pad with arbitrary slots and
    zero their mask entries), and ``mask`` ([S] in {0, 1}) selects who
    participates.  Masked slots are scaled to the merge identity
    (`rolann.mask_knowledge` / zeroed encoder singular values, so a zero
    encoder Gram) BEFORE the reduction, so the fixed-shape butterfly needs
    no data-dependent control flow: excluded sites ride along as no-ops.
    This is how the async `FederationSession` folds whichever sites are
    fresh on a mesh without a participation barrier.

    Requires ``method="gram"`` — factor-form knowledge is rank-ragged across
    sites (r depends on the local sample count) and cannot stack; the host
    paths (`federated.merge_exchange_states`) handle it instead.  Raises
    ``ValueError`` on a non-power-of-two S or an all-zero mask.

    Returns the merged ``(enc_factors, knowledge)`` with the slot axis
    reduced away, the encoder factored by one eigh of the summed Gram.  The
    caller re-solves weights once from the result
    (`daef._model_from_knowledge`).
    """
    if config.method != "gram":
        raise ValueError(
            "merge_state_tree: masked tree reduction stacks site states into "
            "one fixed-shape batch, but method='svd' factor knowledge is "
            "rank-ragged across sites — use the host reduce "
            "(federated.merge_exchange_states) or method='gram'"
        )
    s_count = int(enc.u.shape[0])
    if s_count < 1 or (s_count & (s_count - 1)):
        raise ValueError(
            f"merge_state_tree: slot count must be a positive power of two "
            f"(the butterfly exchanges partner d ^ 2^r each round), got "
            f"{s_count} — pad the batch with zero-masked slots"
        )
    mask = np.asarray(mask)
    if mask.shape != (s_count,):
        raise ValueError(
            f"merge_state_tree: mask must be [{s_count}] (one entry per "
            f"slot), got shape {mask.shape}"
        )
    if not mask.any():
        raise ValueError(
            "merge_state_tree: all slots masked out — nothing to merge "
            "(an async refresh with no fresh sites keeps the previous model)"
        )

    w = jnp.asarray(mask, enc.u.dtype)
    enc = dsvd.SvdFactors(u=enc.u, s=enc.s * w[:, None])
    knowledge = tuple(rolann.mask_knowledge(k, w) for k in knowledge)

    if mesh is None:
        d, avail = 1, len(jax.devices())
        while d * 2 <= avail and s_count % (d * 2) == 0:
            d *= 2
        mesh = tenant_mesh(d)
    if TENANT_AXIS not in mesh.shape:
        raise ValueError(f"mesh has no '{TENANT_AXIS}' axis: {mesh.axis_names}")
    d = mesh.shape[TENANT_AXIS]
    if s_count % d:
        raise ValueError(
            f"merge_state_tree: slot count {s_count} must divide evenly over "
            f"the {d}-device '{TENANT_AXIS}' mesh axis"
        )
    local = s_count // d
    if local & (local - 1) or d & (d - 1):
        raise ValueError(
            f"merge_state_tree: per-device slot count {local} and device "
            f"count {d} must both be powers of two"
        )
    local_rounds = local.bit_length() - 1
    cross_rounds = d.bit_length() - 1

    spec = tenant_sharding(mesh)
    enc = jax.tree.map(lambda leaf: jax.device_put(leaf, spec), enc)
    knowledge = jax.tree.map(lambda leaf: jax.device_put(leaf, spec), knowledge)
    fn = _state_tree_fn(config, mesh, local_rounds, cross_rounds)
    enc_m, knw_m = fn(enc, knowledge)
    # The root state is replicated across the remaining slot axis; keep one.
    return jax.tree.map(lambda leaf: leaf[0], (enc_m, knw_m))


def merge_wire_tree(wires: list) -> list:
    """The butterfly reduction over secagg FIXED-POINT wires, on host.

    Secure-aggregation wires (`repro.privacy.secagg`) are lists of uint64
    leaves whose arithmetic is mod 2^64 — int64 has no device path without
    x64 mode, so the tree strategy for masked exchanges runs the SAME
    distance-doubling partner pairing as `_state_tree_fn`'s butterfly
    (slot d pairs with d ^ 2^r each round) in numpy.  Because modular
    addition is associative and commutative, the result is bit-identical
    to a sequential fold — the pairing only matters so the session's
    merge='tree' plans exercise the butterfly schedule end to end.

    Non-power-of-two wire counts are padded with zero wires (the additive
    identity — the wire-level analogue of `merge_state_tree`'s masked
    slots).
    """
    if not wires:
        raise ValueError("merge_wire_tree: empty wire list")
    n = len(wires)
    size = 1 << max(0, n - 1).bit_length() if n > 1 else 1
    zeros = [np.zeros_like(np.asarray(leaf, np.uint64)) for leaf in wires[0]]
    slots = [
        [np.asarray(leaf, np.uint64) for leaf in w] for w in wires
    ] + [zeros] * (size - n)
    dist = 1
    while dist < size:
        slots = [
            [a + b for a, b in zip(slots[k], slots[k ^ dist], strict=True)]
            for k in range(size)
        ]
        dist *= 2
    return slots[0]
