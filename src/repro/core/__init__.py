"""DAEF core — the paper's contribution (non-iterative deep autoencoder).

Public API:
  activations  — f / f' / f^-1 bundles used by ROLANN
  rolann       — closed-form one-layer solver + incremental merge
  chol         — the decoder's Cholesky solves: a lane-batched kernel for
                 stacks of small systems, XLA's cholesky otherwise
  dsvd         — distributed truncated SVD (encoder)
  eigh         — the encoder's eigh: lane-batched Jacobi for stacks of
                 small Grams, jnp.linalg.eigh otherwise
  elm_ae       — auxiliary-network decoder-layer trainer (TLD, Alg. 2)
  daef         — DAEFConfig / fit / predict / merge_models / partial_fit
  anomaly      — reconstruction-error thresholds + metrics
  federated    — node simulation: broker protocol + layer-synchronized fit
  sharded      — shard_map on-mesh DAEF (federated node == data shard)
  fleet        — multi-tenant engine: K models per vmap dispatch
  fleet_sharded— fleet with the tenant axis sharded over a device mesh,
                 incl. the cross-device tree-reduce federation

The unified engine (``repro.engine``): client code should not pick between
these execution paths by importing different modules — construct a
``DAEFEngine`` from a ``DAEFConfig`` plus a declarative ``ExecutionPlan``
(mode="loop"|"vmap"|"mesh", tenants=K, mesh_axes/mesh_devices,
stats_backend, merge="sequential"|"pairwise"|"tree", chunk_samples for
streamed training) and use one spelling of ``fit / fit_stream /
partial_fit / predict / scores / merge / reduce / save / load`` plus the
round-based ``FederationSession``.  The engine dispatches to the modules
above; the old module-level fit entry points (``fleet.fleet_fit``,
``fleet_sharded.sharded_fleet_fit``, ``federated.federated_fit``,
``sharded.fit_on_mesh``) remain as thin deprecation shims over it.

Streaming: the paper's sufficient statistics are additive over sample
blocks, so training is also available as a bounded-memory fold —
``daef.fit_chunked`` (scan over on-device chunks) and ``daef.fit_stream``
(host chunk iterator), built on ``rolann.init_stats``/``accumulate_stats``,
``elm_ae.accumulate_layer_stats`` and ``dsvd.masked_gram``.
"""
from repro.core import (  # noqa: F401
    activations,
    anomaly,
    chol,
    daef,
    dsvd,
    eigh,
    elm_ae,
    federated,
    fleet,
    fleet_sharded,
    initializers,
    rolann,
)
from repro.core.daef import DAEFConfig, DAEFModel, fit, predict  # noqa: F401
