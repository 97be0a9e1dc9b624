"""DAEFEngine — one client-facing API over every DAEF execution path.

The engine binds a ``DAEFConfig`` (the math: layer sizes, lambdas, knowledge
representation) to an ``ExecutionPlan`` (the placement: loop / vmap / mesh,
tenant count, merge strategy, streaming chunk width) and exposes ONE
spelling of

    fit / fit_stream / partial_fit / predict / scores / merge / reduce /
    thresholds / classify / save / load / session

Training is a fold over the paper's additive sufficient statistics:
``ExecutionPlan(chunk_samples=...)`` makes ``fit``/``partial_fit``
accumulate per-layer Gram statistics over sample chunks (peak memory flat
in the sample count), and ``fit_stream`` drives the same fold from a host
chunk iterator for data that never fits on device at once.

Internally it dispatches to the existing kernels — the eager single-model
core (`core.daef`), the vmapped fleet kernels (`core.fleet`), the
tenant-sharded fleet (`core.fleet_sharded`) and the data-sharded single
model (`core.sharded`) — resolving env/config precedence exactly once at
construction and building/caching the device mesh on first use, so client
code selects placement by configuration, never by importing a different
module.

State convention: with a 3-D ``[K, features, samples]`` batch the engine
works on a ``DAEFFleet`` (every method takes/returns fleets); with a 2-D
``[features, samples]`` matrix it works on a single ``DAEFModel``.  The two
agree bit-for-bit with the direct module-level calls they subsume
(tests/test_engine.py property-checks every mode at the test_parity
tolerances).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import anomaly, daef, dsvd, fleet, fleet_sharded, rolann, sharded
from repro.engine.plan import ExecutionPlan, PlanError

Array = jnp.ndarray

EngineState = daef.DAEFModel | fleet.DAEFFleet


def _bumps_model_version(method):
    """Mark an engine method as producing a NEW model: the engine's
    ``model_version`` counter ticks after it returns (not on error).

    The serving layer's score/threshold cache keys on this counter
    (`serving.cache.ScoreCache`), so every state-producing mutation —
    fit / fit_stream / partial_fit / merge / reduce and session rounds —
    invalidates cached scores by construction."""
    @functools.wraps(method)
    def wrapper(self, *args, **kwargs):
        out = method(self, *args, **kwargs)
        self._model_version += 1
        return out
    return wrapper


def _only(outs: list):
    """The state of a fit that makes one program call: its output."""
    return outs[0]


def _tenant(x, i: int):
    """Tenant ``i``'s data of a fleet batch (for lowering, its shape)."""
    if isinstance(x, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(x.shape[1:], x.dtype)
    return x[i]


class DAEFEngine:
    """Unified DAEF training/serving engine (see module docstring).

    Runnable end to end (the fleet version of the README quickstart):

    >>> import numpy as np
    >>> from repro.core import daef
    >>> from repro.engine import DAEFEngine, ExecutionPlan
    >>> cfg = daef.DAEFConfig(layer_sizes=(8, 3, 5, 8))
    >>> engine = DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=4))
    >>> xs = np.random.default_rng(0).normal(size=(4, 8, 64)).astype("float32")
    >>> fl = engine.fit(xs)                       # one jitted fleet dispatch
    >>> scores = engine.scores(fl, xs)            # [4, 64] reconstruction MSE
    >>> sites = engine.reduce(fl, group_size=2)   # federate per plan.merge
    >>> sites.size
    2

    Full API index with contracts: docs/api.md.
    """

    def __init__(
        self,
        config: daef.DAEFConfig,
        plan: ExecutionPlan | None = None,
        *,
        mesh=None,
    ):
        """Bind the math to a placement.

        Args:
            config: the DAEF formulation — layer sizes, lambdas, knowledge
                representation (``method``), seed, gram solver.
            plan: the placement/dispatch choice; ``None`` means the default
                ``ExecutionPlan()`` (one model, vmap mode).
            mesh: an explicit device mesh for ``mode="mesh"`` plans (e.g.
                from ``launch.mesh.make_production_mesh``).  ``None`` builds
                and caches one on first use from ``plan.mesh_devices``.

        Raises:
            PlanError: ``plan`` is not an ExecutionPlan; the plan and config
                conflict (``chunk_samples`` with ``method="svd"``); the mesh
                is missing a required axis or does not tile the fleet.
        """
        plan = plan if plan is not None else ExecutionPlan()
        if not isinstance(plan, ExecutionPlan):
            raise PlanError(
                f"plan must be an ExecutionPlan, got {type(plan).__name__}"
            )
        if plan.chunk_samples is not None and config.method != "gram":
            raise PlanError(
                f"chunk_samples={plan.chunk_samples} streams the fit by "
                "accumulating Gram sufficient statistics chunk by chunk, but "
                f"config.method={config.method!r} — SVD factors have no "
                "additive chunk form; use method='gram'"
            )
        if plan.privacy is not None and plan.privacy.enabled:
            if config.method != "gram":
                raise PlanError(
                    "plan.privacy hardens ADDITIVE (G, M) exchanges, but "
                    f"config.method={config.method!r} — factor knowledge has "
                    "neither a bounded-sensitivity DP release nor an additive "
                    "secagg wire form; use method='gram'"
                )
            if plan.privacy.dp_enabled and (
                config.act_hidden != "logsig" or config.act_last != "linear"
            ):
                raise PlanError(
                    "plan.privacy DP sensitivity bounds are derived for "
                    "act_hidden='logsig' + act_last='linear', got "
                    f"({config.act_hidden!r}, {config.act_last!r}) — "
                    "unbounded activations make the release sensitivity "
                    "unbounded (privacy.dp.block_sensitivities)"
                )
        self.config = config
        self.plan = plan
        self._model_version = 0
        self._mesh = None
        if mesh is not None:
            self._check_mesh(mesh)
            self._mesh = mesh
        elif plan.mode == "mesh" and plan.mesh_devices is not None:
            self.mesh  # build eagerly: surface bad mesh sizes at init

    @property
    def model_version(self) -> int:
        """Monotone counter of model-producing mutations through this
        engine (fit / fit_stream / partial_fit / merge / reduce / session
        rounds).  The serving layer keys its score/threshold cache on it:
        a version bump means previously scored samples must re-score."""
        return self._model_version

    def _bump_version(self) -> None:
        """Tick ``model_version`` for mutations that bypass the decorated
        engine methods (e.g. `FederationSession.round`)."""
        self._model_version += 1

    # ------------------------------------------------------------------
    # Mesh
    # ------------------------------------------------------------------

    def _check_mesh(self, mesh) -> None:
        if self.plan.mode != "mesh":
            raise PlanError(
                f"an explicit mesh was given but plan.mode={self.plan.mode!r}; "
                "use ExecutionPlan(mode='mesh', ...)"
            )
        missing = [a for a in self.plan.mesh_axes if a not in mesh.shape]
        if missing:
            raise PlanError(
                f"mesh {dict(mesh.shape)} has no axis {missing} required by "
                f"plan.mesh_axes={self.plan.mesh_axes}"
            )
        if self.plan.tenant_sharded:
            d = mesh.shape[fleet_sharded.TENANT_AXIS]
            if self.plan.tenants % d:
                raise PlanError(
                    f"bad mesh size: tenants={self.plan.tenants} does not "
                    f"divide evenly over the {d}-device "
                    f"'{fleet_sharded.TENANT_AXIS}' axis — pad the fleet or "
                    "resize the mesh"
                )

    @property
    def mesh(self):
        """The device mesh this plan runs on (built once, then cached).
        None for loop/vmap plans."""
        if self.plan.mode != "mesh":
            return None
        if self._mesh is None:
            self._mesh = self._build_mesh()
        return self._mesh

    def _build_mesh(self):
        plan = self.plan
        avail = len(jax.devices())
        if plan.tenant_sharded:
            d = plan.mesh_devices
            if d is None:
                d = min(avail, plan.tenants)
                while d > 1 and plan.tenants % d:
                    d -= 1
            if d > avail:
                raise PlanError(
                    f"bad mesh size: mesh_devices={d} exceeds the {avail} "
                    "available device(s) — shrink the plan or run on more "
                    "devices"
                )
            return fleet_sharded.tenant_mesh(d)
        if len(plan.mesh_axes) != 1:
            raise PlanError(
                f"cannot auto-build a mesh for axes {plan.mesh_axes}; pass "
                "mesh= explicitly (e.g. launch.mesh.make_production_mesh())"
            )
        n = plan.mesh_devices or avail
        if n > avail:
            raise PlanError(
                f"bad mesh size: mesh_devices={n} exceeds the {avail} "
                "available device(s)"
            )
        return jax.make_mesh(
            (n,), plan.mesh_axes,
            axis_types=(jax.sharding.AxisType.Auto,) * len(plan.mesh_axes),
        )

    # ------------------------------------------------------------------
    # Input handling
    # ------------------------------------------------------------------

    def _check_x(self, x, *, what: str) -> bool:
        """Validate a data batch; True when it is a [K, m, n] fleet batch."""
        ndim = getattr(x, "ndim", None)
        m0 = self.config.layer_sizes[0]
        if ndim == 3:
            k = x.shape[0]
            if k != self.plan.tenants:
                raise PlanError(
                    f"{what}: batch has {k} tenants but the plan declares "
                    f"tenants={self.plan.tenants} — reshape the batch or "
                    "re-plan"
                )
            if x.shape[1] != m0:
                raise PlanError(
                    f"{what}: feature dim {x.shape[1]} != layer_sizes[0] {m0}"
                )
            if self.plan.data_sharded:
                raise PlanError(
                    f"{what}: plan shards the sample axis of a single model "
                    f"(mesh_axes={self.plan.mesh_axes}) but got a 3-D tenant "
                    "batch; use mesh_axes=('tenants',) for fleets"
                )
            return True
        if ndim == 2:
            if self.plan.tenants != 1:
                raise PlanError(
                    f"{what}: got a single [features, samples] matrix but the "
                    f"plan declares tenants={self.plan.tenants}; stack the "
                    "per-tenant data to [K, features, samples]"
                )
            if x.shape[0] != m0:
                raise PlanError(
                    f"{what}: feature dim {x.shape[0]} != layer_sizes[0] {m0}"
                )
            return False
        raise PlanError(
            f"{what}: expected [features, samples] or [K, features, samples], "
            f"got shape {getattr(x, 'shape', None)}"
        )

    def _is_fleet(self, state: EngineState, *, what: str) -> bool:
        if isinstance(state, fleet.DAEFFleet):
            if state.size != self.plan.tenants:
                raise PlanError(
                    f"{what}: fleet has {state.size} tenants but the plan "
                    f"declares tenants={self.plan.tenants}"
                )
            return True
        if isinstance(state, daef.DAEFModel):
            if self.plan.tenants != 1:
                raise PlanError(
                    f"{what}: got a single DAEFModel but the plan declares "
                    f"tenants={self.plan.tenants}"
                )
            return False
        raise PlanError(
            f"{what}: expected a DAEFModel or DAEFFleet, got "
            f"{type(state).__name__}"
        )

    # ------------------------------------------------------------------
    # fit / partial_fit
    # ------------------------------------------------------------------

    @_bumps_model_version
    def fit(
        self,
        x,
        *,
        seeds=None,
        lam_hidden=None,
        lam_last=None,
        n_partitions: int = 1,
    ) -> EngineState:
        """Train under the plan — closed form, no epochs.

        With ``plan.chunk_samples`` set, training streams: every layer's
        statistics accumulate over sample chunks (one scan pass per layer)
        instead of materializing the full activations — same result as the
        one-shot fit within accumulation-order float error, peak memory flat
        in the sample count.

        Args:
            x: ``[K, features, samples]`` for a fleet (K == plan.tenants) or
                ``[features, samples]`` for a single model.
            seeds, lam_hidden, lam_last: scalar-or-``[K]`` per-tenant
                overrides (fleet batches only; single models set them on the
                DAEFConfig).
            n_partitions: split the sample axis to exercise the distributed
                SVD/merge path (loop + vmap modes).

        Returns:
            A trained ``DAEFFleet`` (3-D input) or ``DAEFModel`` (2-D input),
            placed per the plan (mesh plans shard the result).

        Raises:
            PlanError: batch shape disagrees with the plan (tenant count,
                feature dim), per-tenant overrides on a single model, or
                ``n_partitions`` combined with ``plan.chunk_samples``.

        Host spans (`repro.obs`): ``engine.fit`` (attributes ``tenants`` and
        ``samples`` per tenant) over ``fit.prepare``, then ``fit.place`` and
        ``fit.dispatch`` for each program call.
        """
        shape = getattr(x, "shape", None) or (0,)
        with obs.span("engine.fit", tenants=self.plan.tenants, samples=int(shape[-1])):
            with obs.span("fit.prepare"):
                calls, finish = self._fit_calls(x, seeds, lam_hidden, lam_last,
                                                n_partitions)
            return finish([call.run() for call in calls])

    def lower_fit(self, x, *, seeds=None, lam_hidden=None, lam_last=None):
        """The lowered program ``fit`` would run for ``x`` under the plan
        (for ``mode="loop"``, the per-tenant program, which every tenant
        shares): ``.compile().as_text()`` is what the device executes.

        ``x`` may be a ``jax.ShapeDtypeStruct``: lowering needs only its
        shape and dtype, and takes the placement from the plan.  Raises what
        ``fit`` raises for the same arguments.
        """
        calls, _ = self._fit_calls(x, seeds, lam_hidden, lam_last, 1)
        return calls[0].lower()

    def _fit_calls(self, x, seeds, lam_hidden, lam_last, n_partitions):
        """The program calls ``fit`` makes for ``x`` under the plan
        (`daef.FitCall`; one per tenant in loop mode, else one) and the
        function that makes the fitted state from their outputs."""
        cfg, plan = self.config, self.plan
        chunk = plan.chunk_samples
        if chunk is not None and n_partitions != 1:
            raise PlanError(
                f"fit: n_partitions={n_partitions} simulates explicit "
                "partitions but plan.chunk_samples already streams the "
                "sample axis — drop one of the two"
            )
        if not self._check_x(x, what="fit"):
            if seeds is not None or lam_hidden is not None or lam_last is not None:
                raise PlanError(
                    "fit: per-tenant seeds/lambdas apply to fleet batches; "
                    "for a single model set them on the DAEFConfig"
                )
            if plan.data_sharded:
                call = sharded._fit_on_mesh_call(
                    cfg, x, self.mesh, data_axes=plan.mesh_axes,
                    local_factorization=plan.local_factorization,
                )
            else:
                call = daef._fit_call(cfg, x, n_partitions=n_partitions,
                                      chunk_samples=chunk)
            return [call], _only

        if plan.mode == "loop":
            seeds, lam_hidden, lam_last = fleet._prepare_fit(
                cfg, x, seeds, lam_hidden, lam_last
            )
            calls = [
                daef._fit_call(
                    self._tenant_cfg(seeds, lam_hidden, lam_last, i),
                    _tenant(x, i), n_partitions=n_partitions, chunk_samples=chunk,
                )
                for i in range(plan.tenants)
            ]

            def finish(models):
                return fleet.fleet_from_models(
                    cfg, models, seeds=seeds, lam_hidden=lam_hidden,
                    lam_last=lam_last,
                )

            return calls, finish
        if plan.mode == "vmap":
            call = fleet._fit_fleet_call(
                cfg, x, seeds, lam_hidden, lam_last, n_partitions=n_partitions,
                chunk_samples=chunk,
            )
        else:
            call = fleet_sharded._fit_sharded_call(
                cfg, x, self.mesh, seeds, lam_hidden, lam_last,
                n_partitions=n_partitions, chunk_samples=chunk,
            )
        return [call], _only

    @_bumps_model_version
    def fit_stream(
        self,
        batches,
        *,
        seeds=None,
        lam_hidden=None,
        lam_last=None,
    ) -> EngineState:
        """Train from a host chunk source — data that never fits on device.

        ``batches`` yields fixed-shape chunks — ``[features, chunk_samples]``
        for a single model, ``[K, features, chunk_samples]`` for a fleet
        (only the final chunk may be narrower; it is padded and masked
        exactly).  Accepts any iterable (snapshotted into a host list of
        chunk references — the fit makes one pass per layer) or a zero-arg
        callable returning a fresh iterator per pass (true streaming, e.g.
        re-opening a file reader).

        Each pass feeds chunks into one re-traced jitted step whose
        accumulators are donated; mesh plans place every chunk by sharding,
        so a device only ever holds its tenant slice of one chunk plus the
        O(m^2) running statistics.  Matches ``fit`` on the concatenated data
        within accumulation-order float error."""
        cfg, plan = self.config, self.plan
        if cfg.method != "gram":
            raise PlanError(
                "fit_stream accumulates Gram sufficient statistics; "
                f"config.method={cfg.method!r} has no additive chunk form — "
                "use method='gram'"
            )
        if plan.data_sharded:
            raise PlanError(
                "fit_stream streams host chunks, but the plan shards the "
                f"sample axis on-mesh (mesh_axes={plan.mesh_axes}) — use "
                "mode='vmap'/'loop' or a tenant-sharded mesh plan"
            )
        if plan.tenants == 1:
            if seeds is not None or lam_hidden is not None or lam_last is not None:
                raise PlanError(
                    "fit_stream: per-tenant seeds/lambdas apply to fleet "
                    "streams; for a single model set them on the DAEFConfig"
                )
            return daef.fit_stream(cfg, batches)
        if plan.mode == "loop":
            factory = daef._stream_chunk_source(batches)
            seeds, lam_hidden, lam_last = self._prepare_stream_fleet(
                factory, seeds, lam_hidden, lam_last
            )
            if not callable(batches):
                # snapshot sources: convert each chunk to host ONCE and hand
                # every tenant a view — not K device-to-host copies per chunk
                host_chunks = [np.asarray(c) for c in factory()]
                factory = lambda: iter(host_chunks)  # noqa: E731
            models = [
                daef.fit_stream(
                    self._tenant_cfg(seeds, lam_hidden, lam_last, i),
                    lambda i=i: (np.asarray(c)[i] for c in factory()),
                )
                for i in range(plan.tenants)
            ]
            return fleet.fleet_from_models(
                cfg, models, seeds=seeds, lam_hidden=lam_hidden,
                lam_last=lam_last,
            )
        if plan.mode == "vmap":
            return fleet._fit_fleet_stream(
                cfg, batches, seeds=seeds, lam_hidden=lam_hidden,
                lam_last=lam_last, tenants=plan.tenants,
            )
        return fleet_sharded._fit_sharded_stream(
            cfg, batches, self.mesh, seeds=seeds, lam_hidden=lam_hidden,
            lam_last=lam_last, tenants=plan.tenants,
        )

    def _prepare_stream_fleet(self, factory, seeds, lam_hidden, lam_last):
        """Loop-mode stream helper: peek one chunk to learn K, then broadcast
        the per-tenant hyperparameters exactly as the batched paths do."""
        first = next(iter(factory()), None)
        if first is None:
            raise PlanError("fit_stream: empty chunk stream")
        shape = getattr(first, "shape", None)
        if shape is None or len(shape) != 3 or shape[0] != self.plan.tenants:
            raise PlanError(
                f"fit_stream: fleet chunks must be [K={self.plan.tenants}, "
                f"features, chunk_samples], got {shape}"
            )
        k = shape[0]
        return (
            fleet._per_tenant(seeds, self.config.seed, k, jnp.int32),
            fleet._per_tenant(lam_hidden, self.config.lam_hidden, k, jnp.float32),
            fleet._per_tenant(lam_last, self.config.lam_last, k, jnp.float32),
        )

    @_bumps_model_version
    def partial_fit(self, state: EngineState, x_new) -> EngineState:
        """Incremental learning: absorb a new data block (per tenant).

        Honors ``plan.chunk_samples``: the update block is fitted by the
        streaming accumulator before the knowledge merge.

        Args:
            state: a trained state from ``fit``/``fit_stream``/``load``.
            x_new: the new block, shaped like the data ``state`` was trained
                on (``[K, features, n_new]`` / ``[features, n_new]``).

        Returns:
            The updated state: knowledge summed, weights re-solved once.

        Raises:
            PlanError: ``state`` or ``x_new`` disagrees with the plan.
        """
        cfg, plan = self.config, self.plan
        chunk = plan.chunk_samples
        if not self._is_fleet(state, what="partial_fit"):
            self._check_x(x_new, what="partial_fit")
            if plan.data_sharded:
                update = sharded._fit_on_mesh(
                    cfg, x_new, self.mesh, data_axes=plan.mesh_axes,
                    local_factorization=plan.local_factorization,
                )
                return daef.merge_models(cfg, state, update)
            if chunk is not None:
                update = daef.fit_chunked(cfg, x_new, chunk_samples=chunk)
                return daef.merge_models(cfg, state, update)
            return daef.partial_fit(cfg, state, x_new)
        self._check_x(x_new, what="partial_fit")
        if plan.mode == "loop":
            models = []
            for i in range(plan.tenants):
                cfg_i = self._tenant_cfg(
                    state.seeds, state.lam_hidden, state.lam_last, i
                )
                if chunk is not None:
                    update = daef.fit_chunked(cfg_i, x_new[i],
                                              chunk_samples=chunk)
                    models.append(
                        daef.merge_models(cfg_i, fleet.get_model(state, i),
                                          update)
                    )
                else:
                    models.append(
                        daef.partial_fit(cfg_i, fleet.get_model(state, i),
                                         x_new[i])
                    )
            return fleet.fleet_from_models(
                cfg, models, seeds=state.seeds, lam_hidden=state.lam_hidden,
                lam_last=state.lam_last,
            )
        if plan.mode == "vmap":
            if chunk is not None:
                update = fleet._fit_fleet_chunked(
                    cfg, x_new, chunk_samples=chunk, seeds=state.seeds,
                    lam_hidden=state.lam_hidden, lam_last=state.lam_last,
                )
            else:
                update = fleet._fit_fleet(
                    cfg, x_new, seeds=state.seeds, lam_hidden=state.lam_hidden,
                    lam_last=state.lam_last,
                )
            return fleet.fleet_merge(cfg, state, update)
        return fleet_sharded.sharded_fleet_partial_fit(
            cfg, state, x_new, mesh=self.mesh, chunk_samples=chunk,
        )

    def _tenant_cfg(self, seeds, lam_hidden, lam_last, i: int) -> daef.DAEFConfig:
        return dataclasses.replace(
            self.config,
            seed=int(np.asarray(seeds)[i]),
            lam_hidden=float(np.asarray(lam_hidden)[i]),
            lam_last=float(np.asarray(lam_last)[i]),
        )

    # ------------------------------------------------------------------
    # predict / scores
    # ------------------------------------------------------------------

    def predict(self, state: EngineState, x) -> Array:
        """Reconstruct ``x`` ([K, m, n] per-tenant, or [m, n] single)."""
        cfg, plan = self.config, self.plan
        if not self._is_fleet(state, what="predict"):
            self._check_x(x, what="predict")
            if plan.data_sharded:
                return sharded.predict_on_mesh(
                    cfg, state, x, self.mesh, data_axes=plan.mesh_axes
                )
            return daef.predict(cfg, state, x)
        self._check_x(x, what="predict")
        if plan.mode == "loop":
            return jnp.stack([
                daef.predict(cfg, fleet.get_model(state, i), x[i])
                for i in range(plan.tenants)
            ])
        if plan.mode == "vmap":
            return fleet.fleet_predict(cfg, state, x)
        return fleet_sharded.sharded_fleet_predict(cfg, state, x, mesh=self.mesh)

    def scores(self, state: EngineState, x, n_valid=None) -> Array:
        """Per-sample anomaly scores (reconstruction MSE): [K, n] or [n].

        ``n_valid`` ([K] ints, fleet only) masks a padded serving batch:
        scores of padding columns come back NaN."""
        cfg, plan = self.config, self.plan
        if not self._is_fleet(state, what="scores"):
            if n_valid is not None:
                raise PlanError(
                    "scores: n_valid masks padded FLEET batches; a single "
                    "model takes an unpadded [features, samples] matrix"
                )
            self._check_x(x, what="scores")
            if plan.data_sharded:
                recon = sharded.predict_on_mesh(
                    cfg, state, x, self.mesh, data_axes=plan.mesh_axes
                )
                return daef.sample_mse(recon, x)
            return daef.reconstruction_error(cfg, state, x)
        self._check_x(x, what="scores")
        if plan.mode == "loop":
            errs = jnp.stack([
                daef.reconstruction_error(cfg, fleet.get_model(state, i), x[i])
                for i in range(plan.tenants)
            ])
            if n_valid is None:
                return errs
            mask = (jnp.arange(x.shape[-1])[None, :]
                    < jnp.asarray(n_valid)[:, None])
            return jnp.where(mask, errs, jnp.nan)
        if plan.mode == "vmap":
            return fleet.fleet_scores(cfg, state, x, n_valid=n_valid)
        return fleet_sharded.sharded_fleet_scores(
            cfg, state, x, n_valid=n_valid, mesh=self.mesh
        )

    def thresholds(self, state: EngineState, rule: str = "extreme_iqr") -> Array:
        """Per-tenant anomaly thresholds from each model's train errors."""
        if self._is_fleet(state, what="thresholds"):
            return fleet.fleet_thresholds(state, rule=rule)
        return anomaly.threshold(state.train_errors, rule)

    def classify(self, scores: Array, thresholds: Array) -> Array:
        """Flag anomalies (1 = anomalous); NaN padding scores classify 0."""
        scores = jnp.asarray(scores)
        if scores.ndim == 2:
            return fleet.fleet_classify(scores, jnp.asarray(thresholds))
        return anomaly.classify(scores, thresholds)

    # ------------------------------------------------------------------
    # Federation: merge / reduce / session
    # ------------------------------------------------------------------

    @_bumps_model_version
    def merge(self, a: EngineState, b: EngineState) -> EngineState:
        """Federated aggregation of two states trained with shared seeds
        (tenant k of ``a`` merges with tenant k of ``b``).

        Args:
            a, b: two states of the same kind (both fleets of plan.tenants,
                or both single models) whose tenants share stage-1 seeds.

        Returns:
            The merged state: statistics added (Eq. 6-9), one re-solve.

        Raises:
            PlanError: mixed state kinds, or a fleet whose size/seed vector
                disagrees with the plan.
        """
        a_fleet = self._is_fleet(a, what="merge")
        b_fleet = self._is_fleet(b, what="merge")
        if a_fleet != b_fleet:
            raise PlanError(
                "merge: cannot mix a DAEFModel with a DAEFFleet — wrap the "
                "single model in a 1-tenant fleet (fleet.fleet_from_models) "
                "or extract the tenant (engine.get_model)"
            )
        if not a_fleet:
            return daef.merge_models(self.config, a, b)
        if self.plan.mode == "loop":
            fleet._check_merge_compat(a, b, "merge")
            models = [
                daef.merge_models(
                    self._tenant_cfg(a.seeds, a.lam_hidden, a.lam_last, i),
                    fleet.get_model(a, i), fleet.get_model(b, i),
                )
                for i in range(self.plan.tenants)
            ]
            return fleet.fleet_from_models(
                self.config, models, seeds=a.seeds, lam_hidden=a.lam_hidden,
                lam_last=a.lam_last,
            )
        return fleet.fleet_merge(self.config, a, b)

    @_bumps_model_version
    def reduce(self, state: fleet.DAEFFleet, group_size: int) -> fleet.DAEFFleet:
        """Federate adjacent groups of ``group_size`` tenants into one model
        each (K -> K/group_size), using the plan's ``merge`` strategy:

        * "sequential" — host left-to-right ``daef.merge_models`` reduce;
        * "pairwise"   — log2(group_size) rounds of vmapped pairwise merges;
        * "tree"       — the on-mesh shard_map butterfly (`fleet_merge_tree`).

        All three agree up to float error; tenants within a group must share
        a seed (the paper's shared-randomness requirement).

        Returns:
            A ``DAEFFleet`` of K/group_size models (serve it through
            ``engine.for_tenants(K // group_size)``).

        Raises:
            PlanError: a single model, a group size that does not divide the
                fleet, a non-power-of-two group under "pairwise"/"tree", or
                unequal seeds within a group.

        Host spans (`repro.obs`): ``engine.reduce`` (attributes ``tenants``
        and ``group_size``) over ``reduce.prepare`` (checks, the host read
        of the seeds and lambdas); under "tree" then ``reduce.place``,
        ``reduce.dispatch`` (attribute ``exchange_bytes``, the bytes each
        device sends in the butterfly) and ``reduce.dedup``.
        """
        with obs.span("engine.reduce", tenants=self.plan.tenants, group_size=group_size):
            with obs.span("reduce.prepare"):
                call = self._reduce_call(state, group_size)
            if group_size == 1:
                return state
            if call is not None:
                return call.run()
            return self._reduce_on_host(state, group_size)

    def lower_reduce(self, state: fleet.DAEFFleet, group_size: int):
        """The lowered tree program ``reduce`` would dispatch for ``state``
        under ``merge="tree"``: ``.compile().as_text()`` is what the devices
        execute, its ops named by scope (``merge_local``, ``merge_exchange``,
        ``merge_cross``, ``merge_solve``).

        ``state``'s leaves may be ``jax.ShapeDtypeStruct``s: lowering needs
        only their shapes and dtypes, and takes the placement from the plan.
        Raises what ``reduce`` raises for the same arguments, and
        ``PlanError`` for another merge strategy or a group of one.
        """
        if self.plan.merge != "tree":
            raise PlanError(
                f"lower_reduce: plan.merge={self.plan.merge!r} runs no tree "
                "program; use merge='tree'"
            )
        call = self._reduce_call(state, group_size)
        if call is None:
            raise PlanError("lower_reduce: group_size 1 merges nothing")
        return call.lower()

    def _reduce_call(self, state, group_size: int):
        """Check a reduce of ``state``; under "tree" the tree program's call
        (`fleet_sharded.MergeCall`, None for a group of one), else None."""
        if not self._is_fleet(state, what="reduce"):
            raise PlanError("reduce: a single model has nothing to reduce")
        k, merge = state.size, self.plan.merge
        if group_size < 1 or k % group_size:
            raise PlanError(
                f"reduce: group_size {group_size} must divide the fleet "
                f"size {k}"
            )
        if merge in ("pairwise", "tree") and (group_size & (group_size - 1)):
            raise PlanError(
                f"reduce: merge={merge!r} needs a power-of-two group_size "
                f"(got {group_size}) — use merge='sequential' for arbitrary "
                "group sizes"
            )
        if merge == "tree":
            return fleet_sharded._merge_tree_call(
                self.config, state, group_size,
                self.mesh if self.plan.tenant_sharded else None,
            )
        if group_size > 1:
            fleet_sharded._validate_groups(state, group_size)
        return None

    def _reduce_on_host(self, state: fleet.DAEFFleet, group_size: int):
        """The "pairwise" and "sequential" reduces."""
        k = state.size
        if self.plan.merge == "pairwise":
            while group_size > 1:
                state = fleet.fleet_merge_pairwise(self.config, state)
                group_size //= 2
            return state
        # sequential: exact left-to-right reduction per group, on host
        models = []
        for g in range(k // group_size):
            cfg_g = self._tenant_cfg(
                state.seeds, state.lam_hidden, state.lam_last, g * group_size
            )
            merged = fleet.get_model(state, g * group_size)
            for j in range(1, group_size):
                merged = daef.merge_models(
                    cfg_g, merged, fleet.get_model(state, g * group_size + j)
                )
            models.append(merged)
        stride = slice(None, None, group_size)
        return fleet.fleet_from_models(
            self.config, models, seeds=state.seeds[stride],
            lam_hidden=state.lam_hidden[stride],
            lam_last=state.lam_last[stride],
        )

    def for_tenants(self, tenants: int) -> "DAEFEngine":
        """A derived engine for a different fleet size — same config, same
        mode/merge/backend.  The natural follow-up to ``reduce``: the
        K/group_size result fleet is served by ``engine.for_tenants(K //
        group_size)``.  Mesh plans keep their device count when it still
        divides the new tenant count and fall back to auto-sizing
        otherwise."""
        plan = self.plan
        mesh_devices = plan.mesh_devices
        if mesh_devices is not None and tenants % mesh_devices:
            mesh_devices = None
        return DAEFEngine(
            self.config,
            dataclasses.replace(plan, tenants=tenants,
                                mesh_devices=mesh_devices),
        )

    def session(self) -> "FederationSession":
        """A multi-round federation driver bound to this engine.

        ``plan.federation`` selects the round semantics — "sync" lockstep
        rounds or "async" continual rounds with a versioned per-site ledger
        and ``plan.max_staleness`` bounds (docs/federation.md has worked
        examples of both)."""
        from repro.engine.session import FederationSession

        return FederationSession(self)

    # ------------------------------------------------------------------
    # save / load
    # ------------------------------------------------------------------

    def save(self, state, path: str) -> str:
        """Persist a trained state (msgpack-framed numpy, via
        train.checkpoint) or a mid-federation ``FederationSession`` (model
        + per-site ledger + privacy spend — see ``FederationSession.save``).
        Returns the checkpoint directory."""
        from repro.engine.session import FederationSession
        from repro.train import checkpoint

        if isinstance(state, FederationSession):
            return state.save(path)
        self._is_fleet(state, what="save")
        return checkpoint.save(path, state)

    def load(self, path: str):
        """Restore whatever ``save`` wrote at ``path`` under a structurally
        identical config/plan: a ``session.json`` in the directory means a
        ``FederationSession`` (rebound to THIS engine), anything else a
        model/fleet state; mesh plans re-place the fleet onto the mesh."""
        import os

        from repro.train import checkpoint

        if os.path.exists(os.path.join(path, "session.json")):
            from repro.engine.session import FederationSession

            return FederationSession.restore(self, path)
        try:
            state = checkpoint.restore(path, self._template())
        except ValueError as e:
            raise PlanError(
                f"load: checkpoint at {path!r} does not match this engine's "
                f"config/plan ({e}); load with the engine that saved it"
            ) from e
        if isinstance(state, fleet.DAEFFleet) and self.plan.tenant_sharded:
            return fleet_sharded.shard_fleet(state, self.mesh)
        return state

    def _template(self) -> EngineState:
        """Structural skeleton matching what fit() returns — checkpoint
        restore only consults the tree structure; shapes come from the
        manifest."""
        cfg = self.config
        n_layers = len(cfg.layer_sizes)

        def z():
            return np.zeros((0,), np.float32)

        if cfg.method == "gram":
            know = rolann.RolannStats(g=z(), m=z())
        else:
            know = rolann.RolannFactors(u=z(), s=z(), m=z())
        model = daef.DAEFModel(
            weights=tuple(z() for _ in range(n_layers - 1)),
            biases=tuple(z() for _ in range(n_layers - 2)),
            encoder_factors=dsvd.SvdFactors(u=z(), s=z()),
            layer_knowledge=tuple(know for _ in range(n_layers - 2)),
            train_errors=z(),
        )
        if self.plan.tenants == 1:
            return model
        return fleet.DAEFFleet(
            model=model, seeds=z(), lam_hidden=z(), lam_last=z()
        )

    # ------------------------------------------------------------------

    def get_model(self, state: EngineState, i: int = 0) -> daef.DAEFModel:
        """Extract tenant ``i`` as a plain single-model DAEFModel."""
        if self._is_fleet(state, what="get_model"):
            return fleet.get_model(state, i)
        return state

    def __repr__(self) -> str:
        return (
            f"DAEFEngine(layers={self.config.layer_sizes}, "
            f"method={self.config.method!r}, plan={self.plan})"
        )
