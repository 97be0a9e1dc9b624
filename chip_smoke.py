#!/usr/bin/env python3
"""Chip smoke test: DAEF's fit, serve and federation path on a TPU, checked.

Drives the system through the entry points a user calls — ``DAEFEngine`` /
``ExecutionPlan``, ``FleetServer`` and ``FederationSession`` — at the
paper's published sizes, with the Pallas kernels compiled by Mosaic, and
checks every result: a fit against the same fit run in float32 on the host
CPU in this process, served scores against ``engine.scores``, a federated
round against the centralized fit, and each four-chip phase against the same
work on one chip.

    python3 chip_smoke.py              # one chip: device, fit, serve, federate
    python3 chip_smoke.py --chips 4    # only the four-chip phases

Every phase prints its facts and its seconds, split into trace+lower,
compile (XLA and Mosaic, persistent-cache reads included) and run.  The last
line is one JSON object, ``{"ok": true, "device": {...}}``, printed only
after every check passed.  A host without a TPU, or a failed check, exits
non-zero without it.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
import warnings
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.launch import compile_cache  # noqa: E402  (fails outside the repo)

# Paper Table 5 architectures and lambdas (benchmarks/table2_f1.py).
CREDITCARD = ((29, 15, 18, 21, 24, 27, 29), 0.8, 0.9)
CARDIO = ((21, 4, 8, 12, 16, 21), 0.9, 0.9)
TENANTS = 256
CHUNK = 4096
PARTITIONS = 8

# Train-error quantiles (q50/q90/q99) against the reference.  On the CPU
# alone, folding creditcard's 255,883 training samples in 4096-sample
# chunks instead of at once moves them by 2.0e-3 (relative).  On a TPU v5e
# at default matmul precision the one-shot einsum fit lands 6.7e-4 from the
# CPU fit, and 1.7e-3 with every matmul at full precision: the gap is
# summation order, not precision.  The bar is five times the CPU's spread.
QUANTILE_RTOL = 1e-2
# Held-out scores, the check that covers every layer: the test split's
# per-sample reconstruction MSE, compared as ||s - r|| / ||r||.  The CPU's
# own chunked and one-shot fits differ by 1.3e-2 here, a sequential
# federated round and the centralized fit by 1.4e-2.  Planted faults in the
# per-output (G, M) statistics move it far more: one output's M zeroed,
# 0.29; one 512-sample tile of every 4096-sample chunk dropped, 0.14.
SCORE_RTOL = 5e-2
# Encoder weights: the leading singular vectors of the data Gram, 8e-5 to
# 1e-4 from the CPU fit on a TPU v5e.  The decoder weights are printed but
# not held to a bar: their Grams are near-singular at n = 255,883 (lambda
# 0.8 against Gram entries ~1e5), so the CPU's own chunked and one-shot fits
# differ by 0.16 in them while the bars above hold.
ENCODER_ATOL = 1e-3
# Served scores against engine.scores: the same float32 math on batches of
# other shapes, so only the accumulation order may differ.
SERVE_RTOL = 1e-5
SERVE_ATOL = 1e-6
# One-chip against four-chip results of the same fleet: per-tenant shapes
# differ (K/4 per device), so again only the accumulation order may differ.
MESH_RTOL = 1e-4

QUANTILES = (0.5, 0.9, 0.99)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")
_spans: list[tuple[float, float, bool]] = []    # (start, end, is_compile)
_cache = {"hits": 0, "requests": 0}


def _on_span(event: str, start: float, end: float, **_) -> None:
    if event == _COMPILE_EVENT or event in _TRACE_EVENTS:
        _spans.append((start, end, event == _COMPILE_EVENT))


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _cache["hits"] += 1
    elif event == "/jax/compilation_cache/compile_requests_use_cache":
        _cache["requests"] += 1


def _covered(spans) -> float:
    """Seconds covered by the union of (start, end) spans (nested traces
    and compiles inside a trace are counted once)."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(spans):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


class Clock:
    """Per-phase seconds from JAX's own compile events: compile (XLA and
    Mosaic, persistent-cache reads included), trace+lower (the rest of the
    time spent tracing and lowering), run (wall clock minus both)."""

    def __init__(self):
        self.totals = {"trace": 0.0, "compile": 0.0, "run": 0.0}

    @contextlib.contextmanager
    def __call__(self, name: str):
        first = len(_spans)
        t0 = time.time()
        yield
        wall = time.time() - t0
        spans = _spans[first:]
        comp = _covered([(a, b) for a, b, c in spans if c])
        both = _covered([(a, b) for a, b, _ in spans])
        parts = {"trace": both - comp, "compile": comp, "run": wall - both}
        for k, v in parts.items():
            self.totals[k] += v
        print(f"  [{name}] trace+lower {parts['trace']:.2f} s, compile "
              f"{comp:.2f} s, run {parts['run']:.2f} s")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")
    print(f"  ok: {what}")


def _quantiles(errors) -> np.ndarray:
    return np.quantile(np.asarray(errors, np.float64).ravel(), QUANTILES)


def score_gap(got, ref) -> float:
    """||got - ref|| / ||ref|| over held-out scores (see SCORE_RTOL)."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def compare_models(tag: str, got, ref, scores) -> None:
    """Print and check a model against its reference; ``scores`` is the
    pair (got, ref) of held-out scores (see the bars above)."""
    enc = float(np.abs(np.asarray(got.weights[0]) - np.asarray(ref.weights[0])).max())
    dw = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
             for a, b in zip(got.weights + got.biases, ref.weights + ref.biases,
                             strict=True))
    qg, qr = _quantiles(got.train_errors), _quantiles(ref.train_errors)
    rel = float(np.abs(qg / qr - 1.0).max())
    gap = score_gap(*scores)
    print(f"  {tag}: held-out score gap {gap:.3e}; max|dW| {dw:.3e} (encoder "
          f"{enc:.3e}); train-error q50/q90/q99 "
          f"{np.array2string(qg, precision=5)} vs "
          f"{np.array2string(qr, precision=5)} (max rel {rel:.2e})")
    check(gap <= SCORE_RTOL, f"{tag}: held-out scores within {SCORE_RTOL}")
    check(enc <= ENCODER_ATOL, f"{tag}: encoder within {ENCODER_ATOL}")
    check(rel <= QUANTILE_RTOL, f"{tag}: train-error quantiles within "
                                f"{QUANTILE_RTOL} (relative)")


def _config(arch, **kw):
    from repro.core import daef

    sizes, lam_hidden, lam_last = arch
    return daef.DAEFConfig(layer_sizes=sizes, lam_hidden=lam_hidden,
                           lam_last=lam_last, **kw)


def creditcard(scale: float = 1.0):
    from repro.data import synthetic

    ds = synthetic.make_dataset("creditcard", seed=0, scale=scale)
    x_train, x_test, _ = ds.train_test_split(fold=0)
    return x_train, x_test


def cardio_fleet(k: int, scale: float = 1.0):
    """K cardio tenants (one replica seed each): train [K, 21, n], tests."""
    from repro.data import synthetic

    splits = [synthetic.make_dataset("cardio", seed=t, scale=scale)
              .train_test_split(fold=0) for t in range(k)]
    n = min(s[0].shape[1] for s in splits)
    xs = np.stack([s[0][:, :n] for s in splits]).astype(np.float32)
    return xs, [s[1] for s in splits]


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------

def fit_phase(clock: Clock, scale: float = 1.0):
    """creditcard at the paper's size: einsum and fused, one-shot and
    chunked, each against the same plan fit in float32 on the host CPU."""
    from repro.core import daef, stats_backend
    from repro.engine import DAEFEngine, ExecutionPlan
    from repro.kernels.rolann_stats import ops

    x_train, x_test = creditcard(scale)
    cfg = _config(CREDITCARD)
    print(f"fit: creditcard replica, {x_train.shape[1]} training samples x "
          f"{x_train.shape[0]} features (fold 0), layers {cfg.layer_sizes}")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        auto = stats_backend.resolve("auto")
    why = "; ".join(str(w.message) for w in caught) or "measured entry"
    print(f"  stats_backend 'auto' resolves to {auto!r} ({why})")
    interpret = ops._resolve_interpret(None)
    check(interpret is False, "Pallas kernels resolve to Mosaic, not the "
                              "interpreter (_resolve_interpret(None) is False)")

    cpu = jax.devices("cpu")[0]
    x_dev, xt_dev = jax.device_put(x_train), jax.device_put(x_test)
    x_cpu, xt_cpu = jax.device_put(x_train, cpu), jax.device_put(x_test, cpu)
    fits = {}
    for chunk in (None, CHUNK):
        plan_name = "one-shot" if chunk is None else f"chunk {chunk}"
        with clock(f"fit {plan_name}, einsum, CPU float32 reference"), \
                jax.default_device(cpu):
            engine = DAEFEngine(cfg, ExecutionPlan(stats_backend="einsum",
                                                   chunk_samples=chunk))
            ref = engine.fit(x_cpu)
            ref_scores = engine.scores(ref, xt_cpu)
            jax.block_until_ready((ref, ref_scores))
        for backend in ("einsum", "fused"):
            tag = f"fit {plan_name}, {backend}"
            engine = DAEFEngine(cfg, ExecutionPlan(stats_backend=backend,
                                                   chunk_samples=chunk))
            with clock(tag):
                model = engine.fit(x_dev)
                scores = engine.scores(model, xt_dev)
                jax.block_until_ready((model, scores))
            with clock(f"{tag}: read back the compiled fit"):
                program = daef.lower_fit(engine.config, x_dev,
                                         chunk_samples=chunk).compile()
            kernels = program.as_text().count("tpu_custom_call")
            print(f"  {tag}: {kernels} Mosaic kernel call(s) in the compiled fit")
            check((kernels > 0) == (backend == "fused"),
                  f"{tag}: Mosaic kernels present iff the backend is fused")
            compare_models(tag, model, ref, (scores, ref_scores))
            fits[(chunk, backend)] = model
    return x_train, x_test, fits[(None, "einsum")]


def serve_phase(clock: Clock, k: int = TENANTS, rounds: int = 4,
                scale: float = 1.0):
    """K cardio tenants fit in one vmap dispatch, then ragged rounds through
    FleetServer; served scores must equal engine.scores."""
    from repro.engine import DAEFEngine, ExecutionPlan
    from repro.serving import FleetServer

    xs, tests = cardio_fleet(k, scale)
    cfg = _config(CARDIO)
    print(f"serve: {k} cardio tenants x {xs.shape[2]} training samples, "
          f"layers {cfg.layer_sizes}")
    fleets = {}
    for backend in ("einsum", "fused"):
        engine = DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=k,
                                               stats_backend=backend))
        with clock(f"serve: fleet fit, {backend}, one dispatch"):
            fl = engine.fit(xs, seeds=jnp.arange(k))
            jax.block_until_ready(fl.model)
        fleets[backend] = (engine, fl)
    qf = _quantiles(fleets["fused"][1].model.train_errors)
    qe = _quantiles(fleets["einsum"][1].model.train_errors)
    rel = float(np.abs(qf / qe - 1.0).max())
    print(f"  fleet train-error q50/q90/q99 fused {np.array2string(qf, precision=5)}"
          f" vs einsum {np.array2string(qe, precision=5)} (max rel {rel:.2e})")
    check(rel <= QUANTILE_RTOL, "fused fleet fit matches the einsum fleet fit")

    engine, fl = fleets["fused"]
    server = FleetServer(engine, fl, tile_width=32, rule="q90")
    with clock("serve: warmup (every tile shape)"):
        shapes = server.warmup()
    print(f"  warmup traced {shapes} tile shapes")
    rng = np.random.default_rng(0)
    worst, served = 0.0, 0
    with clock(f"serve: {rounds} rounds submit/flush/take + engine.scores"):
        for _ in range(rounds):
            counts = np.array([min(int(c), t.shape[1]) for c, t in
                               zip(rng.integers(1, 65, size=k), tests,
                                   strict=True)])
            requests = [t[:, rng.choice(t.shape[1], size=c, replace=False)]
                        .astype(np.float32)
                        for c, t in zip(counts, tests, strict=True)]
            rids = [server.submit(t, requests[t]) for t in range(k)]
            server.flush()
            results = [server.take(rid) for rid in rids]
            batch = np.zeros((k, xs.shape[1], 64), np.float32)
            for t in range(k):
                batch[t, :, :counts[t]] = requests[t]
            want = np.asarray(engine.scores(fl, batch, n_valid=counts))
            mus = server.thresholds
            for t, res in enumerate(results):
                ref = want[t, :counts[t]]
                check_ok = (np.isfinite(res.scores).all()
                            and np.allclose(res.scores, ref, rtol=SERVE_RTOL,
                                            atol=SERVE_ATOL)
                            and np.array_equal(res.flags,
                                               (res.scores > mus[t])
                                               .astype(np.int32)))
                if not check_ok:
                    check(False, f"tenant {t}: served scores/flags equal "
                                 "engine.scores and its threshold")
                worst = max(worst, float(np.abs(res.scores - ref).max()))
            served += int(counts.sum())
    s = server.stats
    print(f"  served {served} samples: {s['dispatches']} tile dispatches, "
          f"{s['cache_hit_cols']} cache-hit columns, max |served - "
          f"engine.scores| {worst:.3e}")
    check(True, f"served scores equal engine.scores within rtol {SERVE_RTOL}, "
                "all finite, flags = scores > threshold")


def federate_phase(clock: Clock, x_train, x_test, central):
    """One sequential FederationSession round over 8 creditcard partitions
    against the centralized fit, then two async rounds with a straggler."""
    from repro.engine import DAEFEngine, ExecutionPlan

    cfg = _config(CREDITCARD)
    parts = np.array_split(x_train, PARTITIONS, axis=1)
    print(f"federate: {PARTITIONS} creditcard partitions of "
          f"{parts[0].shape[1]} samples")
    engine = DAEFEngine(cfg, ExecutionPlan(merge="sequential",
                                           stats_backend="einsum"))
    x_dev = jax.device_put(x_test)
    with clock("federate: sequential round"):
        model = engine.session().round(parts)
        scores = engine.scores(model, x_dev)
        jax.block_until_ready((model, scores))
    compare_models("sequential round vs centralized fit", model, central,
                   (scores, engine.scores(central, x_dev)))

    plan = ExecutionPlan(federation="async", merge="pairwise",
                         max_staleness=1, stats_backend="einsum")
    session = DAEFEngine(cfg, plan).session()
    blocks = [np.array_split(p, 2, axis=1) for p in parts]
    straggler = PARTITIONS - 1
    with clock("federate: 2 async rounds, one straggler"):
        session.round({s: blocks[s][0] for s in range(straggler)})
        first = dict(session.sites)
        later = {s: blocks[s][1] for s in range(straggler)}
        later[straggler] = np.concatenate(blocks[straggler], axis=1)
        model = session.round(later)
        jax.block_until_ready(model)
    print(f"  after round 1: {len(first)} sites in the ledger; after round 2: "
          f"staleness {sorted(session.sites.items())}")
    check(straggler not in first and len(first) == straggler,
          "round 1 ran without the straggler")
    check(set(session.sites) == set(range(PARTITIONS))
          and all(v == 0 for v in session.sites.values()),
          "round 2 brought the straggler's backlog in; every site fresh")
    check(model.train_errors.shape[0] == x_train.shape[1],
          "the live model holds every training sample's error")
    check(all(bool(jnp.isfinite(w).all()) for w in model.weights),
          "async model weights finite")
    held = np.asarray(engine.scores(model, x_dev))
    cen = np.asarray(engine.scores(central, x_dev))
    print(f"  held-out score median: async {np.median(held):.5f}, "
          f"centralized {np.median(cen):.5f}")


# ---------------------------------------------------------------------------
# Four-chip phases
# ---------------------------------------------------------------------------

def _spread(tree) -> tuple[set, set]:
    """(devices each leaf spans, leading-axis rows each device holds) over
    every shard of every leaf."""
    leaves = jax.tree.leaves(tree)
    spans = {len({s.device for s in leaf.addressable_shards}) for leaf in leaves}
    rows = {s.data.shape[0] for leaf in leaves for s in leaf.addressable_shards}
    return spans, rows


def mesh_fleet_phase(clock: Clock, devices: int, k: int = TENANTS,
                     scale: float = 1.0):
    """The K=256 fleet on a 4-device 'tenants' mesh, reduced through the
    tree merge, against the same plan on a one-device mesh."""
    from repro.core import fleet
    from repro.engine import DAEFEngine, ExecutionPlan

    xs, tests = cardio_fleet(k, scale)
    cfg = _config(CARDIO)
    seeds = jnp.zeros(k, jnp.int32)   # shared randomness: one federation
    n_test = min(t.shape[1] for t in tests)
    x_test = np.stack([t[:, :n_test] for t in tests]).astype(np.float32)
    print(f"mesh fleet: {k} cardio tenants on a {devices}-device 'tenants' "
          "mesh, against one chip")
    results = {}
    for name, d in (("one chip", 1), ("mesh", devices)):
        engine = DAEFEngine(cfg, ExecutionPlan(mode="mesh", tenants=k,
                                               mesh_devices=d,
                                               stats_backend="fused",
                                               merge="tree"))
        with clock(f"mesh fleet: fit + scores + reduce, {name}"):
            fl = engine.fit(xs, seeds=seeds)
            scores = engine.scores(fl, x_test)
            merged = engine.reduce(fl, group_size=k)
            jax.block_until_ready((fl.model, scores, merged.model))
        results[name] = (fl, np.asarray(scores), merged)
    spans, rows = _spread(results["mesh"][0].model)
    print(f"  mesh fleet leaves span {sorted(spans)} device(s), holding "
          f"{sorted(rows)} tenant(s) per device")
    check(spans == {devices} and rows == {k // devices},
          f"every leaf spread {k // devices} tenants per device")
    (fl1, s1, m1), (fl4, s4, m4) = results["one chip"], results["mesh"]
    err = float(np.abs(np.asarray(fl4.model.train_errors)
                       / np.asarray(fl1.model.train_errors) - 1).max())
    srel = float(np.abs(s4 / s1 - 1).max())
    print(f"  per-tenant train errors max rel {err:.2e}, held-out scores "
          f"max rel {srel:.2e}")
    check(err <= MESH_RTOL and srel <= MESH_RTOL,
          f"mesh fleet equals the one-chip fleet within {MESH_RTOL}")
    merged = [fleet.get_model(m, 0) for m in (m4, m1)]
    x_all = jnp.asarray(np.concatenate(list(x_test), axis=1))
    scores = [DAEFEngine(cfg).scores(m, x_all) for m in merged]
    compare_models("tree-merged fleet, 4 devices vs 1", *merged, scores)


def mesh_data_phase(clock: Clock, devices: int, scale: float = 1.0):
    """creditcard with its sample axis sharded over a 4-device 'data' mesh,
    against the one-chip fit of the same samples."""
    from repro.engine import DAEFEngine, ExecutionPlan

    x_train, x_test = creditcard(scale)
    x_train = x_train[:, : x_train.shape[1] - x_train.shape[1] % devices]
    x_test = jnp.asarray(x_test)
    cfg = _config(CREDITCARD)
    print(f"mesh data: creditcard, {x_train.shape[1]} samples sharded over a "
          f"{devices}-device 'data' mesh, against one chip")
    one = DAEFEngine(cfg, ExecutionPlan(stats_backend="einsum"))
    with clock("mesh data: one-chip fit"):
        ref = one.fit(jnp.asarray(x_train))
        ref_scores = one.scores(ref, x_test)
        jax.block_until_ready((ref, ref_scores))
    engine = DAEFEngine(cfg, ExecutionPlan(mode="mesh", mesh_axes=("data",),
                                           mesh_devices=devices,
                                           stats_backend="einsum"))
    with clock("mesh data: sharded fit"):
        model = engine.fit(jnp.asarray(x_train))
        scores = one.scores(model, x_test)
        jax.block_until_ready((model, scores))
    spans, rows = _spread(model.train_errors)
    print(f"  train errors span {sorted(spans)} device(s), {sorted(rows)} "
          "samples per device")
    check(spans == {devices} and rows == {x_train.shape[1] // devices},
          "train errors stay sharded over the data axis")
    compare_models("data-sharded fit vs one-chip fit", model, ref,
                   (scores, ref_scores))


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip phases (tenant-mesh "
                         "fleet + tree merge, data-sharded fit)")
    args = ap.parse_args()

    cache_dir = compile_cache.enable()
    jax.monitoring.register_event_time_span_listener(_on_span)
    jax.monitoring.register_event_listener(_on_event)
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform {dev.platform}, kind {dev.device_kind!r}, "
          f"count {len(devices)}; jax {jax.__version__}; compile cache "
          f"{cache_dir}")
    if dev.platform != "tpu":
        print("device: no TPU found — this smoke test runs only on the chip",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"device: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    clock = Clock()
    if args.chips == 1:
        x_train, x_test, central = fit_phase(clock)
        serve_phase(clock)
        federate_phase(clock, x_train, x_test, central)
    else:
        mesh_fleet_phase(clock, args.chips)
        mesh_data_phase(clock, args.chips)
    t = clock.totals
    print(f"report: trace+lower {t['trace']:.2f} s, compile {t['compile']:.2f} "
          f"s, run {t['run']:.2f} s; persistent cache hits {_cache['hits']} "
          f"of {_cache['requests']} compile requests")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
