"""Serving launcher: LM decode, a DAEF fleet scorer, or async federation.

Three modes share this entry point:

* LM serve (default) — prefill a batch of prompts, then decode tokens; the
  CPU demo of the serve path (prefill + KV-cache decode) used by the
  decode-shape dry-runs.  Greedy sampling over synthetic prompts.
* Fleet serve (``--fleet K``) — train K per-tenant DAEF anomaly detectors in
  one vmap dispatch, then serve rounds of ragged per-tenant request batches.
  ``--packing continuous`` (default) routes them through the production
  serving layer (`repro.serving.FleetServer`): requests pack into dense
  tenant x sample tiles, scores+flags come back in one fused dispatch per
  tile, repeated samples against an unchanged tenant hit the score cache.
  ``--packing pad`` keeps the pad-to-max baseline: every round padded to
  [K, m0, n_pad] and scored + thresholded for the whole fleet (scores of
  padding columns are NaN-masked).
* Async federation (``--async-rounds R``) — drive a continual
  ``FederationSession`` over ``--sites`` edge sites where a ``--straggle``
  fraction of sites misses each round: stragglers fall out of the live
  global model once past ``--max-staleness`` and rejoin with their full
  backlog on their next report (see docs/federation.md).

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --batch 4 --prompt-len 32 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --fleet 32 --rounds 20
  PYTHONPATH=src python -m repro.launch.serve --async-rounds 6 --sites 8 \
      --straggle 0.25 --max-staleness 1
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.data import synthetic
from repro.models import get_bundle


def run_fleet(args) -> None:
    """Train + serve a fleet of per-tenant anomaly detectors.

    Everything goes through the unified engine facade: placement
    (``--mesh-tenants``) and the stats backend are ExecutionPlan fields, not
    different call paths.
    """
    from repro.core import daef, fleet_sharded
    from repro.engine import DAEFEngine, ExecutionPlan, PlanError
    from repro.serving import metrics as serving_metrics

    k, n_pad = args.fleet, args.pad
    datasets = [
        synthetic.make_dataset("cardio", seed=t, scale=args.scale) for t in range(k)
    ]
    splits = [ds.train_test_split(fold=0) for ds in datasets]
    n_train = min(s[0].shape[1] for s in splits)
    xs_train = np.stack([s[0][:, :n_train] for s in splits]).astype(np.float32)
    m0 = xs_train.shape[1]

    cfg = daef.DAEFConfig(layer_sizes=(m0, 4, 8, m0), lam_hidden=0.9, lam_last=0.9)
    try:
        plan = ExecutionPlan(
            mode="mesh" if args.mesh_tenants else "vmap",
            tenants=k,
            mesh_devices=args.mesh_tenants or None,
            stats_backend=args.stats_backend,
            chunk_samples=args.chunk_samples or None,
        )
        engine = DAEFEngine(cfg, plan)
    except PlanError as e:  # bad mesh sizes etc. -> clean CLI error
        raise SystemExit(f"error: {e}") from e
    print(f"fleet: Gram-stats backend '{engine.config.stats_backend}'")
    if engine.mesh is not None:
        d = engine.mesh.shape[fleet_sharded.TENANT_AXIS]
        print(f"fleet: sharding {k} tenants over a {d}-device '"
              f"{fleet_sharded.TENANT_AXIS}' mesh axis ({k // d} per device)")

    t0 = time.perf_counter()
    if args.chunk_samples:
        # Streaming plan: the host iterator feeds fixed-shape [K, m0, chunk]
        # chunks into the engine — the training data never sits on device as
        # one array (chunked plans also stream engine.fit; fit_stream is the
        # data-never-fits-at-once entry point).
        c = args.chunk_samples
        fl = engine.fit_stream(
            lambda: (xs_train[:, :, i:i + c] for i in range(0, n_train, c)),
            seeds=jnp.arange(k),
        )
        how = f"streamed in {c}-sample chunks"
    else:
        # Mesh plans place the host-built batch BY SHARDING: each device
        # pulls only its K/D tenant slice, never a full replicated copy.
        fl = engine.fit(xs_train, seeds=jnp.arange(k))
        how = "in one dispatch"
    jax.block_until_ready(fl.model.train_errors)
    t_fit = time.perf_counter() - t0
    mus = engine.thresholds(fl, rule="q90")
    print(f"fleet: trained {k} tenant models [{m0} features, {n_train} samples] "
          f"{how} ({t_fit:.2f}s incl. JIT)")

    # Serving loop: ragged tenant request batches — either through the
    # continuous-batching FleetServer (production path) or the pad-to-max
    # baseline (one [K, m0, n_pad] dispatch per round).
    server = None
    if args.packing == "continuous":
        from repro.serving import FleetServer

        server = FleetServer(engine, fl, tile_width=args.tile_width,
                             rule="q90")
        n_shapes = server.warmup()
        print(f"fleet: pre-traced {n_shapes} tile shapes "
              "(no serving-path compiles)")
    rng = np.random.default_rng(0)
    round_served = []
    flagged = 0
    lat = []
    for _ in range(args.rounds):
        counts = rng.integers(1, n_pad + 1, size=k)
        requests = []
        for t in range(k):
            x_test = splits[t][1]
            # A tenant's request burst can't exceed its test pool when
            # sampling without replacement.
            counts[t] = min(int(counts[t]), x_test.shape[1])
            idx = rng.choice(x_test.shape[1], size=counts[t], replace=False)
            requests.append(x_test[:, idx].astype(np.float32))
        if server is not None:
            t0 = time.perf_counter()
            rids = [server.submit(t, requests[t]) for t in range(k)]
            server.flush()
            results = [server.take(rid) for rid in rids]
            lat.append(time.perf_counter() - t0)
            flagged += int(sum(r.flags.sum() for r in results))
        else:
            batch = np.zeros((k, m0, n_pad), np.float32)
            for t in range(k):
                batch[t, :, : counts[t]] = requests[t]
            t0 = time.perf_counter()
            scores = engine.scores(fl, batch, n_valid=jnp.asarray(counts))
            flags = engine.classify(scores, mus)
            jax.block_until_ready(flags)
            lat.append(time.perf_counter() - t0)
            flagged += int(flags.sum())
        round_served.append(int(counts.sum()))
    # Steady-state stats exclude round 0 (JIT warm-up) from the time, the
    # percentiles AND the served-request count — one denominator for all
    # three (unless a single round ran).
    steady = slice(1, None) if len(lat) > 1 else slice(None)
    summary = serving_metrics.latency_summary(
        lat[steady], sum(round_served[steady])
    )
    how = (f"continuous batching, <= {args.tile_width}-wide dense tiles"
           if server is not None
           else f"{k} tenants x <= {n_pad} padded samples per dispatch")
    print(f"served {summary['served']} requests over {summary['rounds']} "
          f"steady-state rounds (+1 warm-up; {how})")
    print(f"latency p50 {summary['p50_ms_per_round']:.2f} / "
          f"p95 {summary['p95_ms_per_round']:.2f} ms/round; "
          f"throughput {summary['scores_per_sec']:.0f} scores/sec "
          f"(steady-state); flagged {flagged} anomalies")
    if server is not None:
        s = server.stats
        print(f"serving: {s['dispatches']} tile dispatches, "
              f"{s['dispatched_cols']} dispatched columns for "
              f"{s['scored']} scored samples, "
              f"{s['cache_hit_cols']} cache-hit columns")
    assert bool(jnp.isfinite(fl.model.train_errors).all()), "non-finite fit"
    print("fleet serve OK")


def run_async(args) -> None:
    """Drive a continual async federation over straggling edge sites.

    Every round each site produces a fresh data block, but only a random
    (1 - ``--straggle``) subset reports; the rest bank their blocks as a
    backlog and submit it whole on their next report (delta replay).  The
    session rebuilds the live global model from whichever sites are within
    ``--max-staleness`` refreshes — no barrier ever blocks a round.
    """
    from repro.core import daef
    from repro.engine import DAEFEngine, ExecutionPlan, PlanError

    s_count = args.sites
    datasets = [
        synthetic.make_dataset("cardio", seed=t, scale=args.scale)
        for t in range(s_count)
    ]
    splits = [ds.train_test_split(fold=0) for ds in datasets]
    m0 = splits[0][0].shape[0]
    cfg = daef.DAEFConfig(layer_sizes=(m0, 4, 8, m0), lam_hidden=0.9,
                          lam_last=0.9)
    privacy = _privacy_spec(args)
    max_staleness = args.max_staleness
    if privacy is not None and privacy.secagg and max_staleness:
        # Masked aggregation hides per-site states from the broker, so
        # stale sites cannot be excluded — the plan would reject the combo.
        print("secagg: forcing max_staleness=0 (masked aggregation cannot "
              "exclude stale sites)")
        max_staleness = 0
    args.max_staleness = max_staleness
    try:
        plan = ExecutionPlan(federation="async", merge="pairwise",
                             max_staleness=max_staleness,
                             privacy=privacy)
        engine = DAEFEngine(cfg, plan)
    except PlanError as e:
        raise SystemExit(f"error: {e}") from e
    session = engine.session()
    print(f"async federation: {s_count} sites, straggle fraction "
          f"{args.straggle}, max_staleness {max_staleness}")
    if privacy is not None:
        print(f"privacy: dp epsilon={privacy.epsilon} delta={privacy.delta} "
              f"clip={privacy.clip}, secagg={privacy.secagg}")

    # Pre-slice each site's train pool into one block per round.
    rounds = args.async_rounds
    blocks = []
    for x_train in (s[0] for s in splits):
        bounds = np.linspace(0, x_train.shape[1], rounds + 1).astype(int)
        blocks.append([
            x_train[:, bounds[r]:bounds[r + 1]].astype(np.float32)
            for r in range(rounds)
        ])

    rng = np.random.default_rng(0)
    backlog: list[list] = [[] for _ in range(s_count)]
    for r in range(rounds):
        report = rng.random(s_count) >= args.straggle
        if not report.any():
            report[rng.integers(s_count)] = True  # someone always reports
        parts = {}
        for t in range(s_count):
            backlog[t].append(blocks[t][r])
            if report[t]:
                # The site ships its whole backlog: missed blocks replay as
                # one delta the moment it comes back.
                parts[t] = np.concatenate(backlog[t], axis=1)
                backlog[t] = []
        t0 = time.perf_counter()
        model = session.round(parts)
        jax.block_until_ready(model.weights[-1])
        dt = time.perf_counter() - t0
        fresh = sum(
            stale <= args.max_staleness for stale in session.sites.values()
        )
        print(f"round {r + 1}/{rounds}: {len(parts)}/{s_count} sites "
              f"reported, {fresh} fresh in the live model "
              f"({dt * 1e3:.0f} ms)")

    # One global model scores every site's held-out split.
    mses = [
        float(jnp.mean(daef.reconstruction_error(
            cfg, session.model, jnp.asarray(s[1].astype(np.float32))
        )))
        for s in splits
    ]
    print(f"held-out reconstruction MSE across {s_count} sites: "
          f"mean {np.mean(mses):.4f} (min {min(mses):.4f}, "
          f"max {max(mses):.4f})")
    if privacy is not None and privacy.dp_enabled:
        eps_spent = [session.privacy_spent(t)[0] for t in range(s_count)]
        print(f"privacy: cumulative epsilon spent per site — "
              f"min {min(eps_spent):.2f}, max {max(eps_spent):.2f}")
    assert bool(jnp.isfinite(session.model.weights[-1]).all()), \
        "non-finite model"
    print("async federation OK")


def _privacy_spec(args):
    """Build a PrivacySpec from the --dp-*/--secagg flags, or None when the
    privacy tier is off (plain exchanges, bit-exact with the old paths)."""
    if args.dp_epsilon is None and not args.secagg:
        return None
    from repro.privacy import PrivacySpec

    return PrivacySpec(
        epsilon=args.dp_epsilon,
        delta=args.dp_delta,
        clip=args.dp_clip,
        secagg=args.secagg,
    )


def run_privacy_smoke(args) -> None:
    """CI smoke of the privacy tier end to end: a DP-calibrated federated
    fit at epsilon=8 and one secagg-masked round checked against the
    unmasked merge (docs/privacy.md)."""
    from repro.core import daef
    from repro.engine import DAEFEngine, ExecutionPlan
    from repro.privacy import PrivacySpec

    ds = synthetic.make_dataset("cardio", seed=0, scale=args.scale)
    split = ds.train_test_split(fold=0)
    x_train, x_test = split[0], split[1]
    m0 = x_train.shape[0]
    half = x_train.shape[1] // 2
    parts = {"a": x_train[:, :half].astype(np.float32),
             "b": x_train[:, half:].astype(np.float32)}
    cfg = daef.DAEFConfig(layer_sizes=(m0, 4, 8, m0), lam_hidden=0.9,
                          lam_last=0.9)

    # 1. DP release at epsilon=8: every exchanged block noised, finite model.
    t0 = time.perf_counter()
    engine = DAEFEngine(cfg, ExecutionPlan(
        federation="async", merge="pairwise", privacy=PrivacySpec(epsilon=8.0)
    ))
    session = engine.session()
    model = session.round(parts)
    jax.block_until_ready(model.weights[-1])
    assert bool(jnp.isfinite(model.weights[-1]).all()), "non-finite DP model"
    mse = float(jnp.mean(daef.reconstruction_error(
        cfg, model, jnp.asarray(x_test.astype(np.float32))
    )))
    eps, delta = session.privacy_spent("a")
    print(f"privacy smoke: DP fit at epsilon=8 over {len(parts)} sites "
          f"({time.perf_counter() - t0:.2f}s incl. JIT) — held-out MSE "
          f"{mse:.4f}, per-site spend ({eps:.1f}, {delta:.1e})")

    # 2. One secagg round: masked aggregate must match the unmasked merge.
    t0 = time.perf_counter()
    masked = DAEFEngine(cfg, ExecutionPlan(
        federation="async", merge="pairwise", privacy=PrivacySpec(secagg=True)
    )).session().round(parts)
    plain = DAEFEngine(cfg, ExecutionPlan(
        federation="async", merge="pairwise"
    )).session().round(parts)
    for wm, wp in zip(masked.weights, plain.weights):
        np.testing.assert_allclose(np.asarray(wm), np.asarray(wp),
                                   atol=5e-4, rtol=1e-3)
    print(f"privacy smoke: secagg round matches unmasked merge "
          f"({time.perf_counter() - t0:.2f}s)")
    print("privacy smoke OK")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, choices=sorted(registry.ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--fleet", type=int, default=0,
                    help="serve a DAEF fleet of this many tenants instead of an LM")
    ap.add_argument("--mesh-tenants", type=int, default=0,
                    help="fleet mode: shard the tenant axis over this many "
                         "devices (NamedSharding on a 'tenants' mesh axis)")
    ap.add_argument("--packing", default="continuous",
                    choices=["continuous", "pad"],
                    help="fleet mode: request batching — 'continuous' "
                         "(production serving layer: dense tenant x sample "
                         "tiles, score cache, online thresholds) or 'pad' "
                         "(baseline: every round padded to [K, m0, --pad] "
                         "and dispatched fleet-wide)")
    ap.add_argument("--tile-width", type=int, default=32,
                    help="fleet mode, continuous packing: max samples per "
                         "tile slot")
    ap.add_argument("--pad", type=int, default=64,
                    help="fleet mode: per-tenant sample padding per dispatch")
    ap.add_argument("--rounds", type=int, default=10,
                    help="fleet mode: number of serving rounds")
    ap.add_argument("--scale", type=float, default=0.25,
                    help="fleet mode: synthetic dataset scale")
    ap.add_argument("--stats-backend", default=None,
                    choices=["einsum", "fused", "auto"],
                    help="fleet mode: Gram-stats producer (default: "
                         "$REPRO_STATS_BACKEND or 'auto', which picks the "
                         "measured winner from the committed autotune cache "
                         "for this platform; 'fused' forces training stats "
                         "through the Pallas rolann_stats kernels — "
                         "interpret mode on CPU)")
    ap.add_argument("--chunk-samples", type=int, default=0,
                    help="fleet mode: train with a streaming (chunked) "
                         "ExecutionPlan — per-layer Gram stats accumulate "
                         "over sample chunks of this width via "
                         "engine.fit_stream, bounding training memory")
    ap.add_argument("--async-rounds", type=int, default=0,
                    help="drive this many continual async federation rounds "
                         "(ExecutionPlan(federation='async')) instead of an "
                         "LM or a fleet")
    ap.add_argument("--sites", type=int, default=8,
                    help="async mode: number of federated edge sites")
    ap.add_argument("--straggle", type=float, default=0.25,
                    help="async mode: fraction of sites that (randomly) miss "
                         "each round; they bank a backlog and replay it as "
                         "one delta on their next report")
    ap.add_argument("--max-staleness", type=int, default=1,
                    help="async mode: refresh rounds a site may lag before "
                         "it is excluded from the live global model")
    ap.add_argument("--dp-epsilon", type=float, default=None,
                    help="async mode: release every exchanged statistics "
                         "block under the Gaussian mechanism at this "
                         "per-round epsilon (default: no DP)")
    ap.add_argument("--dp-delta", type=float, default=1e-5,
                    help="async mode: DP delta for --dp-epsilon")
    ap.add_argument("--dp-clip", type=float, default=1.0,
                    help="async mode: per-sample L2 clip bound for the DP "
                         "release")
    ap.add_argument("--secagg", action="store_true",
                    help="async mode: pairwise-masked secure aggregation — "
                         "the broker only ever sees the round aggregate "
                         "(forces --max-staleness 0 semantics)")
    ap.add_argument("--privacy", action="store_true",
                    help="run the privacy-tier smoke instead of an LM/fleet: "
                         "a DP fit at epsilon=8 plus one secagg round "
                         "checked against the unmasked merge")
    args = ap.parse_args()

    # NOTE: several flags use 0 as their "mode/feature off" sentinel — the
    # messages state the accepted domain EXACTLY (a message promising
    # ">= 1" while the check admits 0 lies to the user; tests/
    # test_serve_cli.py pins message <-> check agreement).
    if args.fleet < 0:
        ap.error(f"--fleet must be a tenant count >= 1, or 0 to serve an "
                 f"LM instead; got {args.fleet}")
    if args.mesh_tenants < 0:
        ap.error(f"--mesh-tenants must be >= 1, or 0 to disable tenant "
                 f"sharding; got {args.mesh_tenants}")
    if args.mesh_tenants and not args.fleet:
        ap.error("--mesh-tenants only applies to --fleet mode")
    if args.stats_backend and not args.fleet:
        ap.error("--stats-backend only applies to --fleet mode")
    if args.chunk_samples and not args.fleet:
        ap.error("--chunk-samples only applies to --fleet mode")
    if args.chunk_samples < 0:
        ap.error(f"--chunk-samples must be >= 1, or 0 for one-shot "
                 f"(non-streaming) training; got {args.chunk_samples}")
    if args.fleet and args.rounds < 1:
        ap.error(f"--rounds must be >= 1, got {args.rounds}")
    if args.fleet and args.tile_width < 1:
        ap.error(f"--tile-width must be >= 1, got {args.tile_width}")
    if args.async_rounds < 0:
        ap.error(f"--async-rounds must be >= 1, or 0 for LM/fleet mode; "
                 f"got {args.async_rounds}")
    if args.async_rounds and args.fleet:
        ap.error("--async-rounds and --fleet are separate modes; pick one")
    if args.dp_epsilon is not None and args.dp_epsilon <= 0:
        ap.error(f"--dp-epsilon must be > 0, got {args.dp_epsilon}")
    if (args.dp_epsilon is not None or args.secagg) and not (
        args.async_rounds or args.privacy
    ):
        ap.error("--dp-epsilon/--secagg apply to --async-rounds federation "
                 "(or the --privacy smoke)")
    if args.privacy and (args.fleet or args.async_rounds):
        ap.error("--privacy is a standalone smoke mode; drop --fleet/"
                 "--async-rounds")
    if args.privacy:
        run_privacy_smoke(args)
        return
    if args.async_rounds:
        if args.sites < 1:
            ap.error(f"--sites must be >= 1, got {args.sites}")
        if not 0.0 <= args.straggle < 1.0:
            ap.error(f"--straggle must be in [0, 1), got {args.straggle}")
        if args.max_staleness < 0:
            ap.error(f"--max-staleness must be >= 0, got {args.max_staleness}")
        run_async(args)
        return
    if args.fleet:
        run_fleet(args)
        return
    if args.arch is None:
        ap.error("--arch is required unless --fleet is given")

    cfg = registry.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    bundle = get_bundle(cfg, chunked_attn=False)
    params = bundle.init(jax.random.PRNGKey(0))

    prompts = jnp.asarray(
        synthetic.lm_token_stream(cfg.vocab_size, args.prompt_len, args.batch, seed=1)
    )
    max_len = args.prompt_len + args.gen

    if cfg.family == "encdec":
        from repro.models import encdec

        frames = jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, cfg.encoder_seq, cfg.d_model)
        )
        enc_out = encdec.encode(params, cfg, frames)
        cache = encdec.init_cache(params, cfg, enc_out, max_len, jnp.float32)
    else:
        cache = bundle.init_cache(args.batch, max_len, jnp.float32)

    decode = jax.jit(bundle.decode, donate_argnums=(1,))

    # Prefill by stepping the prompt through the decode path (exercises the
    # same cache-update the decode dry-run lowers).
    t0 = time.time()
    logits = None
    for t in range(args.prompt_len):
        logits, cache = decode(params, cache, prompts[:, t : t + 1], jnp.asarray(t))
    t_prefill = time.time() - t0

    generated = []
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    t0 = time.time()
    for t in range(args.prompt_len, max_len):
        generated.append(tok)
        logits, cache = decode(params, cache, tok, jnp.asarray(t))
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    jax.block_until_ready(logits)
    t_gen = time.time() - t0

    gen = jnp.concatenate(generated, axis=1)
    print(f"prompts [{args.batch}, {args.prompt_len}] -> generated {gen.shape}")
    print("first sequence:", gen[0].tolist())
    print(f"prefill {t_prefill:.2f}s; decode {t_gen / max(1, args.gen) * 1000:.1f} ms/token")
    assert bool(jnp.isfinite(logits).all()), "non-finite logits"
    print("serve OK")


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
