"""Serving latency microbenchmark.

Four sections:

* **DAEF fleet serving (default)** — the `repro.engine` facade end to end:
  train K per-tenant anomaly detectors under an ``ExecutionPlan`` (vmap, and
  mesh when more than one device is visible), then measure per-round scoring
  latency over padded ragged request batches — p50/p95 ms/round and
  scores/sec, the numbers `launch/serve.py --fleet` prints, measured
  repeatably.  Percentiles are interpolated (`repro.serving.metrics`), the
  same helper the CLI report uses.
* **Packed vs padded (default)** — continuous batching
  (`repro.serving.FleetServer`) against the pad-to-max baseline at K=32
  under a MIXED RAGGED load (most tenants trickle 1-4 samples, a burst
  cohort sends hundreds): both paths score the identical per-round
  requests, and the continuous record carries its ``speedup_vs_pad``.
* **Per-tile vs deferred readback (default)** — the same continuous-batching
  server with ``readback="per_tile"`` (depth-2 pipeline, one blocking
  device->host transfer per tile) against ``readback="deferred"``
  (scores/flags stay device-resident; one batched ``block_until_ready`` +
  readback at flush) under the identical mixed-ragged load.
* **LM decode (``--lm``)** — decode ms/token per architecture family (CPU,
  reduced configs), the host-measurable counterpart of the decode-shape
  rooflines.

Each run APPENDS its records to the in-tree trajectory ``BENCH_serve.json``
(a JSON list, committed per PR so the serving-latency history accumulates;
CI uploads it as an artifact).

  PYTHONPATH=src python benchmarks/serve_latency.py [--tenants 32] [--lm]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.metrics import latency_summary

REPO_ROOT = Path(__file__).resolve().parent.parent

ARCHS = ["qwen2-1.5b", "qwen2-moe-a2.7b", "mamba2-780m", "recurrentgemma-9b",
         "deepseek-v2-236b"]


def fleet_records(k: int = 32, m0: int = 16, n_train: int = 256,
                  n_pad: int = 64, rounds: int = 20) -> list[dict]:
    """Engine-served fleet scoring latency, one record per ExecutionPlan."""
    from repro.core import daef
    from repro.engine import DAEFEngine, ExecutionPlan

    cfg = daef.DAEFConfig(layer_sizes=(m0, 4, 8, m0), lam_hidden=0.9,
                          lam_last=0.9)
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(k, m0, n_train)).astype(np.float32)

    plans = {"vmap": ExecutionPlan(mode="vmap", tenants=k)}
    n_dev = len(jax.devices())
    if n_dev > 1 and k % min(n_dev, k) == 0:
        plans["mesh"] = ExecutionPlan(mode="mesh", tenants=k,
                                      mesh_devices=min(n_dev, k))

    records = []
    for name, plan in plans.items():
        engine = DAEFEngine(cfg, plan)
        fl = engine.fit(xs, seeds=jnp.arange(k))
        mus = engine.thresholds(fl, rule="q90")
        lat, served = [], 0
        for r in range(rounds + 1):  # round 0 = JIT warm-up, excluded
            counts = rng.integers(1, n_pad + 1, size=k)
            batch = np.zeros((k, m0, n_pad), np.float32)
            for t in range(k):
                batch[t, :, : counts[t]] = rng.normal(
                    size=(m0, counts[t])
                ).astype(np.float32)
            t0 = time.perf_counter()
            scores = engine.scores(fl, batch, n_valid=jnp.asarray(counts))
            flags = engine.classify(scores, mus)
            jax.block_until_ready(flags)
            if r:
                lat.append(time.perf_counter() - t0)
                served += int(counts.sum())
        summary = latency_summary(lat, served)
        records.append({
            "api": "repro.engine.DAEFEngine",
            "plan": name,
            "devices": n_dev,
            "tenants": k,
            "pad": n_pad,
            "rounds": rounds,
            "p50_ms_per_round": summary["p50_ms_per_round"],
            "p95_ms_per_round": summary["p95_ms_per_round"],
            "scores_per_sec": summary["scores_per_sec"],
        })
        print(f"fleet[{name}]: p50 {records[-1]['p50_ms_per_round']:.2f} ms/round, "
              f"{records[-1]['scores_per_sec']:.0f} scores/sec "
              f"({n_dev} device(s))")
    return records


def _mixed_ragged_counts(k: int, n_pad: int, seed: int,
                         burst_frac: float = 0.2) -> np.ndarray:
    """A mixed ragged request round: most tenants trickle 1-4 samples, a
    ``burst_frac`` cohort sends ``n_pad/2 .. n_pad`` — the traffic shape
    where pad-to-max dispatches mostly padding."""
    rr = np.random.default_rng(seed)
    counts = rr.integers(1, 5, size=k)
    burst = rr.random(k) < burst_frac
    counts[burst] = rr.integers(n_pad // 2, n_pad + 1, size=int(burst.sum()))
    return counts


def packing_records(k: int = 32, m0: int = 64, n_pad: int = 1024,
                    rounds: int = 20, tile_width: int = 256,
                    burst_frac: float = 0.2) -> list[dict]:
    """Continuous batching vs the pad-to-max baseline, identical loads.

    Both paths score the SAME per-round requests.  The pad path is the old
    serving loop (one ``[K, m0, n_pad]`` padded batch -> engine.scores +
    engine.classify, two dispatches); the continuous path is
    `repro.serving.FleetServer` with the score cache OFF, so the comparison
    is pure packing + dispatch (cache behaviour is covered by unit tests,
    not benchmarked away here).
    """
    from repro.core import daef
    from repro.engine import DAEFEngine, ExecutionPlan
    from repro.serving import FleetServer

    cfg = daef.DAEFConfig(layer_sizes=(m0, 16, 32, m0), lam_hidden=0.9,
                          lam_last=0.9)
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(k, m0, 256)).astype(np.float32)
    engine = DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=k))
    fl = engine.fit(xs, seeds=jnp.arange(k))
    mus = engine.thresholds(fl, rule="q90")

    # Pre-draw every round's requests once: both paths score identical data.
    warm = 2
    loads = []
    for r in range(rounds + warm):
        counts = _mixed_ragged_counts(k, n_pad, seed=100 + r,
                                      burst_frac=burst_frac)
        loads.append([
            rng.normal(size=(m0, c)).astype(np.float32) for c in counts
        ])

    # --- pad-to-max baseline ------------------------------------------
    lat_pad, served = [], 0
    for r, reqs in enumerate(loads):
        counts = np.array([x.shape[1] for x in reqs])
        batch = np.zeros((k, m0, n_pad), np.float32)
        for t in range(k):
            batch[t, :, : counts[t]] = reqs[t]
        t0 = time.perf_counter()
        scores = engine.scores(fl, batch, n_valid=jnp.asarray(counts))
        flags = engine.classify(scores, mus)
        jax.block_until_ready(flags)
        if r >= warm:
            lat_pad.append(time.perf_counter() - t0)
            served += int(counts.sum())
    pad = latency_summary(lat_pad, served)

    # --- continuous batching ------------------------------------------
    server = FleetServer(engine, fl, tile_width=tile_width, rule="q90",
                         use_cache=False)
    server.warmup()  # pre-trace every tile shape: no serving-path compiles
    lat_cb, served_cb = [], 0
    for r, reqs in enumerate(loads):
        t0 = time.perf_counter()
        rids = [server.submit(t, reqs[t]) for t in range(k)]
        server.flush()
        results = [server.take(rid) for rid in rids]
        if r >= warm:
            lat_cb.append(time.perf_counter() - t0)
            served_cb += sum(res.scores.size for res in results)
    cb = latency_summary(lat_cb, served_cb)

    st = server.stats
    density = st["scored"] / max(st["dispatched_cols"], 1)
    speedup = cb["scores_per_sec"] / max(pad["scores_per_sec"], 1e-9)
    shared = {
        "api": "repro.serving",
        "tenants": k,
        "features": m0,
        "pad": n_pad,
        "rounds": rounds,
        "burst_frac": burst_frac,
        "load": "mixed-ragged",
    }
    records = [
        {**shared, "packing": "pad",
         "p50_ms_per_round": pad["p50_ms_per_round"],
         "p95_ms_per_round": pad["p95_ms_per_round"],
         "scores_per_sec": pad["scores_per_sec"]},
        {**shared, "packing": "continuous",
         "tile_width": tile_width,
         "p50_ms_per_round": cb["p50_ms_per_round"],
         "p95_ms_per_round": cb["p95_ms_per_round"],
         "scores_per_sec": cb["scores_per_sec"],
         "dispatches": st["dispatches"],
         "dispatched_cols": st["dispatched_cols"],
         "tile_density": round(density, 4),
         "speedup_vs_pad": round(speedup, 3)},
    ]
    print(f"packing[pad]:        p50 {pad['p50_ms_per_round']:.2f} / "
          f"p95 {pad['p95_ms_per_round']:.2f} ms/round, "
          f"{pad['scores_per_sec']:.0f} scores/sec")
    print(f"packing[continuous]: p50 {cb['p50_ms_per_round']:.2f} / "
          f"p95 {cb['p95_ms_per_round']:.2f} ms/round, "
          f"{cb['scores_per_sec']:.0f} scores/sec "
          f"({density:.0%} tile density, {speedup:.2f}x vs pad)")
    return records


def readback_records(k: int = 32, m0: int = 64, n_pad: int = 1024,
                     rounds: int = 20, tile_width: int = 256,
                     burst_frac: float = 0.2) -> list[dict]:
    """Per-tile vs deferred device-resident readback, identical loads.

    Both paths run the continuous-batching `FleetServer` over the same
    mixed-ragged rounds; the only knob is ``readback``: ``"per_tile"``
    blocks on a host transfer for tile t once t+1 is in flight (the old
    depth-2 pipeline), ``"deferred"`` keeps scores/flags device-resident
    until one batched `flush` readback — the hot loop never pays a
    per-tile device->host sync.
    """
    from repro.core import daef
    from repro.engine import DAEFEngine, ExecutionPlan
    from repro.serving import FleetServer

    cfg = daef.DAEFConfig(layer_sizes=(m0, 16, 32, m0), lam_hidden=0.9,
                          lam_last=0.9)
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(k, m0, 256)).astype(np.float32)
    engine = DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=k))
    fl = engine.fit(xs, seeds=jnp.arange(k))

    warm = 2
    loads = []
    for r in range(rounds + warm):
        counts = _mixed_ragged_counts(k, n_pad, seed=300 + r,
                                      burst_frac=burst_frac)
        loads.append([
            rng.normal(size=(m0, c)).astype(np.float32) for c in counts
        ])

    records = []
    summaries = {}
    for readback in ("per_tile", "deferred"):
        server = FleetServer(engine, fl, tile_width=tile_width, rule="q90",
                             use_cache=False, readback=readback)
        server.warmup()
        lat, served = [], 0
        for r, reqs in enumerate(loads):
            t0 = time.perf_counter()
            rids = [server.submit(t, reqs[t]) for t in range(k)]
            server.flush()
            results = [server.take(rid) for rid in rids]
            if r >= warm:
                lat.append(time.perf_counter() - t0)
                served += sum(res.scores.size for res in results)
        summaries[readback] = latency_summary(lat, served)

    speedup = summaries["deferred"]["scores_per_sec"] / max(
        summaries["per_tile"]["scores_per_sec"], 1e-9)
    shared = {
        "api": "repro.serving",
        "tenants": k,
        "features": m0,
        "pad": n_pad,
        "rounds": rounds,
        "burst_frac": burst_frac,
        "load": "mixed-ragged",
        "packing": "continuous",
        "tile_width": tile_width,
    }
    for readback, s in summaries.items():
        rec = {**shared, "readback": readback,
               "p50_ms_per_round": s["p50_ms_per_round"],
               "p95_ms_per_round": s["p95_ms_per_round"],
               "scores_per_sec": s["scores_per_sec"]}
        if readback == "deferred":
            rec["speedup_vs_per_tile"] = round(speedup, 3)
        records.append(rec)
        print(f"readback[{readback}]: p50 {s['p50_ms_per_round']:.2f} / "
              f"p95 {s['p95_ms_per_round']:.2f} ms/round, "
              f"{s['scores_per_sec']:.0f} scores/sec"
              + (f" ({speedup:.2f}x vs per_tile)"
                 if readback == "deferred" else ""))
    return records


def append_trajectory(records: list[dict], out: str) -> None:
    """Append records to the JSON-list trajectory at ``out``."""
    path = Path(out)
    history = []
    if path.exists():
        try:
            history = json.loads(path.read_text())
            assert isinstance(history, list)
        except (ValueError, AssertionError):
            history = []
    history.extend(records)
    path.write_text(json.dumps(history, indent=2, sort_keys=True) + "\n")
    print(f"appended {len(records)} record(s) -> {out} "
          f"({len(history)} total in trajectory)")


def lm_lines(archs=None, gen: int = 24) -> list[str]:
    from repro.configs import registry
    from repro.models import get_bundle

    lines = ["arch,family,decode_ms_per_token"]
    for name in archs or ARCHS:
        cfg = registry.get(name).reduced()
        bundle = get_bundle(cfg, chunked_attn=False)
        params = bundle.init(jax.random.PRNGKey(0))
        b, s = 4, 64
        cache = bundle.init_cache(b, s, jnp.float32)
        decode = jax.jit(bundle.decode, donate_argnums=(1,))
        tok = jnp.zeros((b, 1), jnp.int32)
        logits, cache = decode(params, cache, tok, jnp.asarray(0))  # compile
        jax.block_until_ready(logits)
        t0 = time.perf_counter()
        for t in range(1, gen + 1):
            logits, cache = decode(params, cache, tok, jnp.asarray(t))
        jax.block_until_ready(logits)
        ms = (time.perf_counter() - t0) / gen * 1e3
        lines.append(f"{name},{cfg.family},{ms:.2f}")
    return lines


def main(archs=None, gen: int = 24) -> list[str]:
    """Back-compat hook (benchmarks.run): the LM decode table."""
    return lm_lines(archs=archs, gen=gen)


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tenants", type=int, default=32)
    ap.add_argument("--pad", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--lm", action="store_true",
                    help="also run the per-arch LM decode table")
    ap.add_argument("--no-packing", action="store_true",
                    help="skip the packed-vs-padded comparison section")
    ap.add_argument("--no-readback", action="store_true",
                    help="skip the per-tile vs deferred readback comparison")
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_serve.json"),
                    help="append fleet-serving records to this JSON-list "
                         "trajectory (default: repo root, committed per PR)")
    args = ap.parse_args()
    recs = fleet_records(k=args.tenants, n_pad=args.pad, rounds=args.rounds)
    if not args.no_packing:
        recs += packing_records(k=args.tenants, rounds=args.rounds)
    if not args.no_readback:
        recs += readback_records(k=args.tenants, rounds=args.rounds)
    if args.out:
        append_trajectory(recs, args.out)
    if args.lm:
        print("\n".join(lm_lines()))
