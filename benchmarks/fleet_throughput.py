"""Fleet throughput: the engine's loop vs vmap vs mesh plans, one facade.

The benchmark is now literally a comparison of ``ExecutionPlan``s — the same
``DAEFEngine`` API runs every path:

* ``loop``  — ``ExecutionPlan(mode="loop")``: eager per-model calls, the
  status-quo API before the fleet engine existed;
* ``jit_loop`` — the strongest sequential contender: the single-model core
  jitted ONCE and reused across tenants (identical shapes, so the loop pays
  only dispatch overhead, not retracing) — kept as a manual baseline outside
  the facade;
* ``vmap``  — ``ExecutionPlan(mode="vmap")``: every tenant in one jitted
  dispatch;
* ``mesh``  — ``ExecutionPlan(mode="mesh")``: the same kernel with the
  tenant axis sharded over a 'tenants' device-mesh axis (run under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to see it on a
  laptop), plus the on-mesh tree-reduce federation (``merge="tree"``).

Reported numbers: models/sec (training) and scores/sec (serving), plus the
fleet speedups.  The full record is written as JSON (``--out``, default
``BENCH_fleet.json`` at the *repo root* so bench runs accumulate the
committed perf trajectory; CI archives the same file as an artifact).

  PYTHONPATH=src python benchmarks/fleet_throughput.py [--tenants 64]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import daef
from repro.engine import DAEFEngine, ExecutionPlan

REPO_ROOT = Path(__file__).resolve().parent.parent


def _timed(f, *args, repeats: int = 3):
    """Best-of-N wall time of f(*args) with synchronization."""
    best = float("inf")
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = f(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return out, best


def main(k: int = 64, m0: int = 16, n: int = 256, repeats: int = 3) -> dict:
    cfg = daef.DAEFConfig(layer_sizes=(m0, 4, 8, m0), lam_hidden=0.5, lam_last=0.9)
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(k, m0, n)), jnp.float32)
    seeds = jnp.arange(k, dtype=jnp.int32)

    # ---- engine plans: one facade, three placements ----
    n_dev = len(jax.devices())
    d = n_dev
    while d > 1 and k % d:
        d //= 2
    engines = {
        "loop": DAEFEngine(cfg, ExecutionPlan(mode="loop", tenants=k)),
        "vmap": DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=k)),
        "mesh": DAEFEngine(cfg, ExecutionPlan(mode="mesh", tenants=k,
                                              mesh_devices=d, merge="tree")),
    }

    # ---- loop plan (status-quo API: eager per-model daef.fit) ----
    eng_loop = engines["loop"]
    eng_loop.fit(xs, seeds=seeds)  # warm the trace caches of the eager core
    fl_loop, t_eager = _timed(
        lambda: eng_loop.fit(xs, seeds=seeds), repeats=max(1, repeats - 2)
    )

    # ---- per-model loop, jitted once and reused for every tenant ----
    # (manual baseline: the facade has no "jit the scalar core yourself"
    # plan; this is what a careful user could hand-write.)
    @jax.jit
    def fit_one(x, seed):
        keys = daef.layer_keys_from_seed(seed, len(cfg.layer_sizes))
        return daef._fit_core(cfg, x, keys, cfg.lam_hidden, cfg.lam_last)

    fit_one(xs[0], seeds[0])  # compile

    def loop_fit(xs, seeds):
        return [fit_one(xs[i], seeds[i]) for i in range(k)]

    _, t_loop = _timed(loop_fit, xs, seeds, repeats=repeats)

    # ---- vmap plan ----
    eng_vmap = engines["vmap"]
    eng_vmap.fit(xs, seeds=seeds)  # compile
    fl, t_fleet = _timed(lambda: eng_vmap.fit(xs, seeds=seeds), repeats=repeats)

    # sanity: same models up to float error across plans
    ref = eng_vmap.get_model(fl, 3)
    np.testing.assert_allclose(
        np.asarray(ref.weights[-1]),
        np.asarray(eng_loop.get_model(fl_loop, 3).weights[-1]), atol=1e-4,
    )

    # ---- serving: score a padded tenant batch ----
    from functools import partial

    score_one = jax.jit(partial(daef.reconstruction_error, cfg))
    models = [eng_loop.get_model(fl_loop, i) for i in range(k)]
    score_one(models[0], xs[0])  # compile

    def loop_score(models, xs):
        return [score_one(models[i], xs[i]) for i in range(k)]

    _, ts_loop = _timed(loop_score, models, xs, repeats=repeats)
    eng_vmap.scores(fl, xs)  # compile
    _, ts_fleet = _timed(lambda: eng_vmap.scores(fl, xs), repeats=repeats)

    # ---- mesh plan: same kernels, tenant axis split over devices ----
    eng_mesh = engines["mesh"]
    xs_host = np.asarray(xs)

    eng_mesh.fit(xs_host, seeds=seeds)  # compile
    fl_sh, t_sharded = _timed(
        lambda: eng_mesh.fit(xs_host, seeds=seeds), repeats=repeats
    )

    eng_mesh.scores(fl_sh, xs_host)  # compile
    _, ts_sharded = _timed(
        lambda: eng_mesh.scores(fl_sh, xs_host), repeats=repeats
    )

    # on-mesh tree-reduce federation (all tenants share seed 0 for the bench)
    fl_m = eng_mesh.fit(xs_host)
    local_k = k // d
    group = min(8, k & -k)  # largest power of two dividing k, capped at 8
    while group > 1 and not (
        local_k % group == 0
        or (group % local_k == 0 and local_k & (local_k - 1) == 0)
    ):
        group //= 2
    if group > 1:
        eng_mesh.reduce(fl_m, group)  # compile
        _, t_merge_tree = _timed(
            lambda: eng_mesh.reduce(fl_m, group), repeats=repeats
        )
    else:
        # group_size=1 is a no-op by contract — a timing of it would record
        # a bogus merge throughput in the archived JSON.
        print(f"merge_tree: no power-of-two group tiles k={k} on {d} "
              "device(s); skipping merge benchmark")
        t_merge_tree = None

    result = {
        "api": "repro.engine.DAEFEngine",
        "devices": n_dev,
        "mesh_tenant_devices": d,
        "tenants": k,
        "train_models_per_sec_loop": k / t_eager,
        "train_models_per_sec_jit_loop": k / t_loop,
        "train_models_per_sec_fleet": k / t_fleet,
        "train_speedup_vs_loop": t_eager / t_fleet,
        "train_speedup_vs_jit_loop": t_loop / t_fleet,
        "train_models_per_sec_sharded": k / t_sharded,
        "train_speedup_sharded_vs_jit_loop": t_loop / t_sharded,
        "score_samples_per_sec_loop": k * n / ts_loop,
        "score_samples_per_sec_fleet": k * n / ts_fleet,
        "score_samples_per_sec_sharded": k * n / ts_sharded,
        "score_speedup": ts_loop / ts_fleet,
        "merge_tree_group_size": group if t_merge_tree else None,
        "merge_tree_models_per_sec": k / t_merge_tree if t_merge_tree else None,
    }
    print("metric,loop,jit_loop,fleet,sharded,speedup_vs_loop,speedup_vs_jit_loop")
    print(f"train_models_per_sec,{k / t_eager:.1f},{k / t_loop:.1f},"
          f"{k / t_fleet:.1f},{k / t_sharded:.1f},"
          f"{t_eager / t_fleet:.1f}x,{t_loop / t_fleet:.1f}x")
    print(f"score_samples_per_sec,-,{k * n / ts_loop:.0f},"
          f"{k * n / ts_fleet:.0f},{k * n / ts_sharded:.0f},-,"
          f"{ts_loop / ts_fleet:.1f}x")
    if t_merge_tree:
        print(f"merge_tree[g={group}]_models_per_sec,-,-,-,"
              f"{k / t_merge_tree:.1f},-,-")
    return result


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tenants", type=int, default=64)
    ap.add_argument("--features", type=int, default=16)
    ap.add_argument("--samples", type=int, default=256)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_fleet.json"),
                    help="write the result record to this JSON file "
                         "(default: repo root, committed per PR)")
    a = ap.parse_args()
    record = main(k=a.tenants, m0=a.features, n=a.samples, repeats=a.repeats)
    if a.out:
        with open(a.out, "w") as fh:
            json.dump(record, fh, indent=2, sort_keys=True)
        print(f"wrote {a.out}")
