"""Benchmark aggregator — one section per paper table/figure + roofline.

  table2    — F1 parity, DAEF(3 inits) vs iterative AE      (paper Table 2)
  table3    — training-time ratio DAEF vs AE                (paper Table 3)
  federated — federated == centralized exactness + message sizes (paper §4.3/§5)
  kernels   — Pallas kernel checks vs jnp oracles (interpret mode)
  roofline  — the 40-pair dry-run roofline table            (§Roofline)

``python -m benchmarks.run`` runs a CPU-budget subset (small datasets, few
folds); ``--full`` runs everything.
"""
from __future__ import annotations

import argparse
import time


def section_table2(full: bool) -> list[str]:
    from benchmarks import table2_f1

    datasets = None if full else ["shuttle", "cardio", "ionosphere", "pendigits"]
    return table2_f1.main(datasets=datasets, folds=3 if full else 2)


def section_table3(full: bool) -> list[str]:
    from benchmarks import table3_time

    datasets = None if full else ["shuttle", "cardio", "ionosphere"]
    return table3_time.main(datasets=datasets, folds=2 if full else 1)


def section_federated() -> list[str]:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import daef, federated
    from repro.engine import DAEFEngine, ExecutionPlan

    rng = np.random.default_rng(0)
    z = rng.normal(size=(4, 4000))
    mixed = np.tanh(rng.normal(size=(16, 4)) @ z) + 0.05 * rng.normal(size=(16, 4000))
    x = ((mixed - mixed.mean(1, keepdims=True)) / mixed.std(1, keepdims=True)).astype(
        np.float32
    )
    cfg = daef.DAEFConfig(layer_sizes=(16, 4, 8, 16), lam_hidden=0.1, lam_last=0.5)
    parts = [jnp.asarray(x[:, i * 1000 : (i + 1) * 1000]) for i in range(4)]
    engine = DAEFEngine(cfg, ExecutionPlan(merge="sequential"))
    fed = engine.session().round(parts)
    cen = engine.fit(jnp.asarray(x))
    max_diff = max(
        float(jnp.abs(a - b).max()) for a, b in zip(fed.weights, cen.weights, strict=True)
    )
    upd = federated.publish(daef.fit(cfg, parts[0]))
    raw_bytes = parts[0].nbytes
    return [
        "metric,value",
        f"federated_vs_centralized_max_weight_diff,{max_diff:.2e}",
        f"broker_message_bytes,{upd.nbytes()}",
        f"raw_partition_bytes,{raw_bytes}",
        f"privacy_message_vs_raw_ratio,{upd.nbytes() / raw_bytes:.3f}",
    ]


def section_kernels() -> list[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention import flash_attention, flash_attention_ref
    from repro.kernels.rglru_scan import rglru_scan, rglru_scan_ref
    from repro.kernels.rolann_stats import rolann_stats, rolann_stats_ref

    rng = np.random.default_rng(0)
    lines = ["kernel,us_per_call,max_err_vs_ref"]

    xa = jnp.asarray(rng.normal(size=(33, 2048)), jnp.float32)
    fsq = jnp.asarray(rng.uniform(0.1, 1, (8, 2048)), jnp.float32)
    fd = jnp.asarray(rng.normal(size=(8, 2048)), jnp.float32)
    g, m = rolann_stats(xa, fsq, fd)
    gr, mr = rolann_stats_ref(xa, fsq, fd)
    err = max(float(jnp.abs(g - gr).max()), float(jnp.abs(m - mr).max()))
    t0 = time.perf_counter()
    jax.block_until_ready(rolann_stats(xa, fsq, fd)[0])
    lines.append(f"rolann_stats,{(time.perf_counter()-t0)*1e6:.0f},{err:.2e}")

    q = jnp.asarray(rng.normal(size=(2, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 256, 2, 64)), jnp.float32)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    kr, vr = jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2)
    tr = lambda x: x.transpose(0, 2, 1, 3).reshape(8, 256, 64)
    ref = (
        flash_attention_ref(tr(q), tr(kr), tr(vr))
        .reshape(2, 4, 256, 64)
        .transpose(0, 2, 1, 3)
    )
    err = float(jnp.abs(out - ref).max())
    t0 = time.perf_counter()
    jax.block_until_ready(flash_attention(q, k, v, block_q=64, block_k=64))
    lines.append(f"flash_attention,{(time.perf_counter()-t0)*1e6:.0f},{err:.2e}")

    x = jnp.asarray(rng.normal(size=(2, 128, 256)), jnp.float32)
    r = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(2, 128, 256)), jnp.float32))
    i = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(2, 128, 256)), jnp.float32))
    lam = jnp.asarray(rng.normal(size=(256,)) + 4, jnp.float32)
    y, hl = rglru_scan(x, r, i, lam, block_s=32, block_w=128)
    yr, hr = rglru_scan_ref(x, r, i, lam)
    err = max(float(jnp.abs(y - yr).max()), float(jnp.abs(hl - hr).max()))
    t0 = time.perf_counter()
    jax.block_until_ready(rglru_scan(x, r, i, lam, block_s=32, block_w=128)[0])
    lines.append(f"rglru_scan,{(time.perf_counter()-t0)*1e6:.0f},{err:.2e}")
    return lines


def section_ablations() -> list[str]:
    from benchmarks import ablations

    return ablations.main()


def section_roofline() -> list[str]:
    from benchmarks import roofline_table

    return roofline_table.main()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true")
    ap.add_argument(
        "--only", default=None,
        choices=["table2", "table3", "federated", "kernels", "ablations",
                 "roofline"],
    )
    args = ap.parse_args()

    sections = {
        "table2": lambda: section_table2(args.full),
        "table3": lambda: section_table3(args.full),
        "federated": section_federated,
        "kernels": section_kernels,
        "ablations": section_ablations,
        "roofline": section_roofline,
    }
    if args.only:
        sections = {args.only: sections[args.only]}
    for name, fn in sections.items():
        print(f"\n=== {name} ===")
        t0 = time.perf_counter()
        for line in fn():
            print(line)
        print(f"# section {name} took {time.perf_counter()-t0:.1f}s")


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
