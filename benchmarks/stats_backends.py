"""Gram-stats producer benchmark: einsum vs the fused Pallas kernel.

Times the per-output sufficient statistics G[o] = Xa diag(f'^2) Xa^T,
M[o] = Xa (f'^2 d̄) — DAEF's training hot-spot — through both producers
(`stats_backend.einsum_stats` and the `rolann_stats` kernel, called
directly) over several shapes, plus one end-to-end `daef.fit` per producer
(selected through the autotune cache, `repro.testing.producer`), and
writes the record to
``BENCH_stats.json`` (default: the repo root, so the perf trajectory
accumulates in-tree per PR).

Interpretation note: on CPU the fused kernel runs in Pallas *interpret
mode* — a correctness harness, not a fast path — so fused timings on this
container measure interpreter overhead, not the TPU win.  The number that
matters on CPU is parity (`max_abs_err`); the fused speedup is a TPU
(Mosaic-compiled) claim.  See README "Gram-stats producers".

  PYTHONPATH=src python benchmarks/stats_backends.py [--repeats 3]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import daef, stats_backend
from repro.kernels.rolann_stats import rolann_stats
from repro.testing.producer import stats_producer

REPO_ROOT = Path(__file__).resolve().parent.parent

# (m, n, o): feature rows of Xa, samples, output neurons.
SHAPES = [(9, 2048, 8), (17, 8192, 16), (33, 4096, 33)]

#: Each producer's one-model Gram stats, as ``stats_backend.gram_stats``
#: runs them.
PRODUCERS = {"einsum": stats_backend.einsum_stats, "fused": rolann_stats}


def _timed(f, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(f())
        best = min(best, time.perf_counter() - t0)
    return best


def bench_shapes(repeats: int) -> list[dict]:
    records = []
    for m, n, o in SHAPES:
        rng = np.random.default_rng(0)
        xa = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
        fsq = jnp.asarray(rng.uniform(0.05, 1.0, (o, n)), jnp.float32)
        fd = jnp.asarray(rng.normal(size=(o, n)), jnp.float32)

        runs = {}
        outs = {}
        for backend, stats in PRODUCERS.items():
            fn = jax.jit(stats)
            outs[backend] = jax.block_until_ready(fn(xa, fsq, fd))  # compile
            runs[backend] = _timed(lambda: fn(xa, fsq, fd), repeats)
        err = max(
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(outs["einsum"], outs["fused"], strict=True)
        )
        gflop = 2 * o * m * m * n / 1e9
        rec = {
            "shape": {"m": m, "n": n, "o": o},
            "einsum_ms": runs["einsum"] * 1e3,
            "fused_ms": runs["fused"] * 1e3,
            "fused_speedup": runs["einsum"] / runs["fused"],
            "gflops_einsum": gflop / runs["einsum"],
            "gflops_fused": gflop / runs["fused"],
            "max_abs_err": err,
        }
        records.append(rec)
        print(f"gram_stats m={m} n={n} o={o}: "
              f"einsum {rec['einsum_ms']:.2f} ms, fused {rec['fused_ms']:.2f} ms "
              f"({rec['fused_speedup']:.2f}x), err {err:.2e}")
    return records


def bench_fit(repeats: int) -> dict:
    m0, n = 16, 4096
    cfg = daef.DAEFConfig(layer_sizes=(m0, 4, 8, m0), lam_hidden=0.5, lam_last=0.9)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(m0, n)), jnp.float32)
    times = {}
    for backend in stats_backend.PRODUCERS:
        with stats_producer(backend):
            daef.fit(cfg, x)  # compile/trace warmup
            times[backend] = _timed(lambda: daef.fit(cfg, x), repeats)
    rec = {
        "shape": {"m0": m0, "n": n, "layers": list(cfg.layer_sizes)},
        "einsum_ms": times["einsum"] * 1e3,
        "fused_ms": times["fused"] * 1e3,
        "fused_speedup": times["einsum"] / times["fused"],
    }
    print(f"daef.fit [{m0}x{n}]: einsum {rec['einsum_ms']:.1f} ms, "
          f"fused {rec['fused_ms']:.1f} ms ({rec['fused_speedup']:.2f}x)")
    return rec


def main(repeats: int = 3) -> dict:
    return {
        "backend": jax.default_backend(),
        "fused_mode": "interpret" if jax.default_backend() == "cpu" else "mosaic",
        "devices": len(jax.devices()),
        "gram_stats": bench_shapes(repeats),
        "daef_fit": bench_fit(repeats),
    }


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_stats.json"),
                    help="write the result record to this JSON file "
                         "(default: repo root, committed per PR)")
    a = ap.parse_args()
    record = main(repeats=a.repeats)
    if a.out:
        with open(a.out, "w") as fh:
            json.dump(record, fh, indent=2, sort_keys=True)
        print(f"wrote {a.out}")
