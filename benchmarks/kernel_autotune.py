"""Kernel autotune sweep: measure block candidates, record the winners.

For each kernel kind (one-shot stats, accumulating stats, fused-chunk fold)
and each benchmark shape, every candidate sample-axis block in
``autotune.CANDIDATE_BLOCKS`` is timed and the winner recorded.  With
``--write-cache`` the winners — plus the measured einsum-vs-fused verdict
that ``stats_backend.producer()`` consults — are merged into the
committed per-platform cache (``src/repro/kernels/autotune_cache.json``).

Each record carries attained GFLOP/s from the analytic contraction count
(2*o*m^2*n for the Gram fold, + the fused-chunk kernel's recomputed
stage-1 matmul).  On a device with published peaks
(``launch/roofline.PEAKS``, keyed by ``device_kind``) it also carries the
attained-vs-peak fraction; on any other device (the CPU among them) no
fraction is written — the sweep's *ordering* is what the cache consumes.

The sweep results are appended under the ``"autotune"`` key of
``BENCH_stats.json`` (the rest of the record is `benchmarks/stats_backends.py`'s).

Regenerating on new hardware::

    PYTHONPATH=src python benchmarks/kernel_autotune.py --write-cache

  PYTHONPATH=src python benchmarks/kernel_autotune.py [--repeats 2] [--write-cache]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import stats_backend
from repro.kernels import autotune
from repro.kernels.rolann_stats import ops, rolann_stats
from repro.launch import roofline

REPO_ROOT = Path(__file__).resolve().parent.parent

# (m, n, o) sweeps: feature rows of Xa, samples, output neurons.  Chosen to
# straddle the static heuristic's 512 cap so the 1024 candidate has a shape
# where it could win.
SHAPES = [(9, 1024, 8), (17, 2048, 16)]

#: Batched kinds inherit the unbatched winner for the same shape bucket —
#: the batched grids stream identical per-(k, o) tile work, so a separate
#: sweep would re-measure the same inner loop k times.
KIND_ALIASES = {
    "stats": ("stats_batched",),
    "stats_acc": ("stats_acc_batched",),
    "fused_chunk": ("fused_chunk_batched",),
}


def _timed(f, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(f())
        best = min(best, time.perf_counter() - t0)
    return best


def _problem(m: int, n: int, o: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    xa = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    fsq = jnp.asarray(rng.uniform(0.05, 1.0, (o, n)), jnp.float32)
    fd = jnp.asarray(rng.normal(size=(o, n)), jnp.float32)
    g0 = jnp.zeros((o, m, m), jnp.float32)
    m0 = jnp.zeros((o, m), jnp.float32)
    # fused-chunk problem: h [o, n] (ELM-AE: targets == inputs, o == m_l),
    # stage-1 encoder o -> m-1 so xa rows match m.
    h = jnp.asarray(rng.normal(size=(o, n)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(o, m - 1)) / np.sqrt(o), jnp.float32)
    b = jnp.asarray(rng.normal(size=(m - 1,)), jnp.float32)
    gc = jnp.zeros((o, m, m), jnp.float32)
    mc = jnp.zeros((o, m), jnp.float32)
    return dict(xa=xa, fsq=fsq, fd=fd, g0=g0, m0=m0,
                h=h, w=w, b=b, gc=gc, mc=mc)


def _kind_runner(kind: str, p: dict, block_n: int):
    if kind == "stats":
        return lambda: ops.rolann_stats(p["xa"], p["fsq"], p["fd"],
                                        block_n=block_n)
    if kind == "stats_acc":
        return lambda: ops.rolann_stats_acc(p["g0"], p["m0"], p["xa"],
                                            p["fsq"], p["fd"], block_n=block_n)
    if kind == "fused_chunk":
        return lambda: ops.rolann_fused_chunk(p["gc"], p["mc"], p["h"],
                                              p["w"], p["b"],
                                              act_name="logsig",
                                              block_n=block_n)
    raise ValueError(kind)


def _kind_flops(kind: str, m: int, n: int, o: int) -> float:
    gram = 2 * o * m * m * n + 2 * o * m * n   # G fold + M fold
    if kind == "fused_chunk":
        # + the stage-1 matmul recomputed once per output grid step
        return gram + o * 2 * o * (m - 1) * n
    return gram


def _peak_flops() -> float | None:
    """Published peak FLOP/s of the running device, or None off-table."""
    kind = jax.devices()[0].device_kind
    return roofline.PEAKS[kind].flops if kind in roofline.PEAKS else None


def sweep(repeats: int) -> list[dict]:
    records = []
    peak = _peak_flops()
    for m, n, o in SHAPES:
        p = _problem(m, n, o)
        for kind in ("stats", "stats_acc", "fused_chunk"):
            flops = _kind_flops(kind, m, n, o)
            candidates = {}
            for block in autotune.CANDIDATE_BLOCKS:
                if block > autotune.next_pow2(n):
                    continue   # would be clamped back to next_pow2(n) anyway
                import warnings as _w
                with _w.catch_warnings():
                    # explicit blocks beyond the legacy 512 cap are exactly
                    # what this sweep measures
                    _w.simplefilter("ignore", RuntimeWarning)
                    fn = _kind_runner(kind, p, block)
                    jax.block_until_ready(fn())   # compile
                    candidates[block] = _timed(fn, repeats)
            best_block = min(candidates, key=candidates.get)
            best_s = candidates[best_block]
            rec = {
                "kind": kind,
                "shape": {"m": m, "n": n, "o": o},
                "shape_key": autotune.shape_key(kind, n=n, m=m, o=o),
                "candidates_ms": {str(k): v * 1e3
                                  for k, v in sorted(candidates.items())},
                "best_block_n": best_block,
                "best_ms": best_s * 1e3,
                "static_block_n": autotune.static_block_n(n),
                "attained_gflops": flops / best_s / 1e9,
            }
            if peak is not None:
                rec["peak_gflops"] = peak / 1e9
                rec["attained_vs_peak"] = flops / best_s / peak
            records.append(rec)
            print(f"{kind} m={m} n={n} o={o}: best block {best_block} "
                  f"({rec['best_ms']:.2f} ms, "
                  f"{rec['attained_gflops']:.2f} GFLOP/s)")
    return records


def backend_verdict(repeats: int) -> dict:
    """Measured einsum-vs-fused verdict on the largest sweep shape — what
    ``stats_backend.producer()`` returns on this platform once cached."""
    m, n, o = SHAPES[-1]
    p = _problem(m, n, o)
    times = {}
    producers = {"einsum": stats_backend.einsum_stats, "fused": rolann_stats}
    for backend, stats in producers.items():
        fn = jax.jit(stats)
        jax.block_until_ready(fn(p["xa"], p["fsq"], p["fd"]))
        times[backend] = _timed(lambda: fn(p["xa"], p["fsq"], p["fd"]),
                                repeats)
    preferred = min(times, key=times.get)
    rec = {
        "shape": {"m": m, "n": n, "o": o},
        "einsum_ms": times["einsum"] * 1e3,
        "fused_ms": times["fused"] * 1e3,
        "preferred_backend": preferred,
    }
    print(f"verdict m={m} n={n} o={o}: einsum {rec['einsum_ms']:.2f} ms, "
          f"fused {rec['fused_ms']:.2f} ms -> preferred '{preferred}'")
    return rec


def main(repeats: int = 2, write_cache: bool = False) -> dict:
    platform = jax.default_backend()
    records = sweep(repeats)
    verdict = backend_verdict(repeats)
    result = {
        "platform": platform,
        "fused_mode": "interpret" if platform == "cpu" else "mosaic",
        "devices": len(jax.devices()),
        "sweep": records,
        "verdict": verdict,
    }
    if write_cache:
        blocks = {}
        for rec in records:
            blocks[rec["shape_key"]] = rec["best_block_n"]
            for alias in KIND_ALIASES[rec["kind"]]:
                s = rec["shape"]
                blocks[autotune.shape_key(alias, n=s["n"], m=s["m"],
                                          o=s["o"])] = rec["best_block_n"]
        autotune.update_cache(platform=platform, blocks=blocks,
                              preferred=verdict["preferred_backend"])
        result["cache_path"] = str(autotune.cache_path())
        print(f"wrote {len(blocks)} block entries + preferred backend "
              f"'{verdict['preferred_backend']}' to {autotune.cache_path()}")
    return result


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--write-cache", action="store_true",
                    help="merge winners into the committed autotune cache "
                         "for this platform")
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_stats.json"),
                    help="append the sweep under the 'autotune' key of this "
                         "JSON record (default: repo root, committed per PR)")
    a = ap.parse_args()
    result = main(repeats=a.repeats, write_cache=a.write_cache)
    if a.out:
        out = Path(a.out)
        record = json.loads(out.read_text()) if out.exists() else {}
        record["autotune"] = result
        with open(out, "w") as fh:
            json.dump(record, fh, indent=2, sort_keys=True)
        print(f"wrote {a.out}")
