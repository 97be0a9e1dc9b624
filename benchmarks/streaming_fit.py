"""Streaming-training benchmark: chunked vs one-shot fit, Cholesky vs eigh.

Three measurements, appended to the ``BENCH_train.json`` trajectory (default:
the repo root, committed per PR so the perf history accumulates in-tree):

* **solve** — the per-output gram solve ``(G + lam I) w = M``: direct
  Cholesky (`rolann.solve(..., gram_solver="chol")`, the new default) vs the
  eigh factorization route (``gram_solver="eigh"``, the former path), jitted,
  best-of-N.  This is the post-stats hot spot of every gram-method fit and
  federated merge; the acceptance bar is chol >= 2x on CPU.
* **fit** — one-shot ``engine.fit`` vs the streaming
  ``ExecutionPlan(chunk_samples=...)`` fit at a fixed sample count:
  samples/sec for both (streaming trades a bounded re-forward per layer for
  bounded memory; on CPU expect rough parity, the win is the memory model).
* **memory** — peak live device bytes while STREAMING over growing sample
  counts (>= 4 points, fixed chunk width) vs the one-shot fit's live bytes:
  the streamed peak stays flat in n (accumulators + one chunk), the one-shot
  footprint grows with n.

Peak bytes come from ``device.memory_stats()`` where the backend reports it
(TPU/GPU); on CPU that is unavailable, so the fallback sums ``nbytes`` over
``jax.live_arrays()`` sampled at every chunk boundary — a lower-bound proxy
that still exposes the flat-vs-linear scaling.  The record names the method.

  PYTHONPATH=src python benchmarks/streaming_fit.py [--repeats 3]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import activations, daef, rolann
from repro.engine import DAEFEngine, ExecutionPlan

REPO_ROOT = Path(__file__).resolve().parent.parent

SOLVE_SHAPES = [(17, 16), (33, 33), (65, 64)]  # (m rows of G, outputs)
LAYERS = (21, 6, 12, 21)
MEM_SAMPLES = [2048, 4096, 8192, 16384]  # >= 4 points, chunk fixed
CHUNK = 512


def _timed(f, repeats: int, inner: int = 1) -> float:
    """Best-of-``repeats`` wall time of ``f``; ``inner`` > 1 amortizes the
    per-dispatch overhead for sub-millisecond kernels."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = None
        for _ in range(inner):
            out = f()
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


def live_device_bytes() -> tuple[int, str]:
    """(bytes, method): backend-reported bytes_in_use when available, else
    the sum of live jax.Array buffers (CPU fallback)."""
    stats = jax.local_devices()[0].memory_stats()
    if stats and "bytes_in_use" in stats:
        return int(stats["bytes_in_use"]), "memory_stats.bytes_in_use"
    return (
        int(sum(a.nbytes for a in jax.live_arrays())),
        "sum(jax.live_arrays().nbytes)",
    )


def bench_solve(repeats: int) -> list[dict]:
    act = activations.get("logsig")
    rng = np.random.default_rng(0)
    records = []
    for m, o in SOLVE_SHAPES:
        n = 4096
        x = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
        d = jnp.asarray(rng.uniform(0.1, 0.9, (o, n)), jnp.float32)
        stats = jax.block_until_ready(rolann.compute_stats(x, d, act))
        fns = {
            solver: jax.jit(
                lambda s, _sv=solver: rolann.solve(s, 0.3, gram_solver=_sv)
            )
            for solver in ("chol", "eigh")
        }
        outs = {k: jax.block_until_ready(f(stats)) for k, f in fns.items()}
        times = {k: _timed(lambda _f=f: _f(stats), repeats, inner=10)
                 for k, f in fns.items()}
        err = max(
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(outs["chol"], outs["eigh"], strict=True)
        )
        rec = {
            "shape": {"m": m + 1, "o": o},  # +1: bias row of the augmented G
            "chol_ms": times["chol"] * 1e3,
            "eigh_ms": times["eigh"] * 1e3,
            "chol_speedup": times["eigh"] / times["chol"],
            "max_abs_err": err,
        }
        records.append(rec)
        print(f"solve m={m + 1} o={o}: chol {rec['chol_ms']:.3f} ms, "
              f"eigh {rec['eigh_ms']:.3f} ms "
              f"({rec['chol_speedup']:.1f}x), err {err:.2e}")
    return records


def bench_fit(repeats: int) -> dict:
    n = 8192
    cfg = daef.DAEFConfig(layer_sizes=LAYERS, lam_hidden=0.5, lam_last=0.9)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(LAYERS[0], n)), jnp.float32)
    times = {}
    oneshot = DAEFEngine(cfg, ExecutionPlan(tenants=1))
    chunked = DAEFEngine(cfg, ExecutionPlan(tenants=1, chunk_samples=CHUNK))
    for name, eng in (("oneshot", oneshot), ("chunked", chunked)):
        eng.fit(x)  # warm the trace
        times[name] = _timed(lambda _e=eng: _e.fit(x).train_errors, repeats)
    rec = {
        "shape": {"m0": LAYERS[0], "n": n, "layers": list(LAYERS),
                  "chunk_samples": CHUNK},
        "oneshot_ms": times["oneshot"] * 1e3,
        "chunked_ms": times["chunked"] * 1e3,
        "oneshot_samples_per_sec": n / times["oneshot"],
        "chunked_samples_per_sec": n / times["chunked"],
    }
    print(f"fit [{LAYERS[0]}x{n}]: oneshot {rec['oneshot_ms']:.1f} ms "
          f"({rec['oneshot_samples_per_sec']:.0f} samples/s), chunked "
          f"{rec['chunked_ms']:.1f} ms "
          f"({rec['chunked_samples_per_sec']:.0f} samples/s)")
    return rec


def bench_memory() -> dict:
    """Stream growing sample counts through fit_stream, sampling live bytes
    at every chunk boundary; one-shot live bytes for the same n alongside."""
    cfg = daef.DAEFConfig(layer_sizes=LAYERS, lam_hidden=0.5, lam_last=0.9)
    engine = DAEFEngine(cfg, ExecutionPlan(tenants=1, chunk_samples=CHUNK))
    rng = np.random.default_rng(2)
    points = []
    method = live_device_bytes()[1]
    for n in MEM_SAMPLES:
        x_host = rng.normal(size=(LAYERS[0], n)).astype(np.float32)
        peak = 0

        def chunks():
            nonlocal peak
            for i in range(0, n, CHUNK):
                peak = max(peak, live_device_bytes()[0])
                yield x_host[:, i:i + CHUNK]

        model = engine.fit_stream(chunks)
        jax.block_until_ready(model.train_errors)
        stream_bytes = peak  # in-flight peak: accumulators + one chunk
        model_bytes = sum(int(a.nbytes) for a in jax.tree.leaves(model))
        del model

        x_dev = jnp.asarray(x_host)
        oneshot = DAEFEngine(cfg, ExecutionPlan(tenants=1)).fit(x_dev)
        jax.block_until_ready(oneshot.train_errors)
        oneshot_bytes = live_device_bytes()[0]
        del x_dev, oneshot

        points.append({
            "n": n,
            "stream_peak_bytes": int(stream_bytes),
            "model_bytes": int(model_bytes),
            "oneshot_live_bytes": int(oneshot_bytes),
        })
        print(f"memory n={n}: stream peak {stream_bytes / 1e6:.2f} MB "
              f"(+{model_bytes / 1e6:.2f} MB final model incl. [n] error "
              f"pool), oneshot live {oneshot_bytes / 1e6:.2f} MB")
    return {"chunk_samples": CHUNK, "method": method, "points": points}


def main(repeats: int = 3) -> dict:
    return {
        "benchmark": "streaming_fit",
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "solve": bench_solve(repeats),
        "fit": bench_fit(repeats),
        "memory": bench_memory(),
    }


def append_trajectory(record: dict, out: str) -> None:
    path = Path(out)
    history = []
    if path.exists():
        try:
            history = json.loads(path.read_text())
            assert isinstance(history, list)
        except (ValueError, AssertionError):
            history = []
    history.append(record)
    path.write_text(json.dumps(history, indent=2, sort_keys=True) + "\n")
    print(f"appended 1 record -> {out} ({len(history)} total in trajectory)")


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_train.json"),
                    help="append the record to this JSON-list trajectory "
                         "(default: repo root, committed per PR)")
    a = ap.parse_args()
    record = main(repeats=a.repeats)
    if a.out:
        append_trajectory(record, a.out)
