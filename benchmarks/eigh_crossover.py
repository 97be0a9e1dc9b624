"""Where the lane-batched Jacobi eigensolver beats ``jnp.linalg.eigh``.

For every stack of ``B`` symmetric ``n x n`` Grams in the sweep, times the
two solvers ``core/eigh.py`` chooses between and prints one JSON line per
shape: milliseconds per stack of each (many solves inside one
compiled program, so dispatch is paid once a call), and the Jacobi sweeps the
stack took (or why the Jacobi kernel cannot take that shape).  ``eigh.B0`` and ``eigh.N_MAX`` are read off this table on the
chip (PERF.md section 6); on a CPU it times LAPACK instead and says nothing
about the TPU.

    PYTHONPATH=src python benchmarks/eigh_crossover.py [--batches 1 8 32 128 1024]
        [--sizes 9 21 29 62 64 128] [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import eigh


def grams(seed: int, batch: int, n: int) -> np.ndarray:
    """``batch`` full-rank Grams of ``3 n`` standard-normal samples."""
    x = np.random.default_rng(seed).normal(size=(batch, n, 3 * n))
    return (x @ x.transpose(0, 2, 1) / (3 * n)).astype(np.float32)


def _repeated(solver):
    """A program that solves the same stack ``repeats`` times; each solve
    reads the last one's result, so none can be dropped or hoisted."""

    def run(a, repeats):
        def body(_, acc):
            w, v = solver(a + 0 * acc)
            return w[..., -1:, None] * 0 + v[..., :1, :1] * 0 + acc

        return jax.lax.fori_loop(0, repeats, body,
                                 jnp.zeros(a.shape[:-2] + (1, 1), a.dtype))

    return jax.jit(run)


def time_ms(solver, a: jax.Array, window_s: float) -> float:
    """Milliseconds per solve: the median of three timed calls, each of as
    many solves as fill about ``window_s``, after a warm-up call."""
    f = _repeated(solver)
    f(a, 1).block_until_ready()
    t = time.perf_counter()
    f(a, 1).block_until_ready()
    repeats = int(min(100, max(1, window_s / (time.perf_counter() - t))))
    times = []
    for _ in range(3):
        t = time.perf_counter()
        f(a, repeats).block_until_ready()
        times.append(time.perf_counter() - t)
    return 1e3 * sorted(times)[1] / repeats


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--batches", type=int, nargs="+", default=[1, 8, 32, 128, 1024])
    p.add_argument("--sizes", type=int, nargs="+", default=[9, 21, 29, 62, 64, 128])
    p.add_argument("--window", type=float, default=0.2,
                   help="seconds of solves in each timed call")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "device_kind": dev.device_kind}))

    def jacobi(a):
        w, v, _ = eigh.jacobi(a)
        return w, v

    def xla(a):
        return tuple(jnp.linalg.eigh(a))

    for n in args.sizes:
        for b in args.batches:
            a = jnp.asarray(grams(args.seed, b, n))
            row = {"n": n, "batch": b,
                   "xla_eigh_ms": time_ms(xla, a, args.window)}
            try:
                row["sweeps"] = int(jax.jit(eigh.jacobi)(a).sweeps)
                row["jacobi_ms"] = time_ms(jacobi, a, args.window)
            except Exception as e:  # noqa: BLE001 — report the shape and go on
                row["jacobi_error"] = f"{type(e).__name__}: {str(e)[:300]}"
            else:
                row["jacobi_wins"] = row["jacobi_ms"] < row["xla_eigh_ms"]
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
