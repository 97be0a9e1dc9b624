"""Where the lane-batched Cholesky solve beats XLA's batched Cholesky.

For every stack of ``B`` symmetric positive definite ``n x n`` systems in
the sweep, each with ``r`` right-hand sides, times the two solvers
``core/chol.py`` chooses between and prints one JSON line per shape:
milliseconds per stack of each (many solves inside one compiled program, so
dispatch is paid once a call), and the largest gap between their solutions
relative to the largest entry (or why the kernel cannot take that shape).
``chol.B0`` and ``chol.N_MAX`` are read off this table on the chip (PERF.md
section 6); on a CPU it times LAPACK and the interpreter instead and says
nothing about the TPU.

    PYTHONPATH=src python benchmarks/chol_crossover.py
        [--batches 16 64 128 256 1024 4096 16384] [--sizes 9 17 33 63]
        [--rhs 1] [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.core import eigh


def systems(seed: int, batch: int, n: int, r: int):
    """``batch`` Grams of ``3 n`` standard-normal samples plus the identity
    (a decoder's ``G + lam I``), made on the device, and ``r`` right-hand
    sides each."""
    kx, kb = jax.random.split(jax.random.key(seed))
    x = jax.random.normal(kx, (batch, n, 3 * n), jnp.float32)
    a = jnp.einsum("bij,bkj->bik", x, x, precision="highest") / (3 * n)
    return a + jnp.eye(n), jax.random.normal(kb, (batch, n, r), jnp.float32)


def xla(a, b):
    """XLA's route: batched ``cholesky`` and ``cho_solve``."""
    return jax.scipy.linalg.cho_solve((jnp.linalg.cholesky(a), True), b)


def kernel(a, b):
    from repro.kernels.chol_solve import chol_solve

    return chol_solve(a, b, interpret=eigh._interpret())


def _repeated(solver):
    """A program that solves the same stack ``repeats`` times; each solve
    reads the last one's result, so none can be dropped or hoisted."""

    def run(a, b, repeats):
        def body(_, acc):
            x = solver(a + 0 * acc, b)
            return x[..., :1, :1] * 0 + acc

        return jax.lax.fori_loop(0, repeats, body,
                                 jnp.zeros(a.shape[:-2] + (1, 1), a.dtype))

    return jax.jit(run, static_argnums=2)


def time_ms(solver, a, b, window_s: float) -> float:
    """Milliseconds per solve: the median of three timed calls, each of as
    many solves as fill about ``window_s``, after a warm-up call."""
    f = _repeated(solver)
    f(a, b, 1).block_until_ready()
    t = time.perf_counter()
    f(a, b, 1).block_until_ready()
    repeats = int(min(100, max(1, window_s / (time.perf_counter() - t))))
    f(a, b, repeats).block_until_ready()
    times = []
    for _ in range(3):
        t = time.perf_counter()
        f(a, b, repeats).block_until_ready()
        times.append(time.perf_counter() - t)
    return 1e3 * sorted(times)[1] / repeats


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--batches", type=int, nargs="+",
                   default=[16, 64, 128, 256, 1024, 4096, 16384])
    p.add_argument("--sizes", type=int, nargs="+", default=[9, 17, 33, 63])
    p.add_argument("--rhs", type=int, default=1)
    p.add_argument("--window", type=float, default=0.2,
                   help="seconds of solves in each timed call")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "device_kind": dev.device_kind}))
    for n in args.sizes:
        for b in args.batches:
            a, rhs = systems(args.seed, b, n, args.rhs)
            row = {"n": n, "batch": b, "rhs": args.rhs,
                   "xla_chol_ms": time_ms(xla, a, rhs, args.window)}
            try:
                ref = jax.jit(xla)(a, rhs)
                got = jax.jit(kernel)(a, rhs)
                row["gap"] = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
                row["kernel_ms"] = time_ms(kernel, a, rhs, args.window)
            except Exception as e:  # noqa: BLE001 — report the shape and go on
                row["kernel_error"] = f"{type(e).__name__}: {str(e)[:300]}"
            else:
                row["kernel_wins"] = row["kernel_ms"] < row["xla_chol_ms"]
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
