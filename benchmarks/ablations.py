"""DAEF ablations beyond the paper's tables.

  aux_bias   — the paper's Algorithm-2 bias ambiguity (DESIGN.md §1):
               "zero" vs "c1" decoder bias.
  method     — gram fast path vs paper-faithful svd statistics.
  latent     — latent width sweep (the paper fixes m1 per dataset).
  partitions — federation width: 1/4/16 nodes, same data.

Each row: F1 on the cardio replica protocol (fold 0) + steady-state fit time.
"""
from __future__ import annotations

import dataclasses
import time

import jax.numpy as jnp

from repro.core import anomaly, daef
from repro.data import synthetic


def _eval(cfg: daef.DAEFConfig, x_train, x_test, y_test, n_partitions=4):
    daef.fit(cfg, jnp.asarray(x_train), n_partitions=n_partitions)  # warm
    t0 = time.perf_counter()
    model = daef.fit(cfg, jnp.asarray(x_train), n_partitions=n_partitions)
    jnp.asarray(model.train_errors).block_until_ready()
    wall = time.perf_counter() - t0
    errs = daef.reconstruction_error(cfg, model, jnp.asarray(x_test))
    f1 = anomaly.evaluate(model.train_errors, errs, y_test, "q90").f1
    return f1, wall


def main() -> list[str]:
    ds = synthetic.make_dataset("cardio")
    x_train, x_test, y_test = ds.train_test_split(0)
    base = daef.DAEFConfig(
        layer_sizes=(21, 4, 8, 12, 16, 21), lam_hidden=0.9, lam_last=0.9
    )
    lines = ["ablation,variant,f1,fit_s"]

    for bias in ("zero", "c1"):
        cfg = dataclasses.replace(base, aux_bias=bias)
        f1, wall = _eval(cfg, x_train, x_test, y_test)
        lines.append(f"aux_bias,{bias},{f1:.4f},{wall:.3f}")

    for method in ("gram", "svd"):
        cfg = dataclasses.replace(base, method=method)
        f1, wall = _eval(cfg, x_train, x_test, y_test)
        lines.append(f"method,{method},{f1:.4f},{wall:.3f}")

    for latent in (2, 4, 8, 16):
        sizes = (21, latent, 8, 12, 16, 21)
        cfg = dataclasses.replace(base, layer_sizes=sizes)
        f1, wall = _eval(cfg, x_train, x_test, y_test)
        lines.append(f"latent,{latent},{f1:.4f},{wall:.3f}")

    for parts in (1, 4, 16):
        f1, wall = _eval(base, x_train, x_test, y_test, n_partitions=parts)
        lines.append(f"partitions,{parts},{f1:.4f},{wall:.3f}")
    return lines


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    print("\n".join(main()))
