"""Paper Table 2: test F1 of DAEF (three initializations) vs the iterative AE.

Runs the paper's protocol on the synthetic dataset replicas (DESIGN.md §6):
train on normal data only (k-fold over normals), test on held-out normals +
an equal anomaly sample, threshold from the train reconstruction errors.

The claim validated is *F1 parity* (DAEF within a few points of AE), not the
paper's absolute numbers (real UCI/Kaggle data is unavailable offline).
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.baselines import autoencoder
from repro.core import anomaly, daef
from repro.data import synthetic

# Architectures from the paper's Table 5 (per dataset, DAEF column).
DAEF_ARCH = {
    "shuttle": ((9, 3, 5, 7, 9), 0.8, 0.9, "extreme_iqr"),
    "covertype": ((10, 2, 4, 6, 8, 10), 0.7, 0.1, "q90"),
    "pendigits": ((16, 8, 12, 16), 0.005, 0.7, "q90"),
    "cardio": ((21, 4, 8, 12, 16, 21), 0.9, 0.9, "q90"),
    "creditcard": ((29, 15, 18, 21, 24, 27, 29), 0.8, 0.9, "extreme_iqr"),
    "ionosphere": ((33, 8, 14, 33), 0.01, 0.8, "extreme_iqr"),
    "optdigit": ((62, 10, 20, 30, 40, 50, 62), 0.8, 0.9, "extreme_iqr"),
}
AE_ARCH = {
    "shuttle": ((9, 7, 5, 7, 9), 30),
    "covertype": ((10, 8, 6, 8, 10), 100),
    "pendigits": ((16, 12, 4, 12, 16), 100),
    "cardio": ((21, 12, 4, 12, 21), 100),
    "creditcard": ((29, 25, 20, 15, 20, 25, 29), 100),
    "ionosphere": ((33, 25, 20, 15, 20, 25, 33), 100),
    "optdigit": ((62, 50, 40, 30, 20, 30, 40, 50, 62), 50),
}


# Small per-dataset grid (the paper also grid-searched its Table 5 values on
# the real data; the replicas need their own lambdas/threshold).
_GRID_LAMS = [(0.005, 0.5), (0.1, 0.5), (0.8, 0.9)]
_GRID_RULES = ["q90", "extreme_iqr"]


def _grid_search(ds, arch, init: str) -> tuple[float, float, str]:
    """Pick (lam_hl, lam_ll, rule) on fold 9 (never used for reporting)."""
    x_train, x_test, y_test = ds.train_test_split(9, n_folds=10)
    best = (-1.0, _GRID_LAMS[0][0], _GRID_LAMS[0][1], _GRID_RULES[0])
    for lam_hl, lam_ll in _GRID_LAMS:
        cfg = daef.DAEFConfig(
            layer_sizes=arch, lam_hidden=lam_hl, lam_last=lam_ll, init=init,
        )
        model = daef.fit(cfg, jnp.asarray(x_train), n_partitions=4)
        errs = daef.reconstruction_error(cfg, model, jnp.asarray(x_test))
        for rule in _GRID_RULES:
            f1 = anomaly.evaluate(model.train_errors, errs, y_test, rule).f1
            if f1 > best[0]:
                best = (f1, lam_hl, lam_ll, rule)
    return best[1], best[2], best[3]


def run_dataset(
    name: str,
    *,
    folds: int = 3,
    scale: float | None = None,
    ae_epochs: int | None = None,
    inits: tuple[str, ...] = ("xavier", "random", "orthogonal"),
    include_ae: bool = True,
    seed: int = 0,
    grid: bool = True,
) -> dict:
    """Returns {algo: (mean_f1, std_f1, min_train_seconds)}."""
    if scale is None:
        # Keep CPU benchmark wall-time sane on the two largest datasets.
        scale = 0.1 if synthetic.PAPER_DATASETS[name][0] > 100_000 else 1.0
    ds = synthetic.make_dataset(name, seed=seed, scale=scale)
    arch, lam_hl, lam_ll, rule = DAEF_ARCH[name]
    results: dict[str, tuple[float, float, float]] = {}

    algos: dict[str, dict] = {
        f"daef_{init}": {"init": init} for init in inits
    }
    if include_ae:
        algos["ae"] = {}

    for algo, opts in algos.items():
        f1s, times = [], []
        warmed = False
        for fold in range(folds):
            x_train, x_test, y_test = ds.train_test_split(fold, n_folds=10)
            if algo == "ae":
                ae_arch, epochs = AE_ARCH[name]
                cfg = autoencoder.AEConfig(
                    layer_sizes=ae_arch,
                    epochs=ae_epochs if ae_epochs is not None else epochs,
                    seed=fold,
                )
                model, wall = autoencoder.fit(cfg, x_train)
                errs = autoencoder.reconstruction_error(
                    cfg, model, jnp.asarray(x_test)
                )
                train_errs = model.train_errors
            else:
                d_lam_hl, d_lam_ll, d_rule = lam_hl, lam_ll, rule
                if grid:
                    if "grid" not in opts:
                        opts["grid"] = _grid_search(ds, arch, opts["init"])
                    d_lam_hl, d_lam_ll, d_rule = opts["grid"]
                cfg = daef.DAEFConfig(
                    layer_sizes=arch,
                    lam_hidden=d_lam_hl,
                    lam_last=d_lam_ll,
                    init=opts["init"],
                    seed=fold,
                )
                if not warmed:
                    # Exclude one-time JIT compilation from the timing claim
                    # (the AE's step function also compiles once, then runs
                    # epochs x steps iterations against it).
                    daef.fit(cfg, jnp.asarray(x_train), n_partitions=4)
                    warmed = True
                t0 = time.perf_counter()
                model = daef.fit(cfg, jnp.asarray(x_train), n_partitions=4)
                jnp.asarray(model.train_errors).block_until_ready()
                wall = time.perf_counter() - t0
                errs = daef.reconstruction_error(cfg, model, jnp.asarray(x_test))
                train_errs = model.train_errors
            met = anomaly.evaluate(
                train_errs, errs, y_test,
                d_rule if (algo != "ae" and grid) else rule,
            )
            f1s.append(met.f1)
            times.append(wall)
        results[algo] = (
            float(np.mean(f1s)),
            float(np.std(f1s)),
            float(np.min(times)),  # steady-state time (JIT warm)
        )
    return results


def main(datasets=None, folds: int = 3) -> list[str]:
    lines = ["dataset,algo,f1_mean,f1_std,train_s"]
    for name in datasets or synthetic.PAPER_DATASETS:
        res = run_dataset(name, folds=folds)
        for algo, (f1, std, wall) in res.items():
            lines.append(f"{name},{algo},{f1:.4f},{std:.4f},{wall:.3f}")
    return lines


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    print("\n".join(main()))
