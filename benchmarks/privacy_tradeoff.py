"""Privacy-tier benchmark: anomaly AUC vs epsilon, secagg parity + cost.

One logical experiment, appended to the ``BENCH_privacy.json`` trajectory
(default: the repo root, committed per PR so the privacy/utility history
accumulates in-tree):

* **AUC-vs-epsilon sweep** — for each benchmark anomaly dataset, train the
  DAEF detector under the DP release (`repro.privacy.dp.fit_dp`) at
  epsilon in {0.5, 1, 2, 4, 8} plus the non-private baseline (inf), score
  the paper's held-out normal+anomaly split and record the fold-averaged
  ROC AUC (rank-based Mann-Whitney — no sklearn dependency).  The
  acceptance story: AUC improves monotonically with epsilon and the
  epsilon=8 detector sits within a couple of AUC points of non-private.
* **secagg parity + overhead** — one federation round's exchange states
  aggregated masked vs unmasked: the decoded masked aggregate must be
  BIT-EXACT (uint64 mask cancellation), and the record carries the
  wall-time of both paths per merge strategy.

The DP clip bound is calibrated per dataset as the 90th percentile of the
train-split column norms — the benchmark's stand-in for the public/proxy
calibration a deployment would use (the bound itself is then treated as
public).

  PYTHONPATH=src python benchmarks/privacy_tradeoff.py [--folds 3]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.core import daef, federated
from repro.data import synthetic
from repro.engine import DAEFEngine, ExecutionPlan
from repro.privacy import PrivacySpec, dp, secagg

REPO_ROOT = Path(__file__).resolve().parent.parent

# (name, base fraction of the paper-size dataset): DP utility is sample-
# count bound — the sweep uses the large anomaly datasets.  pendigits
# (6k train samples) is kept as the honest hard case: its epsilon=8 AUC
# lands a few points under non-private, which is what DP costs at that n.
DATASETS = (("shuttle", 1.0), ("covertype", 0.25), ("pendigits", 1.0))
EPSILONS = (0.5, 1.0, 2.0, 4.0, 8.0)
DELTA = 1e-5


def rank_auc(scores: np.ndarray, labels: np.ndarray) -> float:
    """ROC AUC as the normalized Mann-Whitney U statistic (average ranks
    on ties) — higher scores should mean anomalous (label 1)."""
    scores = np.asarray(scores, np.float64)
    labels = np.asarray(labels)
    order = np.argsort(scores, kind="mergesort")
    ranks = np.empty(scores.size, np.float64)
    s = scores[order]
    i = 0
    while i < s.size:
        j = i
        while j + 1 < s.size and s[j + 1] == s[i]:
            j += 1
        ranks[order[i:j + 1]] = (i + j) / 2 + 1  # average 1-based rank
        i = j + 1
    pos = labels == 1
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    u = float(ranks[pos].sum()) - n_pos * (n_pos + 1) / 2
    return u / (n_pos * n_neg)


def _dataset_config(m0: int) -> daef.DAEFConfig:
    return daef.DAEFConfig(layer_sizes=(m0, 4, 8, m0), lam_hidden=0.9,
                           lam_last=0.9, method="gram")


def auc_sweep(args) -> list[dict]:
    records = []
    for name, base_scale in DATASETS:
        ds = synthetic.make_dataset(name, seed=0,
                                    scale=base_scale * args.scale)
        cfg = _dataset_config(ds.dim)
        by_eps: dict[str, list[float]] = {}
        for fold in range(args.folds):
            x_train, x_test, y_test = ds.train_test_split(fold=fold)
            x_train = x_train.astype(np.float32)
            x_test = np.asarray(x_test, np.float32)
            clip = float(np.quantile(
                np.linalg.norm(x_train, axis=0), 0.9
            ))
            baseline = daef.fit(cfg, x_train)
            scores = np.asarray(
                daef.reconstruction_error(cfg, baseline, x_test)
            )
            by_eps.setdefault("inf", []).append(rank_auc(scores, y_test))
            for eps in EPSILONS:
                key = jax.random.fold_in(
                    jax.random.PRNGKey(cfg.seed), fold
                )
                model = dp.fit_dp(
                    cfg, x_train, key,
                    PrivacySpec(epsilon=eps, delta=DELTA, clip=clip),
                )
                scores = np.asarray(
                    daef.reconstruction_error(cfg, model, x_test)
                )
                by_eps.setdefault(str(eps), []).append(
                    rank_auc(scores, y_test)
                )
        record = {
            "dataset": name,
            "dim": ds.dim,
            "folds": args.folds,
            "auc": {k: float(np.mean(v)) for k, v in by_eps.items()},
            "auc_std": {k: float(np.std(v)) for k, v in by_eps.items()},
        }
        record["gap_at_eps8"] = record["auc"]["inf"] - record["auc"]["8.0"]
        records.append(record)
        sweep = " ".join(
            f"eps={k}:{record['auc'][k]:.3f}"
            for k in [str(e) for e in EPSILONS] + ["inf"]
        )
        print(f"{name}: {sweep} (gap@8 {record['gap_at_eps8']:+.3f})")
    return records


def secagg_overhead(args) -> dict:
    """One round's exchange states: masked aggregate must be bit-exact with
    the unmasked sum; record wall time for both paths."""
    ds = synthetic.make_dataset("cardio", seed=0, scale=0.5 * args.scale)
    cfg = _dataset_config(ds.dim)
    x_train, _, _ = ds.train_test_split(fold=0)
    x_train = x_train.astype(np.float32)
    bounds = np.linspace(0, x_train.shape[1], args.sites + 1).astype(int)
    parts = [x_train[:, bounds[i]:bounds[i + 1]] for i in range(args.sites)]

    engine = DAEFEngine(cfg, ExecutionPlan(federation="async",
                                           merge="pairwise"))
    session = engine.session()
    states = session._local_states(list(enumerate(parts)))
    leaves = [federated.exchange_to_additive(cfg, st) for st in states]
    wires = [secagg.encode(lv, 20) for lv in leaves]
    sites = list(range(args.sites))

    t0 = time.perf_counter()
    for _ in range(args.repeats):
        plain = wires[0]
        for w in wires[1:]:
            plain = secagg.add_wires(plain, w)
    t_plain = (time.perf_counter() - t0) / args.repeats

    t0 = time.perf_counter()
    for _ in range(args.repeats):
        masked = [secagg.mask_wire(w, s, sites, "bench-secret", 1)
                  for s, w in zip(sites, wires)]
        agg = secagg.aggregate(masked, "pairwise")
    t_masked = (time.perf_counter() - t0) / args.repeats

    bit_exact = all(
        np.array_equal(a, p) for a, p in zip(agg, plain)
    )
    wire_bytes = int(sum(w.nbytes for w in wires[0]))
    out = {
        "sites": args.sites,
        "bit_exact": bool(bit_exact),
        "wire_bytes_per_site": wire_bytes,
        "plain_ms_per_round": t_plain * 1e3,
        "masked_ms_per_round": t_masked * 1e3,
        "overhead_x": t_masked / max(t_plain, 1e-9),
    }
    print(f"secagg: bit_exact={bit_exact}, "
          f"{out['masked_ms_per_round']:.2f} ms masked vs "
          f"{out['plain_ms_per_round']:.2f} ms plain per round "
          f"({args.sites} sites, {wire_bytes} wire bytes/site)")
    assert bit_exact, "masked aggregate diverged from the unmasked sum"
    return out


def append_trajectory(record: dict, out: str) -> None:
    path = Path(out)
    if not path.is_absolute():
        path = REPO_ROOT / path
    history = []
    if path.exists():
        history = json.loads(path.read_text())
    history.append(record)
    path.write_text(json.dumps(history, indent=2, sort_keys=True) + "\n")
    print(f"appended 1 record -> {out} ({len(history)} total in trajectory)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--folds", type=int, default=3,
                    help="cross-validation folds averaged per epsilon")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="multiplier on each dataset's base scale")
    ap.add_argument("--sites", type=int, default=8,
                    help="sites in the secagg overhead round")
    ap.add_argument("--repeats", type=int, default=5,
                    help="timing repeats for the secagg round")
    ap.add_argument("--out", default="BENCH_privacy.json")
    args = ap.parse_args()

    record = {
        "epsilons": list(EPSILONS),
        "delta": DELTA,
        "sweep": auc_sweep(args),
        "secagg": secagg_overhead(args),
    }
    append_trajectory(record, args.out)


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
