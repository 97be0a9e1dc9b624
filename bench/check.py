"""The comparison that decides ``correct``.

Every number compared is a gap between what the timed path produced and the
plain reference (``reference.py``) on the same inputs, and each has a limit
of its own, kept per cell in ``checks/<workload>.json`` with the readings it
was set from (``PERF.md`` gives them too).  A run is correct when every
compared number is finite and within its limit and no work item failed.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

import reference


def rel_gap(got, ref) -> float:
    """||got - ref|| / ||ref|| over a vector of scores (float64)."""
    got = np.asarray(got, np.float64).ravel()
    ref = np.asarray(ref, np.float64).ravel()
    if got.shape != ref.shape:
        return float("inf")
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))


QUANTILES = (50.0, 90.0, 99.0)


def quantile_gap(got, ref) -> float:
    """Largest relative gap between the 50th, 90th and 99th percentiles."""
    got = np.percentile(np.asarray(got, np.float64), QUANTILES)
    ref = np.percentile(np.asarray(ref, np.float64), QUANTILES)
    return float(np.max(np.abs(got / ref - 1.0)))


def score_numbers(got, ref) -> dict[str, float]:
    """Gaps between two vectors of per-sample scores."""
    return {"score_gap": rel_gap(got, ref), "quantile_gap": quantile_gap(got, ref)}


def model_numbers(got, ref, x) -> dict[str, float]:
    """Gaps between two models on held-out samples x [m0, n], both through
    the reference's float32 forward pass: those of their scores, and
    ``recon_gap``, ``||R - R_ref||_F / ||R_ref||_F`` of their
    reconstructions R of x."""
    out = score_numbers(reference.scores(got, x), reference.scores(ref, x))
    out["recon_gap"] = rel_gap(reference.reconstruct(got, x), reference.reconstruct(ref, x))
    return out


def summarize(items: list[dict[str, float]]) -> dict[str, float]:
    """Per number over the compared items (models or tenants): the median,
    under the number's own name, and the largest, as ``<name>.max``."""
    out = {}
    for key in items[0]:
        vals = np.array([it[key] for it in items], np.float64)
        out[key] = float(np.median(vals))
        out[f"{key}.max"] = float(np.max(vals))
    return out


def limits(bench_dir: Path, workload: str) -> dict[str, float]:
    """The cell's limits, ``{number: limit}``."""
    spec = json.loads((bench_dir / "checks" / f"{workload}.json").read_text())
    return {name: float(entry["limit"]) for name, entry in spec["limits"].items()}


def verdict(readings: dict[str, float], lims: dict[str, float], failed: int):
    """(correct, {number: {"value", "limit"}}) over the numbers the cell's
    limits name; a missing or non-finite number is not correct."""
    table = {name: {"value": readings.get(name, float("nan")), "limit": lims[name]}
             for name in sorted(lims)}
    ok = failed == 0 and bool(table) and all(
        np.isfinite(e["value"]) and np.isfinite(e["limit"]) and e["value"] <= e["limit"]
        for e in table.values())
    return ok, table
