"""Async federation benchmark: barrier-free rounds vs the sync lockstep.

One logical experiment, appended to the ``BENCH_async.json`` trajectory
(default: the repo root, committed per PR so the perf history accumulates
in-tree): S sites each produce one data block per round; a straggler
fraction of sites misses each round and replays its backlog as one delta
when it returns.  Three measurements:

* **sync** — the lockstep baseline: a ``federation="sync"`` session where
  every round waits for ALL sites (the barrier: a straggler would stall the
  whole round, so sync is only measurable at full participation).  Its
  final model — every block from every site merged — is the CONVERGED
  REFERENCE the other trajectories are scored against.
* **async sweep** — ``federation="async"`` sessions at several straggler
  fractions: per-round wall time, the live model's disagreement with the
  reference (mean squared difference of held-out reconstructions), and
  ``rounds_to_converged`` — the first round within the convergence band.
  The story: rounds keep completing and the live model keeps approaching
  the all-data reference at straggler fractions where a barrier would
  stall every round; stragglers cost staleness, not liveness.
* **parity** — with no stragglers and ``max_staleness=0`` the async model
  must match the sync broker merge; the record carries the max abs weight
  difference (acceptance: within test_parity float32 tolerances).

Held-out reconstruction MSE per round is recorded too, but convergence is
deliberately NOT defined on it: the broker merge is the paper's
approximation (decoder statistics against local encoders), so absolute MSE
drifts with the number of merged contributions — model agreement with the
all-data reference is the quantity async-vs-sync actually controls.

  PYTHONPATH=src python benchmarks/async_federation.py [--sites 8 --rounds 5]
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import daef
from repro.engine import DAEFEngine, ExecutionPlan

REPO_ROOT = Path(__file__).resolve().parent.parent

LAYERS = (21, 6, 12, 21)
BLOCK = 256           # samples per site per round
HELD_OUT = 512        # shared held-out pool for the MSE trajectory
FRACTIONS = (0.0, 0.25, 0.5)


def _site_blocks(rng, sites: int, rounds: int):
    """(per-site round blocks, held-out pool) from one shared generative
    process — every site's data helps reconstruct the held-out pool."""
    mix = rng.normal(size=(LAYERS[0], LAYERS[1])).astype(np.float32)

    def draw(n):
        # 0.15 scale keeps the logsig encoder in its linear range — saturated
        # activations would make reconstruction quality meaningless.
        z = rng.normal(size=(LAYERS[1], n)).astype(np.float32)
        noise = 0.1 * rng.normal(size=(LAYERS[0], n)).astype(np.float32)
        return 0.15 * (mix @ z + noise)

    blocks = [[draw(BLOCK) for _ in range(rounds)] for _ in range(sites)]
    return blocks, draw(HELD_OUT)


def run_session(cfg, plan, blocks, x_test, straggle: float, seed: int):
    """Drive one session over the round schedule; stragglers bank a backlog
    and replay it whole on their next report.  Returns per-round times, the
    per-round held-out reconstructions and the final model."""
    sites, rounds = len(blocks), len(blocks[0])
    engine = DAEFEngine(cfg, plan)
    session = engine.session()
    rng = np.random.default_rng(seed)
    backlog: list[list] = [[] for _ in range(sites)]
    times, recons, mses = [], [], []
    for r in range(rounds):
        report = rng.random(sites) >= straggle
        if not report.any():
            report[rng.integers(sites)] = True
        parts = {}
        for t in range(sites):
            backlog[t].append(blocks[t][r])
            if report[t] or not plan.async_federation:
                # sync rounds are lockstep: the barrier forces EVERY site to
                # report (stragglers included) before the merge proceeds.
                parts[t] = np.concatenate(backlog[t], axis=1)
                backlog[t] = []
        t0 = time.perf_counter()
        model = session.round(parts)
        jax.block_until_ready(model.weights[-1])
        times.append(time.perf_counter() - t0)
        recon = daef.predict(cfg, model, x_test)
        recons.append(recon)
        mses.append(float(jnp.mean((recon - x_test) ** 2)))
    return times, recons, mses, session.model


def main(sites: int, rounds: int) -> dict:
    rng = np.random.default_rng(0)
    blocks, x_test = _site_blocks(rng, sites, rounds)
    x_test = jnp.asarray(x_test)
    cfg = daef.DAEFConfig(layer_sizes=LAYERS, lam_hidden=0.5, lam_last=0.9)

    sync_plan = ExecutionPlan(federation="sync", merge="pairwise")
    t_sync, recon_sync, mse_sync, sync_model = run_session(
        cfg, sync_plan, blocks, x_test, straggle=0.0, seed=1
    )
    ref = recon_sync[-1]  # the all-data converged reference
    # Band: disagreement must drop under 1% of the reference signal power.
    band = 0.01 * float(jnp.mean(ref**2))

    def against_ref(recons):
        return [float(jnp.mean((r - ref) ** 2)) for r in recons]

    d_sync = against_ref(recon_sync)
    print(f"sync   (barrier, {sites} sites x {rounds} rounds): "
          f"{sum(t_sync):.2f}s total, convergence band {band:.2e}")

    sweep = []
    parity = None
    for frac in FRACTIONS:
        plan = ExecutionPlan(
            federation="async", merge="tree",
            max_staleness=0 if frac == 0.0 else 1,
        )
        t_async, recon_async, mse_async, model = run_session(
            cfg, plan, blocks, x_test, straggle=frac, seed=1
        )
        d_async = against_ref(recon_async)
        converged = next(
            (r + 1 for r, d in enumerate(d_async) if d <= band), None
        )
        sweep.append({
            "straggler_fraction": frac,
            "max_staleness": plan.max_staleness,
            "total_s": sum(t_async),
            "round_ms": [t * 1e3 for t in t_async],
            "disagreement_trajectory": d_async,
            "mse_trajectory": mse_async,
            "rounds_to_converged": converged,
        })
        print(f"async  (straggle {frac:.2f}): {sum(t_async):.2f}s total, "
              f"final disagreement {d_async[-1]:.2e}, converged at round "
              f"{converged}")
        if frac == 0.0:
            diff = max(
                float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(model.weights, sync_model.weights, strict=True)
            )
            parity = {"max_abs_weight_diff": diff}
            print(f"parity (all report, max_staleness=0): max |dw| {diff:.2e}")

    return {
        "benchmark": "async_federation",
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "shape": {"sites": sites, "rounds": rounds, "block": BLOCK,
                  "layers": list(LAYERS)},
        "convergence_band": band,
        "sync": {"total_s": sum(t_sync),
                 "round_ms": [t * 1e3 for t in t_sync],
                 "disagreement_trajectory": d_sync,
                 "mse_trajectory": mse_sync},
        "async": sweep,
        "parity": parity,
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
    ap.add_argument("--sites", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_async.json"),
                    help="append the record to this JSON-list trajectory "
                         "(default: repo root, committed per PR)")
    a = ap.parse_args()
    record = main(sites=a.sites, rounds=a.rounds)
    if a.out:
        append_trajectory(record, a.out)
