"""What the generators share: the program's config from a configuration file,
the replicas a configuration describes, and the choice of compared items."""
from __future__ import annotations

import numpy as np

import synth


def daef_config(cfg: dict):
    """The program's ``DAEFConfig`` for a configuration file; every other
    field keeps the program's default (``stats_backend`` included, so
    ``"auto"`` resolves as users get it)."""
    from repro.core import daef

    return daef.DAEFConfig(layer_sizes=tuple(cfg["layer_sizes"]),
                           lam_hidden=float(cfg["lam_hidden"]),
                           lam_last=float(cfg["lam_last"]),
                           act_hidden=cfg["act_hidden"], act_last=cfg["act_last"])


def shape(cfg: dict) -> synth.Shape:
    d = cfg["dataset"]
    return synth.Shape.from_table(d["dim"], d["n_total"], d["n_anomaly"],
                                  d.get("fold", 0), d.get("n_folds", 10))


def tenant_seeds(cfg: dict) -> np.ndarray:
    """Per-tenant shared-randomness seeds: tenant t's own seed t, so each
    tenant's model is independent."""
    return np.arange(int(cfg.get("tenants", 1)), dtype=np.int32)


def pick(seed: int, salt: int, population: int, count: int) -> np.ndarray:
    """``count`` distinct indices of ``population``, drawn from the seed."""
    rng = np.random.default_rng([int(seed) % 2**63, salt])
    return np.sort(rng.choice(population, size=min(count, population), replace=False))
