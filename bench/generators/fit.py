"""Traffic kind ``fit``: a closed loop of one-shot fits through
``DAEFEngine.fit`` under the default plan (a fleet plan, ``mode="vmap"``, for
a configuration with ``tenants`` > 1).

Set-up makes ``datasets`` seeded replicas of the configuration (each a fleet
of ``tenants``), keeps them as host arrays, as users pass them, and warms the
fit up once.  The window fits them in turn, each call timed to
``block_until_ready``, until ``--seconds`` have passed.  Traffic keys:
``datasets`` and ``compare_tenants`` (tenants of each compared fleet fit).

Check: for each dataset, one of the window's fits on it, drawn from the seed.
Its models, and the plain reference fitted on the same data with the same
seed, score the held-out normals and anomalies through the reference's
float32 forward pass; the training errors the fit returned are held against
the reference model's errors on the training data (``check.py`` names the
numbers).  This covers the encoder, every decoder layer's statistics and
solve, and the last layer.
"""
from __future__ import annotations

import time

import jax
import numpy as np

import check
import flops
import program
import reference
import synth


def setup(cell) -> dict:
    from repro.engine import DAEFEngine, ExecutionPlan

    cfg = cell.config
    k = int(cfg.get("tenants", 1))
    shape = program.shape(cfg)
    count = int(cell.traffic["datasets"])
    train, test = synth.replicas(cell.seed, shape, count * k)
    train = train.reshape(count, k, *train.shape[1:])
    test = test.reshape(count, k, *test.shape[1:])
    if k == 1:
        engine = DAEFEngine(program.daef_config(cfg))
        xs, kw = [train[d, 0] for d in range(count)], {}
    else:
        engine = DAEFEngine(program.daef_config(cfg),
                            ExecutionPlan(mode="vmap", tenants=k))
        xs, kw = [train[d] for d in range(count)], {"seeds": program.tenant_seeds(cfg)}
    jax.block_until_ready(_weights(engine.fit(xs[0], **kw)))
    return {"engine": engine, "xs": xs, "kw": kw, "train": train, "test": test}


def _weights(state):
    """What a fit hands back that the check reads: the weights, the biases
    and the per-sample training errors (the pool a threshold is drawn from)."""
    model = getattr(state, "model", state)
    return model.weights, model.biases, model.train_errors


def window(cell, state) -> dict:
    engine, xs, kw = state["engine"], state["xs"], state["kw"]
    fitted, host_s = [], 0.0
    start = time.perf_counter()
    done = start
    while done - start < cell.seconds:
        d = len(fitted) % len(xs)
        with jax.profiler.TraceAnnotation("bench.fit.call"):
            t0 = time.perf_counter()
            out = engine.fit(xs[d], **kw)
            host_s += time.perf_counter() - t0
        with jax.profiler.TraceAnnotation("bench.fit.wait"):
            params = jax.block_until_ready(_weights(out))
        del out
        done = time.perf_counter()
        fitted.append((d, params))
    window_s = done - start

    cfg, k = cell.config, int(cell.config.get("tenants", 1))
    n = state["train"].shape[-1]
    tenants = program.pick(cell.seed, 2, k, int(cell.traffic.get("compare_tenants", 1)))
    outputs = []
    for d in range(len(xs)):
        on_d = [i for i, (dd, _) in enumerate(fitted) if dd == d]
        if not on_d:
            continue
        # to the host first: indexing on the device would compile gathers
        ws, bs, errs = jax.device_get(
            fitted[on_d[int(program.pick(cell.seed, 10 + d, len(on_d), 1)[0])]][1])
        if k == 1:
            outputs.append((d, [0], [(ws, bs, errs)]))
        else:
            outputs.append((d, tenants.tolist(),
                            [(tuple(w[t] for w in ws), tuple(b[t] for b in bs), errs[t])
                             for t in tenants]))
    return {
        "attempted": len(fitted), "failed": 0, "fits": len(fitted),
        "window_s": window_s, "host_s": host_s,
        "samples_per_fit": k * n,
        "flops_per_fit": flops.fit_flops(cfg["layer_sizes"], n, k),
        "e2e": {"fit_samples_per_s": len(fitted) * k * n / window_s},
        "inputs": (state["train"], state["test"]),
        "outputs": outputs,
        "notes": [f"{len(fitted)} fits of {k} x {n} samples in {window_s} s, "
                  f"{host_s} s of it inside DAEFEngine.fit"],
    }


def readings(cell, record, outputs) -> dict:
    """Per compared model: its ``check.model_numbers`` on the held-out set,
    ``train_quantile_gap`` of the training errors it returned against the
    reference model's, ``train_count_gap`` (how many training samples the
    returned errors miss or add: exact), and, for the look at a departure,
    ``latent_eig_gap`` (relative gap between the eigenvalues of X X^T at the
    latent width, which decides how well the encoder is defined)."""
    train, test = record["inputs"]
    arch = reference.Arch.from_config(cell.config)
    seeds = program.tenant_seeds(cell.config)
    items = []
    for d, tenants, models in outputs:
        for t, (ws, bs, errs) in zip(tenants, models, strict=True):
            ref = reference.fit(arch, train[d, t], int(seeds[t]))
            got = reference.Model(tuple(ws), tuple(bs))
            item = check.model_numbers(got, ref, test[d, t])
            errs = np.asarray(errs).ravel()
            item["train_quantile_gap"] = check.quantile_gap(
                errs, reference.scores(ref, train[d, t]))
            item["train_count_gap"] = float(abs(errs.size - train.shape[-1]))
            item["latent_eig_gap"] = reference.latent_eig_gap(train[d, t],
                                                              arch.layer_sizes[1])
            items.append(item)
    out = check.summarize(items)
    worst = max(items, key=lambda it: it["recon_gap"])
    out["latent_eig_gap.of_worst"] = worst["latent_eig_gap"]
    return out


def control_outputs(cell, record, outputs):
    """The same items from the reference in bfloat16, in the program's place."""
    train, _ = record["inputs"]
    arch = reference.Arch.from_config(cell.config)
    seeds = program.tenant_seeds(cell.config)
    out = []
    for d, tenants, _ in outputs:
        models = []
        for t in tenants:
            low = reference.fit(arch, train[d, t], int(seeds[t]), reference.BFLOAT16)
            errs = reference.scores(low, train[d, t], reference.BFLOAT16)
            models.append(jax.device_get((low.weights, low.biases, errs)))
        out.append((d, tenants, models))
    return out

