"""Traffic kind ``fed_round``: a closed loop of federated rounds, each two
calls through ``DAEFEngine`` under ``ExecutionPlan(mode="mesh", tenants=K,
mesh_devices=<the cell's chips>, merge="tree")``: ``fit`` of every site
with the federation's shared seed, then ``reduce(fleet, group_size=K)`` to
one model, its weights solved once at the root.

Set-up makes ``datasets`` seeded federations of ``tenants`` sites
(``fed_synth.py``) and places each site's data on the chip that hosts the
site, sharded over the plan's tenant mesh: raw data never travel in a
federated round, so a round passes no host array.  It warms one round up.
The window runs rounds over the federations in turn, each timed to
``block_until_ready`` of the merged weights, biases and training errors,
until ``--seconds`` have passed.  Traffic keys: ``datasets`` and
``compare_sites``.

Check: for each federation, one of the window's merged models, drawn from
the seed, against the plain reference's federation of the same sites
(``reference.federate``: every site's encoder Gram and layer statistics
summed and solved once; DAEF's merge is exact only for the encoder, so a
pooled fit is no reference).  What the sites exchange is held against the
reference's sums: ``stats_gap`` over the encoder Gram and every layer's
G, ``encoder_gap`` over the latent subspace of the merged encoder.

The root solve is held by ``solve_gap``, the normwise backward error of the
merged weights in the reference's summed systems ``(G + lam I) w = m``,
evaluated in float64.  At this size the weights themselves are not
determined in float32: the summed last-layer system has a condition number
of about 1e8 (a ridge of 0.9 against a Gram of 1.5 M samples with norm
about 8e7, less than the rounding of a float32 factorization, some 6e-8 of
that norm), so any float32 solve,
the reference's included, departs from the float64 solve of the same sums
by tens of percent in the weights and the held-out scores.  A
backward-stable solve still leaves a residual near the float32 rounding of
the system, while weights that solve other sums leave one of their
difference.  The held-out scores of both models, against each other
(``score_gap``, ``quantile_gap``) and against the float64 solve
(``witness_score_gap``, ``reference_witness_score_gap``), are read for the
record and bounded by no limit.

The merged model's training errors are, by the program's definition, each
site's local model's errors in site order, and are held against the
reference's local fits of the compared sites (``train_quantile_gap``).
``train_count_gap`` counts the training errors missing or added over all
sites (exact).
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import check
import fed_synth
import flops
import program
import reference


def merge_flops(layer_sizes, n: int, sites: int) -> float:
    """Operations of one tree merge of ``sites`` site models to one,
    counted from the algorithm (the operations of the module docstring of
    ``flops.py``): ``sites - 1`` pairwise merges, then one solve.

    * a pairwise merge: the encoder's concat-SVD of [U_a S_a | U_b S_b]
      (m0 x 2 m0) for U and S, ``2 m0^2`` to form it and ``16 m0^3`` for
      the SVD (Golub and Van Loan, "Matrix Computations", table of SVD
      costs: Sigma and V of a 2 m0 x m0 matrix, ``4 m n^2 + 8 n^3``); each
      decoder layer's (G, M) sums, ``o (a^2 + a)``, and the last layer's,
      ``a^2 + a m0``; the training errors are concatenated, not computed;
    * the solve at the root: each decoder layer's ``o`` Cholesky solves,
      ``o (a^3 / 3 + 2 a^2)``, and the last layer's, ``a^3 / 3 + 2 a^2 m0``.

    ``n`` does not enter: a merge reads statistics, not samples."""
    sizes = tuple(layer_sizes)
    m0 = sizes[0]
    pair = 2.0 * m0 * m0 + 16.0 * m0 ** 3
    root = 0.0
    for li in range(2, len(sizes) - 1):
        o, a = sizes[li - 1], sizes[li] + 1
        pair += o * (a * a + a)
        root += o * (a ** 3 / 3.0 + 2.0 * a * a)
    a = sizes[-2] + 1
    pair += a * a + a * m0
    root += a ** 3 / 3.0 + 2.0 * a * a * m0
    return (sites - 1) * pair + root


def _engine(cfg: dict, chips: int):
    from repro.engine import DAEFEngine, ExecutionPlan

    return DAEFEngine(program.daef_config(cfg),
                      ExecutionPlan(mode="mesh", tenants=int(cfg["tenants"]),
                                    mesh_devices=chips, merge="tree"))


def _group(cfg: dict) -> int:
    group = cfg["federation"]["group"]
    return int(cfg["tenants"]) if group == "all" else int(group)


def _seeds(cfg: dict) -> np.ndarray:
    """Every site's seed: the federation's one shared seed (the paper's
    shared stage-1 randomness)."""
    return np.full(int(cfg["tenants"]), int(cfg["federation"]["seed"]), np.int32)


def _weights(fleet):
    """What a round hands back that the check reads: the merged weights,
    biases and pooled training errors, and the merged state the weights
    were solved from (encoder factors, layer statistics)."""
    model = fleet.model
    return (model.weights, model.biases, model.train_errors, model.encoder_factors,
            model.layer_knowledge)


def setup(cell) -> dict:
    from repro.core import fleet_sharded

    cfg = cell.config
    engine = _engine(cfg, cell.chips)
    spec = fleet_sharded.tenant_sharding(engine.mesh)
    train, test = fed_synth.federations(cell.seed, program.shape(cfg), int(cfg["tenants"]),
                                        int(cell.traffic["datasets"]),
                                        place=lambda a: jax.device_put(a, spec))
    seeds = _seeds(cfg)
    fitted = engine.fit(train[0], seeds=seeds)
    fleet_shape = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding), fitted)
    jax.block_until_ready(_weights(engine.reduce(fitted, group_size=_group(cfg))))
    del fitted
    return {"engine": engine, "train": train, "test": test, "seeds": seeds,
            "fleet_shape": fleet_shape}


def window(cell, state) -> dict:
    engine, xs, seeds = state["engine"], state["train"], state["seeds"]
    cfg = cell.config
    k, group = int(cfg["tenants"]), _group(cfg)
    n = xs[0].shape[-1]
    draw = np.random.default_rng([int(cell.seed) % 2**63, 11])
    seen, kept = [0] * len(xs), {}
    rounds, fit_s, reduce_s = 0, 0.0, 0.0
    start = time.perf_counter()
    done = start
    while done - start < cell.seconds:
        d = rounds % len(xs)
        with jax.profiler.TraceAnnotation("bench.round.fit"):
            t0 = time.perf_counter()
            fitted = engine.fit(xs[d], seeds=seeds)
            t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.round.reduce"):
            merged = engine.reduce(fitted, group_size=group)
            t2 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.round.wait"):
            params = jax.block_until_ready(_weights(merged))
        del fitted, merged
        done = time.perf_counter()
        fit_s += t1 - t0
        reduce_s += t2 - t1
        rounds += 1
        # one merged model per federation, uniform over its rounds (a
        # reservoir of one, drawn from the seed)
        seen[d] += 1
        if draw.integers(seen[d]) == 0:
            kept[d] = params
    window_s = done - start

    sites = program.pick(cell.seed, 2, k, int(cell.traffic["compare_sites"]))
    sizes = cfg["layer_sizes"]
    return {
        "attempted": rounds, "failed": 0, "rounds": rounds,
        "window_s": window_s, "fit_host_s": fit_s, "reduce_host_s": reduce_s,
        "samples_per_round": k * n,
        "flops_per_round": flops.fit_flops(sizes, n, k) + merge_flops(sizes, n, k),
        "e2e": {"fit_samples_per_s": rounds * k * n / window_s},
        "inputs": (state["train"], state["test"]),
        "engine": engine, "fleet_shape": state["fleet_shape"], "group_size": group,
        "outputs": [(d, sites.tolist(), kept[d]) for d in sorted(kept)],
        "notes": [f"{rounds} rounds of {k} sites x {n} samples in {window_s} s "
                  f"(round_s {window_s / max(rounds, 1)}), {fit_s} s of it inside "
                  f"DAEFEngine.fit and {reduce_s} s inside DAEFEngine.reduce"],
    }


@partial(jax.jit, static_argnames=("arch", "p"))
def _summed_state(arch, xs, seed, p=reference.FLOAT32):
    """The reference's exchanged state of the sites xs [K, m0, n]: the
    summed encoder Gram and each layer's summed (G, M), as
    ``reference.federate`` sums them."""
    keys = reference.layer_keys(seed, len(arch.layer_sizes))

    def site(carry, x):
        g_enc, stats, _ = reference._parts(arch, x, keys, p)
        return jax.tree.map(jnp.add, carry, (g_enc, stats)), None

    shapes = jax.eval_shape(lambda x: reference._parts(arch, x, keys, p)[:2], xs[0])
    zero = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), shapes)
    return jax.lax.scan(site, zero, xs)[0]


def _state_gap(enc, knowledge, ref_state) -> float:
    """Largest relative Frobenius gap over the Grams of the exchanged state:
    the encoder's (``U S^2 U^T`` of the merged factors) and each layer's G.
    Each is a sum of positive semi-definite matrices, so no cancellation
    inflates its relative gap; the M sums do cancel (the data are centred)
    and are left to the scores."""
    u, s = (np.asarray(a, np.float64) for a in enc)
    got = [(u * s * s) @ u.T] + [np.asarray(g_m[0], np.float64) for g_m in knowledge]
    ref_g, ref_stats = ref_state
    want = [ref_g] + [g_m[0] for g_m in ref_stats]
    return max(check.rel_gap(a, b) for a, b in zip(got, want, strict=True))


def _subspace_gap(w, ref_w) -> float:
    """||P - P_ref||_F / ||P_ref||_F of the projectors onto the encoders'
    latent subspaces (blind to the order and signs of the columns)."""
    w, ref_w = np.asarray(w, np.float64), np.asarray(ref_w, np.float64)
    return check.rel_gap(w @ w.T, ref_w @ ref_w.T)


def _solved(arch, ref_state):
    """The float64 solve of the reference's summed state: the encoder's
    leading eigenvectors (signs as ``reference``), each decoder layer's
    augmented weights ``[o, a]`` and the last layer's ``[a, m0]``."""
    g_enc, stats = jax.tree.map(lambda a: np.asarray(a, np.float64), ref_state)
    m1 = arch.layer_sizes[1]
    u = np.linalg.eigh(g_enc)[1][:, ::-1][:, :m1]
    sign = np.sign(u[np.argmax(np.abs(u), axis=0), np.arange(m1)])
    enc = u * np.where(sign == 0, 1.0, sign)
    hidden = [np.linalg.solve(g + arch.lam_hidden * np.eye(g.shape[-1]), m[..., None])[..., 0]
              for g, m in stats[:-1]]
    g, m = stats[-1]
    last = np.linalg.solve(g + arch.lam_last * np.eye(g.shape[0]), m.T)
    return enc, hidden, last


def _witness_scores(arch, solved, x) -> np.ndarray:
    """Per-sample reconstruction MSE of x [m0, n] under the float64 solve,
    the forward pass of ``reference`` in float64 (zero decoder biases)."""
    enc, hidden, last = solved
    x = np.asarray(x, np.float64)
    h = 1.0 / (1.0 + np.exp(-(enc.T @ x)))
    for w in hidden:
        h = 1.0 / (1.0 + np.exp(-(w[:, :-1].T @ h)))
    return np.mean((last[:-1].T @ h + last[-1][:, None] - x) ** 2, axis=0)


def _backward_error(a, w, b) -> float:
    """||a w - b|| / (||a||_2 ||w|| + ||b||) of one system (float64)."""
    return float(np.linalg.norm(a @ w - b)
                 / (np.linalg.norm(a, 2) * np.linalg.norm(w) + np.linalg.norm(b)))


def _solve_gap(arch, weights, biases, ref_state) -> float:
    """Largest normwise backward error of the merged weights in the
    reference's summed systems (float64).  The model keeps a decoder
    layer's weights without the auxiliary output bias (the last entry of
    each augmented solution), so that entry is the one that fits the
    system best: the least-squares ``t`` of ``a[:, :-1] w + a[:, -1] t = m``."""
    _, stats = jax.tree.map(lambda a: np.asarray(a, np.float64), ref_state)
    gaps = []
    for w, (g, m) in zip(weights[1:-1], stats[:-1], strict=True):
        w = np.asarray(w, np.float64)
        for j in range(g.shape[0]):
            a = g[j] + arch.lam_hidden * np.eye(g.shape[-1])
            r = a[:, :-1] @ w[j] - m[j]
            t = -(a[:, -1] @ r) / (a[:, -1] @ a[:, -1])
            gaps.append(_backward_error(a, np.append(w[j], t), m[j]))
    g, m = stats[-1]
    w = np.concatenate([np.asarray(weights[-1], np.float64),
                        np.asarray(biases[-1], np.float64)[None]])
    gaps.append(_backward_error(g + arch.lam_last * np.eye(g.shape[0]), w, m.T))
    return max(gaps)


def _site_errors(errs: np.ndarray, site: int, n: int) -> np.ndarray:
    """Site ``site``'s ``n`` training errors of a pool in site order; errors
    missing from the pool read 0."""
    got = errs[site * n:(site + 1) * n]
    return np.pad(got, (0, n - got.size))


def readings(cell, record, outputs) -> dict:
    """Per compared federation: ``stats_gap`` and ``encoder_gap`` of the
    merged state against the reference's sums, ``solve_gap`` of the merged
    weights in the reference's summed systems, ``train_quantile_gap`` of the
    compared sites' training errors against their local reference models'
    errors, ``train_count_gap`` (exact); for the record,
    ``check.model_numbers`` of the merged model against the reference's
    federation on the compared sites' pooled held-out samples, the held-out
    score gaps of both against the float64 solve of the reference's sums,
    and ``latent_eig_gap`` of the pooled training data (how well the
    federation's encoder is defined)."""
    cfg = cell.config
    arch = reference.Arch.from_config(cfg)
    seed = int(cfg["federation"]["seed"])
    k = int(cfg["tenants"])
    train, test = record["inputs"]
    items = []
    for d, sites, (ws, bs, errs, enc, knowledge) in outputs:
        x, held = np.asarray(train[d]), np.asarray(test[d])
        n = x.shape[-1]
        pooled = np.concatenate([held[s] for s in sites], axis=1)
        got = reference.Model(tuple(w[0] for w in ws), tuple(b[0] for b in bs))
        ref = reference.federate(arch, x, seed)
        ref_state = jax.device_get(_summed_state(arch, x, seed))
        item = check.model_numbers(got, ref, pooled)
        item["stats_gap"] = _state_gap(jax.tree.map(lambda a: a[0], enc),
                                       jax.tree.map(lambda a: a[0], knowledge), ref_state)
        item["encoder_gap"] = _subspace_gap(got.weights[0], ref.weights[0])
        item["solve_gap"] = _solve_gap(arch, got.weights, got.biases, ref_state)
        witness = _witness_scores(arch, _solved(arch, ref_state), pooled)
        item["witness_score_gap"] = check.rel_gap(reference.scores(got, pooled), witness)
        item["reference_witness_score_gap"] = check.rel_gap(reference.scores(ref, pooled),
                                                            witness)
        errs = np.asarray(errs, np.float32).ravel()
        item["train_count_gap"] = float(abs(errs.size - k * n))
        mine = [_site_errors(errs, s, n) for s in sites]
        theirs = [reference.scores(reference.fit(arch, x[s], seed), x[s]) for s in sites]
        item["train_quantile_gap"] = check.quantile_gap(np.concatenate(mine),
                                                        np.concatenate(theirs))
        item["latent_eig_gap"] = reference.latent_eig_gap(
            x.transpose(1, 0, 2).reshape(x.shape[1], -1), arch.layer_sizes[1])
        items.append(item)
    return check.summarize(items)


def control_outputs(cell, record, outputs):
    """The same items from the reference in bfloat16, in the program's
    place: its federation, the state it summed, and its local fits'
    training errors at the compared sites (the rest of the pool reads 0;
    only those are read)."""
    cfg = cell.config
    arch = reference.Arch.from_config(cfg)
    seed = int(cfg["federation"]["seed"])
    low_p = reference.BFLOAT16
    train, _ = record["inputs"]
    out = []
    for d, sites, _ in outputs:
        x = np.asarray(train[d])
        n = x.shape[-1]
        low = reference.federate(arch, x, seed, low_p)
        errs = np.zeros(x.shape[0] * n, np.float32)
        for s in sites:
            local = reference.fit(arch, x[s], seed, low_p)
            errs[s * n:(s + 1) * n] = np.asarray(reference.scores(local, x[s], low_p),
                                                 np.float32)
        ws, bs = jax.device_get((low.weights, low.biases))
        g_enc, stats = jax.device_get(_summed_state(arch, x, seed, low_p))
        vals, vecs = np.linalg.eigh(np.asarray(g_enc, np.float64))
        enc = (vecs[None], np.sqrt(np.maximum(vals, 0.0))[None])
        knowledge = [tuple(np.asarray(a, np.float64)[None] for a in g_m) for g_m in stats]
        out.append((d, sites, (tuple(np.asarray(w, np.float32)[None] for w in ws),
                               tuple(np.asarray(b, np.float32)[None] for b in bs),
                               errs[None], enc, knowledge)))
    return out
