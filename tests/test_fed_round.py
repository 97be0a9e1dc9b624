"""A federated round on a four-device tenant mesh, against a plain reference.

One round is what a DAEF federation runs: ``DAEFEngine.fit`` of every site
under ``ExecutionPlan(mode="mesh", merge="tree")`` with one shared seed,
then ``reduce`` of all sites to one model, its weights solved once at the
root.  At a small size (8 sites, 2 a device, the paper's cardio widths, 200
samples a site) the merged model is held against the benchmark's plain
float32 ``jax.numpy`` federation at ``highest`` precision
(``bench/reference.py``: every site's encoder Gram and layer statistics
summed, solved once), and the same round with the butterfly's exchange
taken out must depart from it.  The round's host spans and the tree
program's named scopes are checked on the same run.

Everything multi-device runs in one subprocess with 4 forced host devices
(``_mesh_harness.run_on_devices``); the tests read its report.
"""
import json

import numpy as np
import pytest

from _mesh_harness import ROOT, run_on_devices

K, N = 8, 200
SIZES = (21, 4, 8, 12, 16, 21)

# The merged model's held-out scores against the reference's, as a relative
# 2-norm gap.  The program reaches the federation's encoder through three
# levels of float32 concat-SVDs of [U S | U S] blocks and its decoders
# through Cholesky solves; the reference takes one eigh of the summed Gram
# and LU solves, every product at highest precision.  Both are float32, so
# the gap is rounding: about 1e-7 here.  1e-4 leaves room for other BLAS
# builds, while a round that loses the exchange (each device merges only its
# own two sites) departs by about 14%.
SCORE_TOL = 1e-4

_ROUND = f"""
import contextlib, importlib.util
from repro import obs
from repro.core import daef, fleet_sharded
from repro.engine import DAEFEngine, ExecutionPlan

spec = importlib.util.spec_from_file_location(
    "bench_reference", {str(ROOT)!r} + "/bench/reference.py")
reference = importlib.util.module_from_spec(spec)
sys.modules["bench_reference"] = reference
spec.loader.exec_module(reference)

K, N, SIZES = {K}, {N}, {SIZES}
rng = np.random.default_rng(0)
# one phenomenon, observed by every site around an offset of its own
mix = rng.normal(size=(SIZES[0], 7)) * np.linspace(2.0, 0.5, 7)
def site(k):
    z = rng.normal(size=(7, N + 100)) + 0.5 * rng.normal(size=(7, 1))
    return mix @ np.tanh(z) + 0.1 * rng.normal(size=(SIZES[0], N + 100))
data = np.stack([site(k) for k in range(K)])
data = (data - data[:, :, :N].mean(axis=(0, 2), keepdims=True)) / \\
    data[:, :, :N].std(axis=(0, 2), keepdims=True)
xs = np.asarray(data[:, :, :N], np.float32)
held = np.asarray(np.concatenate(list(data[:, :, N:]), axis=1), np.float32)

cfg = daef.DAEFConfig(layer_sizes=SIZES, lam_hidden=0.9, lam_last=0.9)
arch = reference.Arch(SIZES, 0.9, 0.9)
ref = reference.federate(arch, xs, 0)
ref_scores = np.asarray(reference.scores(ref, held), np.float64)

def round_gap():
    engine = DAEFEngine(cfg, ExecutionPlan(mode="mesh", tenants=K, mesh_devices=4,
                                           merge="tree"))
    fl = engine.fit(xs, seeds=np.zeros(K, np.int32))
    merged = engine.reduce(fl, group_size=K)
    got = reference.Model(tuple(w[0] for w in merged.model.weights),
                          tuple(b[0] for b in merged.model.biases))
    s = np.asarray(reference.scores(got, held), np.float64)
    errs_equal = bool(np.array_equal(np.asarray(merged.model.train_errors).ravel(),
                                     np.asarray(fl.model.train_errors).ravel()))
    return (float(np.linalg.norm(s - ref_scores) / np.linalg.norm(ref_scores)),
            errs_equal, merged.size, engine, fl)

before = obs.snapshot()
gap, errs_equal, size, engine, fl = round_gap()
after = obs.snapshot()
spans = {{p: after[p]["count"] - before.get(p, {{"count": 0}})["count"]
         for p in after if p.startswith("engine.reduce")}}
sent = after["engine.reduce/reduce.dispatch"]["attrs"]["exchange_bytes"] - \\
    before.get("engine.reduce/reduce.dispatch", {{"attrs": {{}}}})["attrs"].get(
        "exchange_bytes", 0)
shape = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), fl)
text = engine.lower_reduce(shape, K).compile().as_text()
scopes = {{s: text.count(s) for s in
          ("merge_local", "merge_exchange", "merge_cross", "merge_solve")}}

@contextlib.contextmanager
def no_exchange():
    old = jax.lax.ppermute
    fleet_sharded._merge_tree_fn.cache_clear()
    jax.clear_caches()
    jax.lax.ppermute = lambda x, axis_name, perm: x
    try:
        yield
    finally:
        jax.lax.ppermute = old
        fleet_sharded._merge_tree_fn.cache_clear()
        jax.clear_caches()

with no_exchange():
    broken_gap = round_gap()[0]

print("REPORT " + json.dumps({{
    "gap": gap, "broken_gap": broken_gap, "errs_equal": errs_equal,
    "size": size, "spans": spans, "sent": sent, "scopes": scopes,
    "errors_bytes": 4 * N, "fixed_bytes": int(sum(
        np.prod(a.shape[1:]) * a.dtype.itemsize for a in jax.tree.leaves(
            (fl.model.encoder_factors, fl.model.layer_knowledge))))}}))
"""


@pytest.fixture(scope="module")
def report():
    out = run_on_devices("import json", _ROUND, n_devices=4)
    line = next(ln for ln in out.splitlines() if ln.startswith("REPORT "))
    return json.loads(line[len("REPORT "):])


def test_the_round_matches_the_plain_federation(report):
    assert report["size"] == 1
    assert report["gap"] < SCORE_TOL, report["gap"]
    # the merged model's training errors are every site's, in site order
    assert report["errs_equal"]


def test_a_round_without_the_exchange_departs(report):
    assert report["broken_gap"] > 100 * SCORE_TOL, report["broken_gap"]


def test_reduce_records_its_spans_once_a_round(report):
    assert report["spans"] == {
        "engine.reduce": 1, "engine.reduce/reduce.prepare": 1,
        "engine.reduce/reduce.place": 1, "engine.reduce/reduce.dispatch": 1,
        "engine.reduce/reduce.dedup": 1}


def test_the_exchange_counter_is_the_butterflys_bytes(report):
    # 2 sites a device merge locally to one slot (1 level), then 2 ppermute
    # levels each send the slot's state, its error pool 2 and 4 sites long
    fixed, errors = report["fixed_bytes"], report["errors_bytes"]
    assert report["sent"] == (fixed + 2 * errors) + (fixed + 4 * errors)


def test_lower_reduce_carries_the_four_merge_scopes(report):
    assert all(count > 0 for count in report["scopes"].values()), report["scopes"]


def test_lower_reduce_needs_the_tree_merge():
    import jax.numpy as jnp

    from repro.core import daef
    from repro.engine import DAEFEngine, ExecutionPlan, PlanError

    cfg = daef.DAEFConfig(layer_sizes=(6, 2, 3, 6))
    engine = DAEFEngine(cfg, ExecutionPlan(mode="vmap", tenants=2, merge="pairwise"))
    fl = engine.fit(np.random.default_rng(0).normal(size=(2, 6, 20)).astype("float32"),
                    seeds=jnp.zeros(2, jnp.int32))
    with pytest.raises(PlanError, match="merge='tree'"):
        engine.lower_reduce(fl, 2)
