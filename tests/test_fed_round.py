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
program's named scopes are checked on the same run, and so is the
encoder's route through the tree: under ``method="gram"`` the sites'
encoders are summed as Grams and factored by one eigh at the root, under
``method="svd"`` merged by a concat-SVD at every pair.

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
# 2-norm gap.  The program reaches the federation's encoder through float32
# sums of the sites' encoder Grams, each rebuilt from its factors as
# U S^2 U^T, and one eigh at the root, and its decoders through Cholesky
# solves; the reference takes one eigh of the summed Gram and LU solves,
# every product at highest precision.  Both are float32, so the gap is
# rounding: about 1e-7 here.  1e-4 leaves room for other BLAS
# builds, while a round that loses the exchange (each device merges only its
# own two sites) departs by about 14%.
SCORE_TOL = 1e-4

# The Gram-sum tree's merged encoder against the concat-SVD merge of the
# same eight site factors (float32 both, relative gaps): the Gram U S^2 U^T
# (about 1.2e-6 here), the projector onto the m1 = 4 latent directions
# (7e-6) and the singular values, each off by at most 1.2e-6 of the
# largest (an eigh gives s^2 to the float32 rounding of the largest s^2).
# A merge that loses one of the eight sites departs by about 1/8.
ENC_GRAM_TOL, ENC_PROJ_TOL, ENC_S_TOL = 1e-5, 1e-4, 1e-5

_ROUND = f"""
import contextlib, functools, importlib.util, re
from repro import obs
from repro.core import daef, dsvd, fleet_sharded
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

# (kind, first output shape) of every SVD and eigh custom call
def factorizations(text):
    return [(m.group(2), m.group(1)) for m in re.finditer(
        r"= [(]f32[[]([0-9,]+)[]].*custom_call_target=.[a-z_]*?(gesdd|syevd)",
        text)]

def counted(cfg_m, fl_m):
    before = obs.snapshot()
    engine_m = DAEFEngine(cfg_m, ExecutionPlan(mode="mesh", tenants=K,
                                               mesh_devices=4, merge="tree"))
    merged = engine_m.reduce(fl_m, group_size=K)
    after = obs.snapshot()
    path = "engine.reduce/reduce.dispatch"
    got = after[path]["attrs"]["encoder_factorizations"] - before.get(
        path, {{"attrs": {{}}}})["attrs"].get("encoder_factorizations", 0)
    shape_m = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), fl_m)
    calls = factorizations(engine_m.lower_reduce(shape_m, K).compile().as_text())
    return [got, calls], merged

cfg_svd = daef.DAEFConfig(layer_sizes=SIZES, lam_hidden=0.9, lam_last=0.9,
                          method="svd")
fl_svd = DAEFEngine(cfg_svd, ExecutionPlan(mode="mesh", tenants=K, mesh_devices=4,
                                           merge="tree")).fit(
    xs, seeds=np.zeros(K, np.int32))
(gram_route, merged), (svd_route, _) = counted(cfg, fl), counted(cfg_svd, fl_svd)
routes = {{"gram": gram_route, "svd": svd_route}}

# the encoder's two merge routes, on the same eight site factors
def f64(f):
    return dsvd.SvdFactors(*(np.asarray(a, np.float64) for a in f))
def gram64(f):
    return (f.u * f.s ** 2) @ f.u.T
def rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))
sites = [jax.tree.map(lambda a: a[i], fl.model.encoder_factors) for i in range(K)]
by_svd = f64(functools.reduce(dsvd.merge_pair, sites))
by_gram = f64(jax.tree.map(lambda a: a[0], merged.model.encoder_factors))
m1 = SIZES[1]
enc_gaps = {{
    "gram": rel(gram64(by_gram), gram64(by_svd)),
    "proj": rel(by_gram.u[:, :m1] @ by_gram.u[:, :m1].T,
                by_svd.u[:, :m1] @ by_svd.u[:, :m1].T),
    "s": float(np.max(np.abs(by_gram.s - by_svd.s)) / by_svd.s[0]),
    "shape": [list(by_gram.u.shape), list(by_svd.u.shape)]}}

# masked slots of the async federation's butterfly: the merge of the rest
mask = np.array([1, 0, 1, 1, 0, 1, 1, 1], np.float32)
keep = np.flatnonzero(mask)
enc_m, knw_m = fleet_sharded.merge_state_tree(
    cfg, fl.model.encoder_factors, fl.model.layer_knowledge, mask,
    mesh=fleet_sharded.tenant_mesh(4))
masked_gaps = [rel(gram64(f64(enc_m)), sum(gram64(f64(sites[i])) for i in keep))] + [
    rel(np.asarray(k_m.g, np.float64), np.asarray(k.g, np.float64)[keep].sum(0))
    for k_m, k in zip(knw_m, fl.model.layer_knowledge, strict=True)]

print("REPORT " + json.dumps({{
    "gap": gap, "broken_gap": broken_gap, "errs_equal": errs_equal,
    "size": size, "spans": spans, "sent": sent, "scopes": scopes,
    "routes": routes, "enc_gaps": enc_gaps, "masked_gaps": masked_gaps,
    "errors_bytes": 4 * N, "fixed_bytes": 4 * SIZES[0] ** 2 + int(sum(
        np.prod(a.shape[1:]) * a.dtype.itemsize
        for a in jax.tree.leaves(fl.model.layer_knowledge)))}}))
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
    # levels each send the slot's state, its error pool 2 and 4 sites long;
    # the state's encoder is its m0 x m0 float32 Gram
    fixed, errors = report["fixed_bytes"], report["errors_bytes"]
    assert report["sent"] == (fixed + 2 * errors) + (fixed + 4 * errors)


def test_the_gram_tree_factors_its_encoder_once_at_the_root(report):
    # one slot a device is left at the root: one eigh of a [1, 21, 21]
    # stack, and no SVD anywhere in the tree program
    count, calls = report["routes"]["gram"]
    assert count == 1
    assert calls == [["syevd", "1,21,21"]], calls


def test_the_svd_tree_keeps_a_concat_svd_per_pair(report):
    # 2 sites a device: 1 local pair and 1 pair after each of 2 exchanges,
    # each an SVD of the [21, 42] concatenation [U_a S_a | U_b S_b]
    count, calls = report["routes"]["svd"]
    assert count == 3
    assert [c for c in calls if c == ["gesdd", "1,21,42"]] == [["gesdd", "1,21,42"]] * 3
    assert not [c for c in calls if c[0] == "syevd"], calls


def test_the_gram_tree_encoder_matches_the_concat_svd_merge(report):
    gaps = report["enc_gaps"]
    assert gaps["shape"] == [[21, 21], [21, 21]]
    assert gaps["gram"] < ENC_GRAM_TOL, gaps
    assert gaps["proj"] < ENC_PROJ_TOL, gaps
    assert gaps["s"] < ENC_S_TOL, gaps


def test_masked_slots_drop_out_of_the_state_tree(report):
    # the encoder Gram and every layer's G of the six unmasked sites
    assert max(report["masked_gaps"]) < ENC_GRAM_TOL, report["masked_gaps"]


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
