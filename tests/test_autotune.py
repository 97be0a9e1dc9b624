"""Autotuner + fused-chunk kernel suite.

Covers the ISSUE-10 tentpole surface:

* cache mechanics — roundtrip through ``update_cache``/``lookup_block``,
  hit/miss determinism, corrupt files and stale entries degrading to the
  static heuristic with a one-time warning;
* backend auto-selection — ``stats_backend.resolve("auto")`` follows the
  cache's measured ``preferred_backend`` verdict per platform;
* wrapper resolution — an explicitly requested ``block_n`` is never
  silently clipped (RPR-adjacent satellite), and interpret mode is chosen
  by an explicit argument or, failing one, by the CPU backend alone;
* fused-chunk parity — ``rolann_fused_chunk`` == the einsum chunked path
  at ``test_parity`` tolerances across modes x dtypes, including c=1 and
  ragged-tail chunks;
* the one-launch guarantee — the fused ``accumulate_layer_stats`` jaxpr
  contains exactly ONE ``pallas_call`` and no ``dot_general`` outside it,
  i.e. the chunk activation never materializes between two XLA ops.
"""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import activations, elm_ae, rolann, stats_backend
from repro.kernels import autotune
from repro.kernels.rolann_stats import ops

# Parity bars match tests/test_parity.py; float64 still accumulates in f32
# inside the kernel (the documented deviation), hence the relative bar.
TOLS = {
    "float32": dict(atol=2e-4, rtol=2e-4),
    "float64": dict(atol=1e-6, rtol=1e-6),
}


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    """Point the autotuner at an empty per-test cache file and reset the
    module's in-memory copy on both sides of the test."""
    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path / "cache.json"))
    autotune.clear_cache()
    yield
    autotune.clear_cache()


# ---------------------------------------------------------------------------
# Cache mechanics
# ---------------------------------------------------------------------------

def test_static_heuristic_matches_legacy_clamp():
    for n, want in [(1, 128), (100, 128), (130, 256), (512, 512),
                    (513, 512), (100000, 512)]:
        assert autotune.static_block_n(n) == want


def test_cache_roundtrip_and_bucketing(tmp_path):
    key = autotune.shape_key("stats_acc", n=3000, m=8, o=7)
    assert key == "stats_acc:n4096:m8:o8"
    autotune.update_cache(platform="cpu", blocks={key: 1024},
                          preferred="einsum")
    # same bucket, different concrete shape -> hit
    assert autotune.lookup_block("stats_acc", n=2049, m=5, o=5,
                                 platform="cpu") == 1024
    # different kind or bucket -> miss
    assert autotune.lookup_block("stats", n=3000, m=8, o=7,
                                 platform="cpu") is None
    assert autotune.lookup_block("stats_acc", n=100, m=8, o=7,
                                 platform="cpu") is None
    # the file is valid JSON in the documented layout
    raw = json.loads(autotune.cache_path().read_text())
    assert raw["version"] == autotune.CACHE_VERSION
    assert raw["platforms"]["cpu"]["blocks"][key] == 1024
    assert raw["platforms"]["cpu"]["preferred_backend"] == "einsum"


def test_best_block_determinism_and_clamp():
    # miss -> static heuristic, deterministically
    a = autotune.best_block_n("stats", n=700, m=8, o=8, platform="cpu")
    b = autotune.best_block_n("stats", n=700, m=8, o=8, platform="cpu")
    assert a == b == autotune.static_block_n(700)
    # a cached 1024 win still clamps to next_pow2(n) for smaller chunks
    key = autotune.shape_key("stats", n=700, m=8, o=8)
    autotune.update_cache(platform="cpu", blocks={key: 1024})
    assert autotune.best_block_n("stats", n=700, m=8, o=8,
                                 platform="cpu") == 1024
    key_small = autotune.shape_key("stats", n=130, m=8, o=8)
    autotune.update_cache(platform="cpu", blocks={key_small: 1024})
    assert autotune.best_block_n("stats", n=130, m=8, o=8,
                                 platform="cpu") == 256


def test_corrupt_cache_warns_once_and_falls_back():
    autotune.cache_path().write_text("{not json")
    with pytest.warns(RuntimeWarning, match="unreadable"):
        got = autotune.best_block_n("stats", n=700, m=8, o=8, platform="cpu")
    assert got == autotune.static_block_n(700)
    # second read is silent (warning deduped) and still falls back
    with warnings.catch_warnings():
        warnings.simplefilter("error", category=RuntimeWarning)
        assert autotune.best_block_n("stats", n=700, m=8, o=8,
                                     platform="cpu") == 512


@pytest.mark.parametrize("bad", ["512", 300, 0, 1 << 20, True])
def test_stale_entry_warns_and_falls_back(bad):
    key = autotune.shape_key("stats", n=512, m=8, o=8)
    autotune.cache_path().write_text(json.dumps({
        "version": 1, "platforms": {"cpu": {"blocks": {key: bad}}},
    }))
    with pytest.warns(RuntimeWarning, match="invalid"):
        got = autotune.best_block_n("stats", n=512, m=8, o=8, platform="cpu")
    assert got == autotune.static_block_n(512)


def test_wrong_version_warns_and_falls_back():
    autotune.cache_path().write_text(json.dumps({"version": 99,
                                                 "platforms": {}}))
    with pytest.warns(RuntimeWarning, match="version"):
        assert autotune.load_cache() == {}


# ---------------------------------------------------------------------------
# "auto" backend resolution
# ---------------------------------------------------------------------------

def test_resolve_auto_follows_cache_verdict():
    plat = jax.default_backend()
    with pytest.warns(RuntimeWarning, match="no preferred_backend"):
        assert stats_backend.resolve("auto") == "einsum"  # unmeasured platform
    autotune.update_cache(platform=plat, preferred="fused")
    assert stats_backend.resolve("auto") == "fused"
    autotune.update_cache(platform=plat, preferred="einsum")
    assert stats_backend.resolve("auto") == "einsum"


def test_resolve_default_is_auto(monkeypatch):
    monkeypatch.delenv(stats_backend.ENV_VAR, raising=False)
    plat = jax.default_backend()
    autotune.update_cache(platform=plat, preferred="fused")
    assert stats_backend.DEFAULT == stats_backend.AUTO
    assert stats_backend.resolve(None) == "fused"
    # env still outranks the default chain
    monkeypatch.setenv(stats_backend.ENV_VAR, "einsum")
    assert stats_backend.resolve(None) == "einsum"


def test_unknown_preferred_backend_warns_to_einsum():
    autotune.cache_path().write_text(json.dumps({
        "version": 1,
        "platforms": {"cpu": {"preferred_backend": "cuda_graphs"}},
    }))
    with pytest.warns(RuntimeWarning, match="unknown preferred_backend"):
        assert autotune.preferred_backend("cpu") == "einsum"


# ---------------------------------------------------------------------------
# Wrapper resolution: explicit block_n, interpret override hook
# ---------------------------------------------------------------------------

def test_explicit_block_n_clip_warns():
    with pytest.warns(RuntimeWarning, match="clipped"):
        assert ops._resolve_block_n(100000, 1024) == 512
    with pytest.warns(RuntimeWarning, match="clipped"):
        # the 128 floor bites when n < 128 and the request exceeds the cap
        assert ops._resolve_block_n(64, 256) == 128
    with warnings.catch_warnings():
        warnings.simplefilter("error", category=RuntimeWarning)
        assert ops._resolve_block_n(100000, 256) == 256  # within cap: silent
    with pytest.raises(ValueError, match="block_n"):
        ops._resolve_block_n(512, 0)


def test_explicit_block_n_warns_through_public_wrapper():
    xa = jnp.ones((3, 600), jnp.float32)
    fsq = jnp.ones((2, 600), jnp.float32)
    fd = jnp.ones((2, 600), jnp.float32)
    with pytest.warns(RuntimeWarning, match="clipped"):
        ops.rolann_stats(xa, fsq, fd, block_n=4096)


def test_interpret_override_and_env(monkeypatch):
    """An explicit argument always wins; ``None`` interprets exactly on the
    CPU backend, so an accelerator never falls back to the interpreter."""
    assert ops._resolve_interpret(True) is True
    assert ops._resolve_interpret(False) is False
    assert ops._resolve_interpret(None) == (jax.default_backend() == "cpu")
    monkeypatch.setattr(ops, "_backend_is_cpu", lambda: False)
    assert ops._resolve_interpret(None) is False
    assert ops._resolve_interpret(True) is True
    monkeypatch.setattr(ops, "_backend_is_cpu", lambda: True)
    assert ops._resolve_interpret(None) is True


# ---------------------------------------------------------------------------
# Fused-chunk parity: fused == einsum chunk fold, modes x dtypes
# ---------------------------------------------------------------------------

def _chunk_problem(m_l, m_c1, n, seed, dtype):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(m_l, n)), dtype)
    w = jnp.asarray(rng.normal(size=(m_l, m_c1)) / np.sqrt(m_l), dtype)
    b = jnp.asarray(rng.normal(size=(m_c1,)), dtype)
    mask = jnp.asarray(rng.random(n) > 0.25, dtype)
    return h, w, b, mask


def _assert_stats_close(got, want, dtype):
    tol = TOLS[np.dtype(dtype).name]
    scale = max(1.0, float(jnp.max(jnp.abs(want.g))))
    np.testing.assert_allclose(np.asarray(got.g), np.asarray(want.g),
                               atol=tol["atol"] * scale, rtol=tol["rtol"])
    np.testing.assert_allclose(np.asarray(got.m), np.asarray(want.m),
                               atol=tol["atol"] * scale, rtol=tol["rtol"])


@pytest.mark.parametrize("act_name", ["logsig", "tanh"])
@pytest.mark.parametrize("n", [1, 130, 512, 700])
def test_fused_chunk_matches_einsum_chunk(act_name, n):
    act = activations.get(act_name, invertible_required=True)
    h, w, b, mask = _chunk_problem(7, 5, n, seed=n, dtype=jnp.float32)
    s0 = rolann.init_stats(5, 7, act, dtype=jnp.float32)
    want = elm_ae.accumulate_layer_stats(s0, w, b, h, act, weights=mask,
                                         backend="einsum")
    got = elm_ae.accumulate_layer_stats(s0, w, b, h, act, weights=mask,
                                        backend="fused")
    _assert_stats_close(got, want, jnp.float32)


def test_fused_chunk_parity_float64():
    if not jax.config.jax_enable_x64:
        pytest.skip("x64 disabled in this tier")
    act = activations.get("logsig", invertible_required=True)
    h, w, b, mask = _chunk_problem(7, 5, 300, seed=3, dtype=jnp.float64)
    s0 = rolann.init_stats(5, 7, act, dtype=jnp.float64)
    want = elm_ae.accumulate_layer_stats(s0, w, b, h, act, weights=mask,
                                         backend="einsum")
    got = elm_ae.accumulate_layer_stats(s0, w, b, h, act, weights=mask,
                                        backend="fused")
    _assert_stats_close(got, want, jnp.float64)


def test_fused_chunk_accumulates_over_ragged_chunks():
    """Folding ragged chunks (last one short, mask-padded) equals the
    one-shot statistics on the concatenated samples."""
    act = activations.get("logsig", invertible_required=True)
    h, w, b, _ = _chunk_problem(7, 5, 700, seed=11, dtype=jnp.float32)
    s_ref = rolann.init_stats(5, 7, act, dtype=jnp.float32)
    want = elm_ae.accumulate_layer_stats(s_ref, w, b, h, act,
                                         backend="einsum")
    stats = rolann.init_stats(5, 7, act, dtype=jnp.float32)
    for start in range(0, 700, 256):   # chunks of 256, 256, 188 (ragged)
        chunk = h[:, start:start + 256]
        stats = elm_ae.accumulate_layer_stats(stats, w, b, chunk, act,
                                              backend="fused")
    _assert_stats_close(stats, want, jnp.float32)


@pytest.mark.parametrize("backend", ["einsum", "fused"])
def test_fused_chunk_vmap_collapses_to_batched(backend, monkeypatch):
    """Vmapping fused_chunk_acc dispatches ONE tenant-batched call (the
    custom_vmap rule), and the batched result matches per-tenant folds."""
    calls = []
    orig = stats_backend.fused_chunk_acc_batched

    def spy(g, m, h, w, b, mask=None, *, act, backend=None):
        calls.append((h.shape, backend))
        return orig(g, m, h, w, b, mask, act=act, backend=backend)

    monkeypatch.setattr(stats_backend, "fused_chunk_acc_batched", spy)
    stats_backend._fused_chunk_fn.cache_clear()

    act = activations.get("logsig", invertible_required=True)
    k = 3
    hs, ws, bs, masks, singles = [], [], [], [], []
    for t in range(k):
        h, w, b, mask = _chunk_problem(7, 5, 200, seed=t, dtype=jnp.float32)
        s0 = rolann.init_stats(5, 7, act, dtype=jnp.float32)
        singles.append(elm_ae.accumulate_layer_stats(
            s0, w, b, h, act, weights=mask, backend="einsum"))
        hs.append(h); ws.append(w); bs.append(b); masks.append(mask)
    g0 = jnp.stack([rolann.init_stats(5, 7, act).g] * k)
    m0 = jnp.stack([rolann.init_stats(5, 7, act).m] * k)

    def per_tenant(g, m, h, w, b, mask):
        return stats_backend.fused_chunk_acc(g, m, h, w, b, mask,
                                             act="logsig", backend=backend)

    gk, mk = jax.vmap(per_tenant)(
        g0, m0, jnp.stack(hs), jnp.stack(ws), jnp.stack(bs), jnp.stack(masks)
    )
    stats_backend._fused_chunk_fn.cache_clear()
    assert calls and calls[0][0] == (k, 7, 200)
    assert all(b == backend for _, b in calls)
    for t in range(k):
        _assert_stats_close(rolann.RolannStats(g=gk[t], m=mk[t]), singles[t],
                            jnp.float32)


def test_fused_chunk_rejects_linear():
    act = activations.get("linear")
    with pytest.raises(ValueError, match="linear"):
        stats_backend.fused_chunk_acc(
            jnp.zeros((2, 3, 3)), jnp.zeros((2, 3)), jnp.zeros((2, 4)),
            jnp.zeros((2, 2)), jnp.zeros((2,)), act=act, backend="fused",
        )


# ---------------------------------------------------------------------------
# The one-launch guarantee (spy on the jaxpr, not on timings)
# ---------------------------------------------------------------------------

def _walk_eqns(jaxpr, skip_inside_pallas=True):
    """Yield every primitive name in a jaxpr, recursing into sub-jaxprs but
    NOT into pallas_call kernel bodies (their internal dot_generals run
    inside the single launch — that is the point)."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        if skip_inside_pallas and eqn.primitive.name == "pallas_call":
            continue
        for val in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(
                val, is_leaf=lambda x: hasattr(x, "eqns")
            ):
                if hasattr(sub, "eqns"):
                    yield from _walk_eqns(sub, skip_inside_pallas)
                elif hasattr(sub, "jaxpr"):
                    yield from _walk_eqns(sub.jaxpr, skip_inside_pallas)


def test_fused_layer_fold_is_one_launch_no_hbm_roundtrip():
    """The fused ``accumulate_layer_stats`` lowers to exactly one
    ``pallas_call`` with NO ``dot_general`` outside it: the stage-1 matmul
    and the (G, M) contractions all happen inside the launch, so the chunk
    activation never materializes between ops (= never round-trips HBM)."""
    act = activations.get("logsig", invertible_required=True)
    h, w, b, mask = _chunk_problem(7, 5, 256, seed=0, dtype=jnp.float32)
    s0 = rolann.init_stats(5, 7, act, dtype=jnp.float32)

    def fold(g, m, h, w, b, mask):
        out = elm_ae.accumulate_layer_stats(
            rolann.RolannStats(g=g, m=m), w, b, h, act, weights=mask,
            backend="fused")
        return out.g, out.m

    prims = list(_walk_eqns(
        jax.make_jaxpr(fold)(s0.g, s0.m, h, w, b, mask).jaxpr))
    assert prims.count("pallas_call") == 1, prims
    assert "dot_general" not in prims, prims
    # the einsum path, by contrast, has the matmul + contractions in XLA
    def fold_einsum(g, m, h, w, b, mask):
        out = elm_ae.accumulate_layer_stats(
            rolann.RolannStats(g=g, m=m), w, b, h, act, weights=mask,
            backend="einsum")
        return out.g, out.m

    prims_e = list(_walk_eqns(
        jax.make_jaxpr(fold_einsum)(s0.g, s0.m, h, w, b, mask).jaxpr))
    assert prims_e.count("pallas_call") == 0
    assert "dot_general" in prims_e
