"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle, with
hypothesis sweeps over shapes/dtypes (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.testing.proptest import given, settings, st

from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.rglru_scan import rglru_scan, rglru_scan_ref
from repro.kernels.rolann_stats import (
    rolann_stats,
    rolann_stats_acc,
    rolann_stats_acc_batched,
    rolann_stats_batched,
    rolann_stats_ref,
)
from repro.kernels.rolann_stats.ops import next_pow2


# ---------------------------------------------------------------------------
# rolann_stats
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(
    m=st.integers(min_value=2, max_value=40),
    n=st.integers(min_value=8, max_value=600),
    o=st.integers(min_value=1, max_value=6),
    seed=st.integers(min_value=0, max_value=99),
)
def test_rolann_stats_shape_sweep(m, n, o, seed):
    rng = np.random.default_rng(seed)
    xa = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    fsq = jnp.asarray(rng.uniform(0.05, 1.0, size=(o, n)), jnp.float32)
    fd = jnp.asarray(rng.normal(size=(o, n)), jnp.float32)
    g, mv = rolann_stats(xa, fsq, fd, block_n=128)
    gr, mr = rolann_stats_ref(xa, fsq, fd)
    scale = max(1.0, float(jnp.abs(gr).max()))
    np.testing.assert_allclose(g, gr, atol=2e-4 * scale)
    np.testing.assert_allclose(mv, mr, atol=2e-4 * scale)


@settings(max_examples=6, deadline=None)
@given(
    k=st.integers(min_value=1, max_value=4),
    m=st.integers(min_value=2, max_value=20),
    n=st.integers(min_value=8, max_value=300),
    o=st.integers(min_value=1, max_value=5),
    seed=st.integers(min_value=0, max_value=99),
)
def test_rolann_stats_batched_vs_oracle(k, m, n, o, seed):
    """The tenant-batched kernel == the per-tenant oracle, per tenant."""
    rng = np.random.default_rng(seed)
    xa = jnp.asarray(rng.normal(size=(k, m, n)), jnp.float32)
    fsq = jnp.asarray(rng.uniform(0.05, 1.0, size=(k, o, n)), jnp.float32)
    fd = jnp.asarray(rng.normal(size=(k, o, n)), jnp.float32)
    g, mv = rolann_stats_batched(xa, fsq, fd, block_n=128)
    gr, mr = jax.vmap(rolann_stats_ref)(xa, fsq, fd)
    scale = max(1.0, float(jnp.abs(gr).max()))
    np.testing.assert_allclose(g, gr, atol=2e-4 * scale)
    np.testing.assert_allclose(mv, mr, atol=2e-4 * scale)


def test_rolann_stats_vmap_matches_batched_entry():
    """jax.vmap over the unbatched wrapper == the explicit batched kernel."""
    rng = np.random.default_rng(3)
    xa = jnp.asarray(rng.normal(size=(3, 6, 200)), jnp.float32)
    fsq = jnp.asarray(rng.uniform(0.1, 1, (3, 2, 200)), jnp.float32)
    fd = jnp.asarray(rng.normal(size=(3, 2, 200)), jnp.float32)
    g_v, m_v = jax.vmap(rolann_stats)(xa, fsq, fd)
    g_b, m_b = rolann_stats_batched(xa, fsq, fd)
    np.testing.assert_allclose(np.asarray(g_v), np.asarray(g_b), atol=1e-5)
    np.testing.assert_allclose(np.asarray(m_v), np.asarray(m_b), atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rolann_stats_dtypes(dtype):
    """Results come back in the promoted *input* dtype (no silent f32
    widening of bf16, no silent f32 downcast of wider inputs), accumulated
    in f32 — so values track the f32 oracle within dtype rounding."""
    rng = np.random.default_rng(0)
    xa = jnp.asarray(rng.normal(size=(16, 512)), dtype)
    fsq = jnp.asarray(rng.uniform(0.1, 1, (4, 512)), dtype)
    fd = jnp.asarray(rng.normal(size=(4, 512)), dtype)
    g, mv = rolann_stats(xa, fsq, fd)
    assert g.dtype == dtype and mv.dtype == dtype
    gr, mr = rolann_stats_ref(
        xa.astype(jnp.float32), fsq.astype(jnp.float32), fd.astype(jnp.float32)
    )
    tol = 1e-3 if dtype == jnp.float32 else 0.3
    np.testing.assert_allclose(
        g.astype(jnp.float32), gr, atol=tol * float(jnp.abs(gr).max())
    )
    np.testing.assert_allclose(
        mv.astype(jnp.float32), mr, atol=tol * float(jnp.abs(mr).max())
    )


def test_rolann_stats_float64_roundtrip():
    """Under jax_enable_x64, f64 inputs come back f64 (accumulation is f32,
    so values carry f32-level error — dtype parity is the contract)."""
    rng = np.random.default_rng(1)
    with jax.enable_x64(True):
        xa = jnp.asarray(rng.normal(size=(8, 256)), jnp.float64)
        fsq = jnp.asarray(rng.uniform(0.1, 1, (3, 256)), jnp.float64)
        fd = jnp.asarray(rng.normal(size=(3, 256)), jnp.float64)
        g, mv = rolann_stats(xa, fsq, fd)
        assert g.dtype == jnp.float64 and mv.dtype == jnp.float64
        gr, mr = rolann_stats_ref(xa, fsq, fd)
        scale = float(jnp.abs(gr).max())
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-4 * scale)
        np.testing.assert_allclose(np.asarray(mv), np.asarray(mr), atol=1e-4 * scale)


def test_rolann_stats_degenerate_shapes():
    """Empty/unit sample axes no longer break the block heuristic."""
    g, mv = rolann_stats(jnp.zeros((4, 0)), jnp.zeros((2, 0)), jnp.zeros((2, 0)))
    assert g.shape == (2, 4, 4) and mv.shape == (2, 4)
    np.testing.assert_array_equal(np.asarray(g), 0.0)
    np.testing.assert_array_equal(np.asarray(mv), 0.0)

    xa = jnp.asarray([[2.0], [3.0]])
    fsq = jnp.asarray([[0.5]])
    fd = jnp.asarray([[4.0]])
    g, mv = rolann_stats(xa, fsq, fd)  # n == 1: pads one 128-lane block
    gr, mr = rolann_stats_ref(xa, fsq, fd)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(mv), np.asarray(mr), atol=1e-6)

    g, mv = rolann_stats_batched(
        jnp.zeros((0, 3, 16)), jnp.zeros((0, 2, 16)), jnp.zeros((0, 2, 16))
    )
    assert g.shape == (0, 2, 3, 3) and mv.shape == (0, 2, 3)


@settings(max_examples=6, deadline=None)
@given(
    m=st.integers(min_value=2, max_value=24),
    n=st.integers(min_value=8, max_value=300),
    o=st.integers(min_value=1, max_value=5),
    seed=st.integers(min_value=0, max_value=99),
)
def test_rolann_stats_acc_shape_sweep(m, n, o, seed):
    """The accumulating kernel == running stats + the einsum oracle of the
    chunk (the streamed fit's per-chunk fold)."""
    rng = np.random.default_rng(seed)
    g0 = jnp.asarray(rng.normal(size=(o, m, m)), jnp.float32)
    m0 = jnp.asarray(rng.normal(size=(o, m)), jnp.float32)
    xa = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    fsq = jnp.asarray(rng.uniform(0.05, 1.0, size=(o, n)), jnp.float32)
    fd = jnp.asarray(rng.normal(size=(o, n)), jnp.float32)
    g, mv = rolann_stats_acc(g0, m0, xa, fsq, fd, block_n=128)
    gr, mr = rolann_stats_ref(xa, fsq, fd)
    scale = max(1.0, float(jnp.abs(gr).max()))
    np.testing.assert_allclose(g, g0 + gr, atol=2e-4 * scale)
    np.testing.assert_allclose(mv, m0 + mr, atol=2e-4 * scale)


def test_rolann_stats_acc_batched_vs_oracle():
    """One batched accumulating launch == the per-tenant oracle fold."""
    rng = np.random.default_rng(5)
    k, m, o, n = 3, 6, 2, 200
    g0 = jnp.asarray(rng.normal(size=(k, o, m, m)), jnp.float32)
    m0 = jnp.asarray(rng.normal(size=(k, o, m)), jnp.float32)
    xa = jnp.asarray(rng.normal(size=(k, m, n)), jnp.float32)
    fsq = jnp.asarray(rng.uniform(0.1, 1, (k, o, n)), jnp.float32)
    fd = jnp.asarray(rng.normal(size=(k, o, n)), jnp.float32)
    g, mv = rolann_stats_acc_batched(g0, m0, xa, fsq, fd, block_n=128)
    gr, mr = jax.vmap(rolann_stats_ref)(xa, fsq, fd)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g0 + gr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(mv), np.asarray(m0 + mr), atol=1e-4)


def test_gram_stats_acc_vmap_dispatches_batched(monkeypatch):
    """vmapping the accumulating fold (the fleet's tenant axis) must lower
    to ONE tenant-batched dispatch via the custom_vmap rule — for the fused
    producer a single `rolann_stats_acc_batched` launch — and agree with the
    per-tenant loop under both producers."""
    from repro.core import stats_backend
    from repro.testing.producer import stats_producer

    calls = []
    orig = stats_backend.gram_stats_acc_batched

    def spy(g, m, xa, fsq, fd):
        calls.append((tuple(xa.shape), stats_backend.producer()))
        return orig(g, m, xa, fsq, fd)

    monkeypatch.setattr(stats_backend, "gram_stats_acc_batched", spy)
    rng = np.random.default_rng(6)
    k, m, o, n = 4, 5, 3, 64
    g0 = jnp.asarray(rng.normal(size=(k, o, m, m)), jnp.float32)
    m0 = jnp.asarray(rng.normal(size=(k, o, m)), jnp.float32)
    xa = jnp.asarray(rng.normal(size=(k, m, n)), jnp.float32)
    fsq = jnp.asarray(rng.uniform(0.1, 1, (k, o, n)), jnp.float32)
    fd = jnp.asarray(rng.normal(size=(k, o, n)), jnp.float32)
    for backend in stats_backend.PRODUCERS:
        with stats_producer(backend):
            calls.clear()
            g, mv = jax.vmap(stats_backend.gram_stats_acc)(g0, m0, xa, fsq, fd)
            assert calls, f"{backend}: batched accumulator was not dispatched"
            assert calls[0] == ((k, m, n), backend)
            for i in range(k):
                gi, mi = stats_backend.gram_stats_acc(
                    g0[i], m0[i], xa[i], fsq[i], fd[i]
                )
                np.testing.assert_allclose(np.asarray(g[i]), np.asarray(gi),
                                           atol=1e-5, rtol=1e-5)
                np.testing.assert_allclose(np.asarray(mv[i]), np.asarray(mi),
                                           atol=1e-5, rtol=1e-5)


def test_rolann_stats_acc_scan_carry_and_dtype():
    """The fold composes over a lax.scan carry (the chunked fit's shape) and
    returns the accumulator dtype; degenerate empty chunks are identity."""
    rng = np.random.default_rng(7)
    o, m, n_chunk, steps = 2, 5, 32, 4
    xa = jnp.asarray(rng.normal(size=(steps, m, n_chunk)), jnp.float32)
    fsq = jnp.asarray(rng.uniform(0.1, 1, (steps, o, n_chunk)), jnp.float32)
    fd = jnp.asarray(rng.normal(size=(steps, o, n_chunk)), jnp.float32)

    def step(carry, inp):
        g, mv = carry
        x, fs, f = inp
        return rolann_stats_acc(g, mv, x, fs, f), None

    init = (jnp.zeros((o, m, m)), jnp.zeros((o, m)))
    (g, mv), _ = jax.lax.scan(step, init, (xa, fsq, fd))
    gr, mr = rolann_stats_ref(
        jnp.concatenate(list(xa), axis=-1),
        jnp.concatenate(list(fsq), axis=-1),
        jnp.concatenate(list(fd), axis=-1),
    )
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(mv), np.asarray(mr), atol=1e-4)
    assert g.dtype == jnp.float32 and mv.dtype == jnp.float32

    ge, me = rolann_stats_acc(
        g, mv, jnp.zeros((m, 0)), jnp.zeros((o, 0)), jnp.zeros((o, 0))
    )
    np.testing.assert_array_equal(np.asarray(ge), np.asarray(g))
    np.testing.assert_array_equal(np.asarray(me), np.asarray(mv))


def test_next_pow2():
    assert [next_pow2(x) for x in (0, 1, 2, 3, 4, 5, 127, 128, 129, 511, 512)] == [
        1, 1, 2, 4, 4, 8, 128, 128, 256, 512, 512,
    ]


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

def _fa_ref(q, k, v, **kw):
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    kr, vr = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    tr = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    out = flash_attention_ref(tr(q), tr(kr), tr(vr), **kw)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(
    b=st.integers(min_value=1, max_value=3),
    s_pow=st.integers(min_value=5, max_value=8),
    hkv=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2, 3]),
    d=st.sampled_from([16, 32, 64]),
    seed=st.integers(min_value=0, max_value=99),
)
def test_flash_attention_shape_sweep(b, s_pow, hkv, g, d, seed):
    s = 2**s_pow
    h = hkv * g
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    np.testing.assert_allclose(out, _fa_ref(q, k, v), atol=2e-5)


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 32))
    k = jax.random.normal(ks[1], (2, 128, 2, 32))
    v = jax.random.normal(ks[2], (2, 128, 2, 32))
    out = flash_attention(q, k, v, window=window, block_q=32, block_k=32)
    np.testing.assert_allclose(out, _fa_ref(q, k, v, window=window), atol=2e-5)


def test_flash_attention_matches_model_attention():
    """Kernel and the model-layer chunked path agree (same oracle)."""
    from repro.models import attention as A

    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 16))
    k = jax.random.normal(ks[1], (1, 64, 2, 16))
    v = jax.random.normal(ks[2], (1, 64, 2, 16))
    kern = flash_attention(q, k, v, block_q=16, block_k=16)
    model = A.attend_chunked(q, k, v, q_block=16, kv_block=16)
    np.testing.assert_allclose(kern, model, atol=2e-5)


# ---------------------------------------------------------------------------
# rglru_scan
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(
    b=st.integers(min_value=1, max_value=3),
    s=st.sampled_from([16, 48, 128]),
    w=st.sampled_from([32, 96, 256]),
    seed=st.integers(min_value=0, max_value=99),
)
def test_rglru_scan_shape_sweep(b, s, w, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (b, s, w))
    r = jax.nn.sigmoid(jax.random.normal(ks[1], (b, s, w)))
    i = jax.nn.sigmoid(jax.random.normal(ks[2], (b, s, w)))
    lam = jax.random.normal(ks[3], (w,)) + 4
    y, hl = rglru_scan(x, r, i, lam, block_s=16, block_w=32)
    yr, hr = rglru_scan_ref(x, r, i, lam)
    np.testing.assert_allclose(y, yr, atol=1e-5)
    np.testing.assert_allclose(hl, hr, atol=1e-5)


def test_rglru_scan_matches_model_rg_lru():
    from repro.models import rglru

    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    b, s, w = 2, 64, 128
    x = jax.random.normal(ks[0], (b, s, w))
    r = jax.nn.sigmoid(jax.random.normal(ks[1], (b, s, w)))
    i = jax.nn.sigmoid(jax.random.normal(ks[2], (b, s, w)))
    lam = jax.random.normal(ks[3], (w,)) + 4
    y_kern, h_kern = rglru_scan(x, r, i, lam, block_s=16, block_w=64)
    y_model, h_model = rglru.rg_lru(x, r, i, lam)
    np.testing.assert_allclose(y_kern, y_model, atol=1e-4)
    np.testing.assert_allclose(h_kern, h_model, atol=1e-4)


# ---------------------------------------------------------------------------
# ssd_chunk
# ---------------------------------------------------------------------------

from repro.kernels.ssd_chunk import ssd_chunk, ssd_chunk_ref  # noqa: E402


@settings(max_examples=8, deadline=None)
@given(
    bh=st.integers(min_value=1, max_value=4),
    s=st.sampled_from([16, 64, 128]),
    p=st.sampled_from([4, 8, 16]),
    n=st.sampled_from([8, 16]),
    seed=st.integers(min_value=0, max_value=99),
)
def test_ssd_chunk_shape_sweep(bh, s, p, n, seed):
    rng = np.random.default_rng(seed)
    xdt = jnp.asarray(rng.normal(size=(bh, s, p)), jnp.float32)
    la = jnp.asarray(-np.abs(rng.normal(size=(bh, s))) * 0.3, jnp.float32)
    b = jnp.asarray(rng.normal(size=(bh, s, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(bh, s, n)), jnp.float32)
    y, h = ssd_chunk(xdt, la, b, c, chunk=16)
    yr, hr = ssd_chunk_ref(xdt, la, b, c)
    np.testing.assert_allclose(y, yr, atol=2e-4)
    np.testing.assert_allclose(h, hr, atol=2e-4)


def test_ssd_chunk_matches_model_ssd():
    """Kernel agrees with the model-layer chunked SSD (mamba2.ssd_chunked)."""
    from repro.models import mamba2

    rng = np.random.default_rng(1)
    B, S, H, P, N = 2, 64, 3, 8, 16
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(B, S, H))) + 0.1, jnp.float32)
    a = jnp.asarray(np.abs(rng.normal(size=(H,))) + 0.1, jnp.float32)
    b = jnp.asarray(rng.normal(size=(B, S, H, N)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(B, S, H, N)), jnp.float32)

    y_model, h_model = mamba2.ssd_chunked(x, dt, a, b, c, chunk=16)

    # Kernel layout: fold (B, H) -> BH; la = -a * dt; xdt = x * dt.
    tr = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, -1)
    xdt = tr(x * dt[..., None])
    la = (-a[None, None, :] * dt).transpose(0, 2, 1).reshape(B * H, S)
    y_k, h_k = ssd_chunk(xdt, la, tr(b), tr(c), chunk=16)
    y_k = y_k.reshape(B, H, S, P).transpose(0, 2, 1, 3)
    h_k = h_k.reshape(B, H, P, N)
    np.testing.assert_allclose(y_k, y_model, atol=2e-4)
    np.testing.assert_allclose(h_k, h_model, atol=2e-4)


# ---------------------------------------------------------------------------
# flash_attention custom VJP (backward is also Pallas)
# ---------------------------------------------------------------------------

def test_flash_attention_vjp_matches_autodiff():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    B, S, H, Hkv, D = 2, 64, 4, 2, 16
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))

    def ref_attn(q, k, v):
        rep = q.shape[2] // k.shape[2]
        kr, vr = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
        tr = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, S, D)
        out = flash_attention_ref(tr(q), tr(kr), tr(vr))
        return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)

    gk = jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, block_q=16, block_k=16) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda q, k, v: (ref_attn(q, k, v) ** 2).sum(), argnums=(0, 1, 2)
    )(q, k, v)
    for a, b in zip(gk, gr, strict=True):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_flash_attention_vjp_windowed():
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 16))
    k = jax.random.normal(ks[1], (1, 64, 2, 16))
    v = jax.random.normal(ks[2], (1, 64, 2, 16))

    def ref_attn(q, k, v):
        tr = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, 64, 16)
        out = flash_attention_ref(tr(q), tr(k), tr(v), window=24)
        return out.reshape(1, 2, 64, 16).transpose(0, 2, 1, 3)

    gk = jax.grad(
        lambda q: (flash_attention(q, k, v, window=24, block_q=16, block_k=16) ** 2).sum()
    )(q)
    gr = jax.grad(lambda q: (ref_attn(q, k, v) ** 2).sum())(q)
    np.testing.assert_allclose(gk, gr, atol=2e-5)
