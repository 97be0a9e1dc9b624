"""Coverage for core/sharded.py (fit_on_mesh / predict_on_mesh) on a forced
8-host-device mesh — previously the least-tested core module: only the
default gram path and the svd+gram_eigh path had any test at all.

Complements tests/test_distributed.py: predict_on_mesh parity, the
paper-faithful ``local_factorization="local_svd"`` message path, a deeper
decoder, multi-axis data meshes, and train-error sharding semantics.
"""
import pytest

from _mesh_harness import run_on_devices

_DATA = """
from repro.core import daef, sharded
from repro.launch.mesh import make_host_mesh
rng = np.random.default_rng(0)
z = rng.normal(size=(3, 1600))
x = np.tanh(rng.normal(size=(9, 3)) @ z) + 0.05 * rng.normal(size=(9, 1600))
x = ((x - x.mean(1, keepdims=True)) / x.std(1, keepdims=True)).astype(np.float32)
x = jnp.asarray(x)
"""


@pytest.mark.slow
def test_predict_on_mesh_matches_host_predict():
    out = run_on_devices(_DATA, """
    cfg = daef.DAEFConfig(layer_sizes=(9, 3, 5, 9), lam_hidden=0.5, lam_last=0.9)
    mesh = make_host_mesh()  # data=8, model=1
    model = daef.fit(cfg, x)
    recon_host = daef.predict(cfg, model, x)
    recon_mesh = sharded.predict_on_mesh(cfg, model, x, mesh)
    assert len(recon_mesh.sharding.device_set) == 8, recon_mesh.sharding
    np.testing.assert_allclose(np.asarray(recon_mesh), np.asarray(recon_host),
                               atol=1e-5)
    errs = daef.reconstruction_error(cfg, model, x)
    errs_mesh = jnp.mean((recon_mesh - x) ** 2, axis=0)
    np.testing.assert_allclose(np.asarray(errs_mesh), np.asarray(errs), atol=1e-5)
    print("PREDICT OK")
    """)
    assert "PREDICT OK" in out


@pytest.mark.slow
@pytest.mark.parametrize("method", ["gram", "svd"])
def test_fit_on_mesh_deeper_decoder(method):
    """Two decoder hidden layers — exercises the per-layer knowledge merge
    loop more than the minimal (m0, m1, m0) nets the other tests use."""
    out = run_on_devices(_DATA, f"""
    cfg = daef.DAEFConfig(layer_sizes=(9, 3, 6, 4, 9), lam_hidden=0.7,
                          lam_last=0.9, method={method!r})
    mesh = make_host_mesh()
    model_mesh = sharded.fit_on_mesh(cfg, x, mesh)
    model_host = daef.fit(cfg, x, n_partitions=8)
    assert len(model_mesh.weights) == 4 and len(model_mesh.biases) == 3
    ea = float(daef.reconstruction_error(cfg, model_mesh, x).mean())
    eb = float(daef.reconstruction_error(cfg, model_host, x).mean())
    assert abs(ea - eb) / eb < 0.05, (ea, eb)
    print("DEEP OK", ea, eb)
    """)
    assert "DEEP OK" in out


def test_fit_on_mesh_local_svd_factorization():
    """The paper's direct local-SVD message (local_factorization="local_svd")
    must agree with the default gram_eigh local factorization."""
    out = run_on_devices(_DATA, """
    cfg = daef.DAEFConfig(layer_sizes=(9, 3, 5, 9), lam_hidden=0.5,
                          lam_last=0.9, method="svd")
    mesh = make_host_mesh()
    m_eigh = sharded.fit_on_mesh(cfg, x, mesh, local_factorization="gram_eigh")
    m_svd = sharded.fit_on_mesh(cfg, x, mesh, local_factorization="local_svd")
    sv = np.abs(np.asarray(m_eigh.encoder_factors.s[:5])
                - np.asarray(m_svd.encoder_factors.s[:5]))
    assert sv.max() < 1e-2, sv
    ea = float(daef.reconstruction_error(cfg, m_eigh, x).mean())
    eb = float(daef.reconstruction_error(cfg, m_svd, x).mean())
    assert abs(ea - eb) / max(eb, 1e-9) < 0.05, (ea, eb)
    print("FACTORIZATION OK")
    """)
    assert "FACTORIZATION OK" in out


def test_fits_are_one_compiled_program():
    """The one-shot, chunked and data-sharded fits each compile as one
    program per shape, and a second fit of that shape compiles nothing.
    Dispatched op by op, a TPU compiles every small op on its own: the
    data-sharded fit then recompiled ~200 ops on every call."""
    out = run_on_devices(_DATA, """
    from repro.analysis.retrace import trace_guard
    cfg = daef.DAEFConfig(layer_sizes=(9, 3, 5, 9), lam_hidden=0.5, lam_last=0.9)
    mesh = make_host_mesh()
    fits = {
        "fit": lambda: daef.fit(cfg, x),
        "chunked": lambda: daef.fit_chunked(cfg, x, chunk_samples=256),
        "mesh": lambda: sharded._fit_on_mesh(cfg, x, mesh),
    }
    for name, fit in fits.items():
        for budget in (5, 0):
            with trace_guard(max_compiles=budget, what=name):
                jax.block_until_ready(fit())
    print("COMPILED OK")
    """)
    assert "COMPILED OK" in out


@pytest.mark.slow
def test_fit_on_mesh_multi_axis_data_mesh():
    """Collectives that loop over several data axes (('pod', 'data'))."""
    out = run_on_devices(_DATA, """
    cfg = daef.DAEFConfig(layer_sizes=(9, 3, 5, 9), lam_hidden=0.5, lam_last=0.9)
    mesh = jax.make_mesh((2, 4), ("pod", "data"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    model_mesh = sharded.fit_on_mesh(cfg, x, mesh, data_axes=("pod", "data"))
    model_host = daef.fit(cfg, x)
    diffs = [float(jnp.abs(a - b).max())
             for a, b in zip(model_mesh.weights, model_host.weights)]
    assert max(diffs) < 5e-2, diffs
    print("MULTIAXIS OK", max(diffs))
    """)
    assert "MULTIAXIS OK" in out


@pytest.mark.slow
def test_fit_on_mesh_train_errors_stay_sharded_in_order():
    """train_errors come back sharded over the data axes but in sample
    order, so host-side thresholding sees the same values as daef.fit."""
    out = run_on_devices(_DATA, """
    from repro.core import anomaly
    cfg = daef.DAEFConfig(layer_sizes=(9, 3, 5, 9), lam_hidden=0.5, lam_last=0.9)
    mesh = make_host_mesh()
    model_mesh = sharded.fit_on_mesh(cfg, x, mesh)
    assert len(model_mesh.train_errors.sharding.device_set) == 8
    errs_host = daef.fit(cfg, x).train_errors
    np.testing.assert_allclose(np.asarray(model_mesh.train_errors),
                               np.asarray(errs_host), atol=1e-3)
    mu_a = float(anomaly.threshold(model_mesh.train_errors, "q90"))
    mu_b = float(anomaly.threshold(errs_host, "q90"))
    assert abs(mu_a - mu_b) / mu_b < 0.02, (mu_a, mu_b)
    print("ERRORS OK")
    """)
    assert "ERRORS OK" in out
