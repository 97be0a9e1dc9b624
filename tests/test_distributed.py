"""Distributed-path tests: run in a subprocess with 8 virtual host devices
(XLA locks the device count at first init, so the main pytest process must
stay single-device for every other test)."""
import json
import os
import subprocess
import sys

import pytest

from _mesh_harness import ROOT, run_on_devices


def _run(body: str) -> str:
    return run_on_devices("from repro.launch.mesh import make_host_mesh", body)


def test_daef_fit_on_mesh_matches_host():
    out = _run("""
    from repro.core import daef, sharded
    mesh = make_host_mesh()  # data=8, model=1
    rng = np.random.default_rng(0)
    z = rng.normal(size=(3, 1600))
    x = np.tanh(rng.normal(size=(9, 3)) @ z) + 0.05 * rng.normal(size=(9, 1600))
    x = ((x - x.mean(1, keepdims=True)) / x.std(1, keepdims=True)).astype(np.float32)
    cfg = daef.DAEFConfig(layer_sizes=(9, 3, 5, 9), lam_hidden=0.5, lam_last=0.9)
    model_mesh = sharded.fit_on_mesh(cfg, jnp.asarray(x), mesh)
    model_host = daef.fit(cfg, jnp.asarray(x))
    diffs = [float(jnp.abs(a - b).max())
             for a, b in zip(model_mesh.weights, model_host.weights)]
    ea = float(daef.reconstruction_error(cfg, model_mesh, jnp.asarray(x)).mean())
    eb = float(daef.reconstruction_error(cfg, model_host, jnp.asarray(x)).mean())
    print("DIFFS", max(diffs), ea, eb)
    assert max(diffs) < 5e-2, diffs
    assert abs(ea - eb) / eb < 0.05, (ea, eb)
    """)
    assert "DIFFS" in out


@pytest.mark.slow
def test_daef_fit_on_mesh_svd_method():
    out = _run("""
    import dataclasses
    from repro.core import daef, sharded
    mesh = make_host_mesh()
    rng = np.random.default_rng(1)
    x = rng.normal(size=(8, 800)).astype(np.float32)
    cfg = daef.DAEFConfig(layer_sizes=(8, 3, 8), lam_hidden=0.5, lam_last=0.9,
                          method="svd")
    model_mesh = sharded.fit_on_mesh(cfg, jnp.asarray(x), mesh)
    model_host = daef.fit(cfg, jnp.asarray(x), n_partitions=8)
    # Singular values must match exactly; weights/predictions only up to the
    # encoder SVD sign ambiguity (isotropic data has no stable canonical
    # sign), so the fit QUALITY is compared.
    sv = np.abs(np.asarray(model_mesh.encoder_factors.s[:5])
                - np.asarray(model_host.encoder_factors.s[:5]))
    assert sv.max() < 1e-2, sv
    ea = float(daef.reconstruction_error(cfg, model_mesh, jnp.asarray(x)).mean())
    eb = float(daef.reconstruction_error(cfg, model_host, jnp.asarray(x)).mean())
    print("OK", ea, eb)
    assert abs(ea - eb) / eb < 0.05, (ea, eb)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_train_step_runs_and_matches_single_device():
    out = _run("""
    from repro import optim
    from repro.configs import registry
    from repro.launch import steps
    from repro.launch.shardings import batch_shardings, param_shardings
    from repro.models import get_bundle

    cfg = registry.get("qwen3-1.7b").reduced()
    bundle = get_bundle(cfg, chunked_attn=False)
    params = bundle.init(jax.random.PRNGKey(0))
    opt = optim.adam(1e-3)
    state = opt.init(params)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                          cfg.vocab_size)}
    step = steps.make_train_step(bundle, opt, microbatches=2)

    # single device
    p1, s1, l1 = jax.jit(step)(params, state, batch)

    mesh = make_host_mesh(model_parallel=2)  # data=4, model=2
    p_shard = param_shardings(params, mesh)
    b_shard = batch_shardings(batch, mesh)
    params_d = jax.device_put(params, p_shard)
    batch_d = jax.device_put(batch, b_shard)
    with jax.set_mesh(mesh):
        p2, s2, l2 = jax.jit(step)(params_d, opt.init(params_d), batch_d)
    print("LOSS", float(l1), float(l2))
    assert abs(float(l1) - float(l2)) < 1e-3
    d = max(float(jnp.abs(a - b).max()) for a, b in
            zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    print("PDIFF", d)
    assert d < 5e-2
    """)
    assert "PDIFF" in out


def test_attend_auto_on_mesh_both_strategies():
    out = _run("""
    from repro.models import attention as A
    mesh = make_host_mesh(model_parallel=4)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    # non-divisible heads -> sequence-parallel shard_map path
    q = jax.random.normal(ks[0], (4, 256, 6, 32))
    k = jax.random.normal(ks[1], (4, 256, 3, 32))
    v = jax.random.normal(ks[2], (4, 256, 3, 32))
    ref = A.attend_full(q, k, v)
    with jax.set_mesh(mesh):
        out = jax.jit(lambda *a: A.attend_auto(*a, q_block=64, kv_block=64))(q, k, v)
    err1 = float(jnp.abs(out - ref).max())
    # divisible heads -> hint path
    q2 = jax.random.normal(ks[3], (4, 256, 8, 32))
    k2 = jax.random.normal(ks[4], (4, 256, 4, 32))
    v2 = jax.random.normal(ks[5], (4, 256, 4, 32))
    ref2 = A.attend_full(q2, k2, v2)
    with jax.set_mesh(mesh):
        out2 = jax.jit(lambda *a: A.attend_auto(*a, q_block=64, kv_block=64))(q2, k2, v2)
    err2 = float(jnp.abs(out2 - ref2).max())
    print("ERRS", err1, err2)
    assert err1 < 1e-5 and err2 < 1e-5
    """)
    assert "ERRS" in out


@pytest.mark.slow
def test_dryrun_record_schema():
    """One real dry-run on the production mesh (reduced-cost pair)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "qwen2-1.5b", "--shape", "decode_32k"],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    record = json.loads(proc.stdout.strip().splitlines()[-1])
    assert record["status"] == "ok"
    rf = record["roofline"]
    assert rf["chips"] == 256
    assert rf["dominant"] in ("compute", "memory", "collective")
    assert rf["peak_memory_per_device_gib"] < 16.0
