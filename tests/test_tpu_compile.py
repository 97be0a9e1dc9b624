"""Compile the main path for a TPU v5e that is described, not attached.

The interpret-mode kernel tests run the Pallas bodies on the CPU and cannot
see what only the TPU compiler (Mosaic) refuses: block shapes off the
(8, 128) tiling, in-kernel relayouts, VMEM overuse.  These tests lower and
compile, for one chip of a described ``v5e:2x2`` topology,

* the ``chol_solve`` kernel on the cardio fleet's decoder stacks;
* all six ``rolann_stats`` kernel variants at every nonlinear layer width of
  the creditcard and cardio architectures (paper Table 5), asserting that
  each compiled program holds a Mosaic kernel (``tpu_custom_call``);
* the einsum fleet fit step (``fleet._fleet_fit``) at the same widths, with
  its device memory within one v5e chip's 16 GB, its encoder eigh on the
  lane-batched Jacobi route (``core/eigh.py``: the ``jacobi_eigh`` Mosaic
  kernel) and no XLA ``EighTpu``, and each decoder stack of at least
  ``chol.B0`` systems on the lane-batched Cholesky route (``core/chol.py``:
  one ``chol_solve`` Mosaic kernel a stack); at cardio's widths no XLA
  ``Cholesky`` is left;
* creditcard's one-model fit program, whose one eigh keeps ``EighTpu`` and
  whose solves keep XLA's ``Cholesky``;
* the federated tree program of 1,024 cardio sites over the four chips of
  the 2x2 mesh, whose encoder merges by Gram sums: one ``EighTpu`` a chip
  at the root and none of the concat-SVD's QDWH loops.

Nothing runs.  The topology is described inside a fixture: only the worker
that is given this file loads the TPU compiler, and a host that cannot
describe one skips here, never at import.  The persistent compilation cache
is off around these compiles (a compile for a described device is written
to it but cannot be read back without the device).
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import chol, daef, eigh, fleet
from repro.kernels.rolann_stats import ops

# (layer sizes, training samples per model, models batched into one call):
# creditcard's fold-0 split trained as one model, or as 8 federated
# partitions in one vmapped fit; cardio as the K=256 serving fleet.
ARCHS = {
    "creditcard": ((29, 15, 18, 21, 24, 27, 29), 255_884, 8),
    "cardio": ((21, 4, 8, 12, 16, 21), 1_490, 256),
}
CHUNK = 4096           # ExecutionPlan(chunk_samples=4096)
BLOCK_N = 512
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _layers(arch: str):
    """(m_l, ma) of every ELM-AE layer: input rows, augmented stage-1 rows."""
    sizes = ARCHS[arch][0]
    return [(sizes[li - 1], sizes[li] + 1) for li in range(2, len(sizes) - 1)]


def _kernel_args(variant: str, arch: str, m_l: int, ma: int, spec):
    """(jitted op, abstract args, static kwargs) for one kernel launch."""
    _, n, k = ARCHS[arch]
    o = m_l
    if variant == "stats":
        return ops._rolann_stats, (spec(ma, n), spec(o, n), spec(o, n)), {}
    if variant == "stats_batched":
        return ops._rolann_stats_batched, (
            spec(k, ma, n), spec(k, o, n), spec(k, o, n)), {}
    if variant == "stats_acc":
        return ops._rolann_stats_acc, (
            spec(o, ma, ma), spec(o, ma), spec(ma, CHUNK), spec(o, CHUNK),
            spec(o, CHUNK)), {}
    if variant == "stats_acc_batched":
        return ops._rolann_stats_acc_batched, (
            spec(k, o, ma, ma), spec(k, o, ma), spec(k, ma, CHUNK),
            spec(k, o, CHUNK), spec(k, o, CHUNK)), {}
    if variant == "fused_chunk":
        return ops._rolann_fused_chunk, (
            spec(o, ma, ma), spec(o, ma), spec(m_l, CHUNK),
            spec(m_l, ma - 1), spec(ma - 1), spec(CHUNK)), {"act_name": "logsig"}
    assert variant == "fused_chunk_batched", variant
    return ops._rolann_fused_chunk_batched, (
        spec(k, o, ma, ma), spec(k, o, ma), spec(k, m_l, CHUNK),
        spec(k, m_l, ma - 1), spec(k, ma - 1), spec(k, CHUNK)), {
            "act_name": "logsig"}


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("variant", [
    "stats", "stats_batched", "stats_acc", "stats_acc_batched",
    "fused_chunk", "fused_chunk_batched",
])
def test_rolann_stats_compiles_for_v5e(one_chip, variant, arch):
    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    for m_l, ma in _layers(arch):
        fn, args, kw = _kernel_args(variant, arch, m_l, ma, spec)
        compiled = fn.lower(*args, block_n=BLOCK_N, interpret=False,
                            **kw).compile()
        assert "tpu_custom_call" in compiled.as_text(), (variant, m_l, ma)


@pytest.mark.parametrize("batch, n, r", [
    (1024 * 4, 9, 1), (1024 * 8, 13, 1), (1024 * 12, 17, 1), (1024, 17, 21),
    (chol.B0, chol.N_MAX, 1),
])
def test_chol_solve_compiles_for_v5e(one_chip, batch, n, r):
    """The Cholesky kernel on the cardio fleet's decoder stacks (1,024
    tenants) and on the widest stack the route takes."""
    from repro.kernels.chol_solve import chol_solve

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(lambda a, b: chol_solve(a, b, interpret=False)).lower(
        spec(batch, n, n), spec(batch, n, r)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1


def _solve_kernels(sizes, k: int) -> int:
    """The decoder stacks of a K-tenant fleet fit that ``chol`` sends to its
    kernel: K x o systems of n = the layer's width + 1 for each ELM-AE
    layer (o = its input width), K shared systems for the last layer."""
    stacks = [(k * sizes[li - 1], sizes[li] + 1) for li in range(2, len(sizes) - 1)]
    stacks.append((k, sizes[-2] + 1))
    return sum(b >= chol.B0 and n <= chol.N_MAX for b, n in stacks)


def _has_chol_scope(text: str) -> bool:
    return re.search(r'op_name="[^"]*\bchol_solve/', text) is not None


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_einsum_fleet_fit_compiles_for_v5e(one_chip, arch, monkeypatch):
    # The host is a CPU, where the Jacobi and Cholesky kernels would
    # interpret: compile them with Mosaic, as on the chip.
    monkeypatch.setattr(eigh, "_interpret", lambda: False)
    sizes, n, k = ARCHS[arch]
    if arch == "creditcard":
        n //= k            # the 8 federated partitions, fit in one dispatch
    cfg = daef.DAEFConfig(layer_sizes=sizes, lam_hidden=0.8, lam_last=0.9)

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = fleet._fleet_fit.lower(
        cfg, spec((k, sizes[0], n)), spec((k,), jnp.int32), spec((k,)),
        spec((k,)),
    ).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
    text = compiled.as_text()
    jacobi = k >= eigh.B0 and sizes[0] <= eigh.N_MAX
    assert jacobi or arch != "cardio"
    assert ("jacobi_eigh" in text, "EighTpu" in text) == (jacobi, not jacobi)
    solves = _solve_kernels(sizes, k)
    assert solves > 0 and _has_chol_scope(text)
    assert text.count('custom_call_target="tpu_custom_call"') == int(jacobi) + solves
    assert arch != "cardio" or 'custom_call_target="Cholesky"' not in text


def test_one_model_fit_keeps_eigh_tpu_for_v5e(one_chip):
    sizes, n, _ = ARCHS["creditcard"]
    cfg = daef.DAEFConfig(layer_sizes=sizes, lam_hidden=0.8, lam_last=0.9)
    x = jax.ShapeDtypeStruct((sizes[0], n), jnp.float32, sharding=one_chip)
    text = daef.lower_fit(cfg, x).compile().as_text()
    assert "EighTpu" in text and "jacobi_eigh" not in text
    assert 'custom_call_target="Cholesky"' in text and not _has_chol_scope(text)


def test_gram_tree_merge_factors_once_a_chip_for_v5e(topo, one_chip):  # noqa: ARG001
    """cardio-fed-1024's reduce (``method="gram"``, 256 sites a chip: 8
    local and 2 ``ppermute`` levels): its encoders are summed as Grams, so
    the program holds one eigh, of the root's Gram, and no SVD."""
    from repro.engine import DAEFEngine, ExecutionPlan

    sizes, n, k = ARCHS["cardio"][0], 1_489, 1_024
    cfg = daef.DAEFConfig(layer_sizes=sizes, lam_hidden=0.9, lam_last=0.9)
    mesh = Mesh(np.array(topo.devices), ("tenants",))

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P("tenants")))

    args = (spec((k,), jnp.int32), spec((k,)), spec((k,)))
    model = jax.eval_shape(functools.partial(fleet._fleet_fit, cfg),
                           spec((k, sizes[0], n)), *args)
    fl = fleet.DAEFFleet(jax.tree.map(lambda a: spec(a.shape, a.dtype), model),
                         *args)
    engine = DAEFEngine(cfg, ExecutionPlan(mode="mesh", tenants=k, mesh_devices=4,
                                           merge="tree"), mesh=mesh)
    text = engine.lower_reduce(fl, k).compile().as_text()
    assert "collective-permute" in text
    assert text.count('custom_call_target="EighTpu"') == 1
    assert not re.search(r"\swhile\(", text)
    assert "CompactWyHelper" not in text and "QrDecompositionBlock" not in text
