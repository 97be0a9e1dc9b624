"""Dry-run the paper's own technique at pod scale: DAEF federated fit.

Lowers the engine's data-sharded mesh plan (`repro.engine`, backed by
``core.sharded``) — every data shard of the production mesh acting as one
federated node — for an LLM-feature-sized
problem (d = 2048 features, n = 4M samples, the llm_feature_anomaly head),
in both representations:

  * ``--method svd``  — paper-faithful: all-gather of local U·S factors +
    merge SVD at every node (the broker broadcast);
  * ``--method gram`` — beyond-paper fast path: one psum of (G, M).

The collective-bytes difference between the two IS the paper-vs-optimized
§Perf comparison (EXPERIMENTS.md).
"""
import os

# Lowering only: 512 virtual CPU devices stand in for the pod, so pin the
# CPU platform too — on a host with a TPU, JAX would otherwise take the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

# ruff: noqa: E402
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.core import daef
from repro.engine import DAEFEngine, ExecutionPlan
from repro.launch import roofline as roofline_mod
from repro.launch.mesh import (
    PRODUCTION_DEVICE_KIND,
    data_axes,
    make_production_mesh,
)


def build(method: str, *, d: int, n: int, multi_pod: bool, latent: int,
          local_fact: str = "gram_eigh"):
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = daef.DAEFConfig(
        layer_sizes=(d, latent, d // 4, d),
        lam_hidden=0.1,
        lam_last=0.5,
        method=method,
    )
    x_spec = jax.ShapeDtypeStruct((d, n), jnp.float32)
    axes = data_axes(mesh)
    engine = DAEFEngine(
        cfg,
        ExecutionPlan(mode="mesh", mesh_axes=axes,
                      local_factorization=local_fact),
        mesh=mesh,
    )

    def fit(x):
        model = engine.fit(x)
        # Return weights + per-shard train errors (the deployable artifact).
        return model.weights, model.biases, model.train_errors

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    x_sharding = NamedSharding(mesh, P(None, axes))
    with jax.set_mesh(mesh):
        lowered = jax.jit(fit, in_shardings=(x_sharding,)).lower(x_spec)
    return lowered, mesh, cfg


def run_one(method: str, *, d: int = 2048, n: int = 1 << 22,
            multi_pod: bool = False, latent: int = 256,
            local_fact: str = "gram_eigh") -> dict:
    tag = method if method == "gram" else f"{method}-{local_fact}"
    record = {
        "arch": f"daef-head-{d}",
        "shape": f"fit_{n >> 20}m_{tag}",
        "mesh": "pod=2,data=16,model=16" if multi_pod else "data=16,model=16",
    }
    t0 = time.time()
    try:
        lowered, mesh, cfg = build(
            method, d=d, n=n, multi_pod=multi_pod, latent=latent,
            local_fact=local_fact,
        )
        compiled = lowered.compile()
        record["status"] = "ok"
        record["compile_s"] = round(time.time() - t0, 1)
        mem = compiled.memory_analysis()
        record["memory"] = {
            k: int(getattr(mem, k, 0))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes")
        }
        rf = roofline_mod.analyze(
            compiled, mesh, device_kind=PRODUCTION_DEVICE_KIND
        )
        record["roofline"] = rf.as_dict()
        # "Useful" flops for DAEF: the Gram/SVD accumulations, ~ sum over
        # layers of 2 * m_in^2 * n (+ per-output for hidden layers).
        sizes = cfg.layer_sizes
        useful = 2.0 * sizes[0] ** 2 * n                       # encoder gram
        h_dims = [sizes[1]] + list(sizes[2:-1])
        for m_in, m_out in zip(h_dims, list(sizes[2:-1]) + [sizes[-1]], strict=True):
            # stage-1 projection + per-output gram (hidden) or shared (last)
            per_out = m_out if m_out != sizes[-1] else 1
            useful += 2.0 * m_in * m_out * n
            useful += 2.0 * (m_in + 1) ** 2 * n * per_out
        record["model_flops"] = useful
        total = rf.flops_per_device * rf.chips
        record["useful_flops_ratio"] = useful / total if total else None
    except Exception as e:  # noqa: BLE001
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-2000:]
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--method", default="gram", choices=["gram", "svd"])
    ap.add_argument("--local-fact", default="gram_eigh",
                    choices=["gram_eigh", "direct_svd"])
    ap.add_argument("--d", type=int, default=2048)
    ap.add_argument("--n", type=int, default=1 << 22)
    ap.add_argument("--latent", type=int, default=256)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    record = run_one(
        args.method, d=args.d, n=args.n, multi_pod=args.multi_pod,
        latent=args.latent, local_fact=args.local_fact,
    )
    line = json.dumps(record)
    print(line)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")
    if record["status"] == "error":
        raise SystemExit(1)


if __name__ == "__main__":
    main()
