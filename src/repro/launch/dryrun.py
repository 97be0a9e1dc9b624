"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
mesh, with no real allocation (ShapeDtypeStruct inputs only).

MUST set the host-device override before any other import touches jax —
jax locks the device count at first init.
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
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro import optim
from repro.configs import registry
from repro.launch import roofline as roofline_mod
from repro.launch import shardings, steps
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
from repro.models import api as model_api
from repro.models import get_bundle

# Microbatch counts for train_4k, tuned so remat'd activations fit ~16 GiB/chip
# (per-device microbatch = 256 / data_extent / microbatches sequences).
MICROBATCHES: dict[str, int] = {
    "deepseek-v2-236b": 16,  # 256/16 seqs = data extent — the max
    "granite-20b": 16,
    "mistral-nemo-12b": 8,
    "recurrentgemma-9b": 8,
    "internvl2-2b": 8,
    "qwen2-moe-a2.7b": 8,
    "whisper-tiny": 8,
    "qwen3-1.7b": 4,
    "qwen2-1.5b": 4,
    "mamba2-780m": 4,
}


def count_params(params_shape) -> tuple[int, int]:
    """(total, active) parameter counts from a ShapeDtypeStruct tree.

    Active discounts routed-expert parameters by top_k/n_experts (per-token
    activated share) — used for MODEL_FLOPS = 6 * N_active * D.
    """
    total = 0
    routed = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params_shape):
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        names = [getattr(p, "key", None) for p in path]
        if "experts" in names:
            routed += n
    return total, routed


def build(arch: str, shape_name: str, *, multi_pod: bool, microbatches: int | None,
          param_dtype=jnp.bfloat16, accum_dtype=jnp.float32,
          moments_dtype=jnp.float32):
    cfg = registry.for_shape(registry.get(arch), registry.SHAPES[shape_name])
    shape = registry.SHAPES[shape_name]
    if not registry.supported(cfg, shape):
        raise ValueError(f"{arch} x {shape_name} is a documented skip (DESIGN.md)")
    mesh = make_production_mesh(multi_pod=multi_pod)
    bundle = get_bundle(cfg, chunked_attn=shape.seq_len > 2048)

    params_shape = jax.eval_shape(
        lambda: bundle.init(jax.random.PRNGKey(0), param_dtype)
    )
    p_shard = shardings.param_shardings(params_shape, mesh)
    batch_specs = bundle.input_specs(shape, jnp.bfloat16)
    b_shard = shardings.batch_shardings(batch_specs, mesh)

    if shape.kind == "train":
        mb = microbatches if microbatches is not None else MICROBATCHES.get(arch, 8)
        # Each microbatch must still shard its batch dim over (pod, data):
        # cap at global_batch / dp_extent (e.g. 256/32 = 8 on the 2-pod mesh).
        import numpy as _np

        from repro.launch.mesh import data_axes as _data_axes

        dp_total = int(_np.prod([dict(mesh.shape)[a] for a in _data_axes(mesh)]))
        mb = min(mb, max(1, shape.global_batch // dp_total))
        opt = optim.adamw(1e-4, weight_decay=0.01, moments_dtype=moments_dtype)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        o_shard = shardings.opt_state_shardings(opt_shape, p_shard, mesh)
        step = steps.make_train_step(
            bundle, opt, microbatches=mb, accum_dtype=accum_dtype
        )
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None),
                donate_argnums=(0, 1),
            ).lower(params_shape, opt_shape, batch_specs)
        extras = {"microbatches": mb, "tokens": shape.global_batch * shape.seq_len}
        return lowered, mesh, bundle, params_shape, extras

    if shape.kind == "prefill":
        step = steps.make_prefill_step(bundle)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step, in_shardings=(p_shard, b_shard)
            ).lower(params_shape, batch_specs)
        extras = {"tokens": shape.global_batch * shape.seq_len}
        return lowered, mesh, bundle, params_shape, extras

    # decode: one token against a seq_len cache.
    cache_shape = model_api.cache_specs(
        bundle, shape.global_batch, shape.seq_len, jnp.bfloat16
    )
    c_shard = shardings.cache_shardings(cache_shape, cfg, mesh)
    token_spec = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    t_shard = shardings.batch_shardings({"t": token_spec}, mesh)["t"]
    pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
    step = steps.make_decode_step(bundle)
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            step,
            in_shardings=(p_shard, c_shard, t_shard, None),
            out_shardings=(None, c_shard),
            donate_argnums=(1,),
        ).lower(params_shape, cache_shape, token_spec, pos_spec)
    extras = {"tokens": shape.global_batch}
    return lowered, mesh, bundle, params_shape, extras


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            microbatches: int | None = None, want_roofline: bool = True,
            accum_dtype=jnp.float32, moments_dtype=jnp.float32,
            tag: str | None = None) -> dict:
    record: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "pod=2,data=16,model=16" if multi_pod else "data=16,model=16",
    }
    if tag:
        record["tag"] = tag
    shape = registry.SHAPES[shape_name]
    cfg = registry.get(arch)
    if not registry.supported(cfg, shape):
        record["status"] = "skipped"
        record["reason"] = "documented long-context skip (DESIGN.md §4)"
        return record
    t0 = time.time()
    try:
        lowered, mesh, bundle, params_shape, extras = build(
            arch, shape_name, multi_pod=multi_pod, microbatches=microbatches,
            accum_dtype=accum_dtype, moments_dtype=moments_dtype,
        )
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        record.update(status="ok", lower_s=round(t_lower, 1),
                      compile_s=round(t_compile, 1), **extras)
        mem = compiled.memory_analysis()
        record["memory"] = {
            k: int(getattr(mem, k, 0))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
                "alias_size_in_bytes",
            )
        }
        if want_roofline:
            rf = roofline_mod.analyze(
                compiled, mesh, device_kind=PRODUCTION_DEVICE_KIND
            )
            record["roofline"] = rf.as_dict()
            total, routed = count_params(params_shape)
            cfg2 = bundle.cfg
            active = total
            if cfg2.moe and cfg2.n_experts:
                active = total - int(routed * (1 - cfg2.top_k / cfg2.n_experts))
            record["n_params"] = total
            record["n_active_params"] = active
            mf = roofline_mod.model_flops(
                total, active, extras["tokens"],
                "train" if shape.kind == "train" else "serve",
            )
            record["model_flops"] = mf
            hw_total = rf.flops_per_device * rf.chips
            record["useful_flops_ratio"] = mf / hw_total if hw_total else None
    except Exception as e:  # noqa: BLE001 — a failed pair is a data point
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-2000:]
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=sorted(registry.ARCHS))
    ap.add_argument("--shape", required=True, choices=sorted(registry.SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--out", default=None, help="append JSON record to this file")
    ap.add_argument("--no-roofline", action="store_true")
    ap.add_argument("--accum-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--moments-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--causal-skip", action="store_true",
                    help="enable attend_auto's causal block-skip (§Perf-3)")
    ap.add_argument("--tag", default=None,
                    help="label for §Perf iteration records")
    args = ap.parse_args()
    if args.causal_skip:
        from repro.models import attention as _attn

        _attn.DEFAULT_CAUSAL_SKIP = True

    record = run_one(
        args.arch, args.shape,
        multi_pod=args.multi_pod,
        microbatches=args.microbatches,
        want_roofline=not args.no_roofline,
        accum_dtype=jnp.bfloat16 if args.accum_dtype == "bfloat16" else jnp.float32,
        moments_dtype=(
            jnp.bfloat16 if args.moments_dtype == "bfloat16" else jnp.float32
        ),
        tag=args.tag,
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
