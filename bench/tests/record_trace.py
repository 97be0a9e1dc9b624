#!/usr/bin/env python3
"""Record the small trace that ``test_trace_reduce.py`` reads, on the chip.

    python3 bench/tests/record_trace.py <out_dir>

A known sequence on every device JAX finds: inside the benchmark's window
span, a matrix product run three times under ``bench.phase.a`` and once
under ``bench.phase.b`` with sleeps between them, and, where there are
several devices, one ``ppermute`` of a sharded array around them.  The
profiler's ``.xplane.pb`` is copied to ``<out_dir>/trace.xplane.pb``.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


def main(out_dir: str) -> int:
    devices = jax.devices()
    mesh = jax.make_mesh((len(devices),), ("d",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    spec = NamedSharding(mesh, P("d"))
    x = jax.device_put(jnp.ones((len(devices) * 1024, 1024), jnp.float32), spec)
    mm = jax.jit(lambda a: jnp.tanh(a @ a.T[:, :1024]))
    perm = [(i, (i + 1) % len(devices)) for i in range(len(devices))]
    shift = jax.jit(jax.shard_map(lambda a: jax.lax.ppermute(a, "d", perm), mesh=mesh,
                                  in_specs=P("d"), out_specs=P("d")))
    jax.block_until_ready((mm(x), shift(x)))
    tmp = tempfile.mkdtemp(prefix="record-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.phase.a"):
            for _ in range(3):
                jax.block_until_ready(mm(x))
        time.sleep(0.01)
        with jax.profiler.TraceAnnotation("bench.phase.b"):
            jax.block_until_ready(mm(x))
            time.sleep(0.005)
        if len(devices) > 1:
            jax.block_until_ready(shift(x))
    jax.profiler.stop_trace()
    os.makedirs(out_dir, exist_ok=True)
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, os.path.join(out_dir, "trace.xplane.pb"))
    print(f"recorded {os.path.getsize(path)} bytes on {len(devices)} x "
          f"{devices[0].device_kind}")
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
