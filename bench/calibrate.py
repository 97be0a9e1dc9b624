#!/usr/bin/env python3
"""Readings behind the benchmark's limits and rates, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... --seconds 5 \
        [--control-seeds 1 2 3] [--fault-seeds 1 2 3]

In one process, for each seed: the cell's set-up and a short window through
the same generator a run uses, then the numbers its check compares, once for
the program's outputs and, for ``--control-seeds``, once for the control's:
the plain reference in bfloat16 put in the program's place.  One JSON line
per seed.  For ``--fault-seeds``, each fault the cell can have
(``tests/faults.py``) is planted in the program in turn and read the same
way.  The benchmark's own runs never run the control or a fault.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=())
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=())
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    import jax

    run.enable_compile_cache(run.ROOT, jax)
    ns = argparse.Namespace(workload=args.workload, seed=args.seeds[0],
                            seconds=args.seconds, trace=0)
    cell, _ = run.load_cell(run.ROOT, run.BENCH_DIR, ns)
    run.device_facts(jax, cell.chips, require_chip=True)
    sys.path.insert(0, str(run.ROOT / "src"))
    generator = run.load_module(run.BENCH_DIR / "generators" / f"{cell.traffic['kind']}.py",
                                "bench_generator")
    for seed in args.seeds:
        c = dataclasses.replace(cell, seed=seed)
        t0 = time.time()
        rec, outputs = _window(jax, generator, c)
        t1 = time.time()
        line = {"seed": seed, "e2e": rec["e2e"], "attempted": rec["attempted"],
                "failed": rec["failed"], "program": generator.readings(c, rec, outputs)}
        t2 = time.time()
        if seed in args.control_seeds:
            line["control"] = generator.readings(c, rec, generator.control_outputs(c, rec, outputs))
        line["seconds"] = {"setup_and_window": t1 - t0, "reference": t2 - t1,
                           "control": time.time() - t2}
        print(json.dumps(line, default=float), flush=True)
        if seed in args.fault_seeds:
            sys.path.insert(0, str(run.BENCH_DIR / "tests"))
            import faults

            for name in faults.BY_KIND[cell.traffic["kind"]]:
                with faults.FAULTS[name]():
                    rec, outputs = _window(jax, generator, c)
                    read = generator.readings(c, rec, outputs)
                print(json.dumps({"seed": seed, "fault": name, "failed": rec["failed"],
                                  "readings": read}, default=float), flush=True)
    return 0


def _window(jax, generator, cell):
    state = generator.setup(cell)
    rec = generator.window(cell, state)
    outputs = jax.device_get(rec.pop("outputs"))
    state.clear()
    gc.collect()
    return rec, outputs


if __name__ == "__main__":
    np.seterr(all="ignore")
    sys.exit(main())
