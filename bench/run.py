#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run loads, warms up every shape its traffic uses, measures for
``--seconds``, checks what the timed path produced against the plain
reference (``reference.py``), and prints one JSON object as the last line of
standard output::

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

With ``--trace 0`` the metrics are the cell's end-to-end metrics, taken on
the host clock with the profiler off; with ``--trace 1`` the window runs
under the profiler and the metrics are the cell's per-layer metrics.

Everything is found by name.  A cell names a configuration, whose ``file``
holds its sizes, and a traffic mix, ``traffic/<traffic>.json``, whose
``kind`` names the generator ``generators/<kind>.py`` that generates it; a
per-layer metric ``<metric>`` is read by ``metrics/<metric>.py``; the
correctness limits of a cell are in ``checks/<workload>.json``.  A cell is
added by adding files and entries, without editing any file here.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.  JAX's persistent compilation cache is kept
in ``$JAX_COMPILATION_CACHE_DIR`` when set, else in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class Unavailable(RuntimeError):
    """The run cannot take place here (no chip, no program, no such cell)."""


@dataclasses.dataclass
class Cell:
    """One run's cell, as the generators see it."""

    name: str
    config: dict
    traffic: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    bench_dir: Path


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise Unavailable(f"no such file: {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: Path, bench_dir: Path, args) -> tuple[Cell, dict]:
    bench_file = root / "BENCHMARK.json"
    if not bench_file.is_file():
        raise Unavailable(f"no {bench_file}")
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise Unavailable(f"no workload {args.workload!r} in BENCHMARK.json "
                          f"(have {sorted(cells)})")
    w = cells[args.workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    cell = Cell(name=w["name"], config=config, traffic=traffic, chips=int(w["chips"]),
                seed=int(args.seed), seconds=float(args.seconds), trace=bool(args.trace),
                bench_dir=bench_dir)
    return cell, bench


def enable_compile_cache(root: Path, jax) -> str:
    """The program's ``launch/compile_cache.enable``: a fixed directory in the
    checkout unless ``$JAX_COMPILATION_CACHE_DIR`` names one."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def device_facts(jax, chips: int, require_chip: bool) -> dict:
    devices = jax.devices()
    dev = devices[0]
    if require_chip and dev.platform != "tpu":
        raise Unavailable(f"no TPU: JAX found platform {dev.platform!r}")
    if len(devices) < chips:
        raise Unavailable(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def memory_peak(jax, chips: int) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks, default=0))


class WindowWatch:
    """Compiles and garbage-collector pauses from now until ``stop()``: what
    could stall a window that should only run warm programs."""

    def __init__(self, jax):
        self.compiles, self.compile_s = 0, 0.0
        self.gc_runs, self.gc_max_s = 0, 0.0
        self._gc_start = None
        self._on = True
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        gc.callbacks.append(self._on_gc)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if self._on and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._on and self._gc_start is not None:
            self.gc_runs += 1
            self.gc_max_s = max(self.gc_max_s, time.perf_counter() - self._gc_start)

    def stop(self) -> None:
        self._on = False
        gc.callbacks.remove(self._on_gc)


def applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def main(argv=None, *, root: Path = ROOT, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench_dir = root / BENCH_DIR.name
    try:
        cell, bench = load_cell(root, bench_dir, args)
        if not (root / "src" / "repro").is_dir():
            raise Unavailable(f"no program under {root / 'src'}")
        import jax

        cache_dir = enable_compile_cache(root, jax)
        device = device_facts(jax, cell.chips, require_chip)
    except Unavailable as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    if str(bench_dir) not in sys.path:
        sys.path.insert(0, str(bench_dir))
    import check
    import trace_reduce

    print(f"bench: {cell.name} seed {cell.seed} on {device['count']} x "
          f"{device['kind']}; compile cache {cache_dir}", file=sys.stderr)
    kind = cell.traffic["kind"]
    generator = load_module(bench_dir / "generators" / f"{kind}.py", f"bench_generator_{kind}")
    state = generator.setup(cell)
    reduction: dict | None = None
    import jax.profiler

    watch = WindowWatch(jax)
    setup_s = time.time() - PROCESS_START
    if cell.trace:
        reduction = {}
        with trace_reduce.capture(reduction):
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                record = generator.window(cell, state)
    else:
        record = generator.window(cell, state)
    watch.stop()
    device["memory_peak_bytes"] = memory_peak(jax, cell.chips)
    outputs = jax.device_get(record.pop("outputs"))
    state.clear()
    gc.collect()

    readings = generator.readings(cell, record, outputs)
    lims = check.limits(bench_dir, cell.name)
    correct, table = check.verdict(readings, lims, record["failed"])

    metrics = {}
    if not cell.trace:
        e2e = dict(record["e2e"], setup_s=setup_s)
        for m in bench["end_to_end"]:
            if applies(m, cell.name) and m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        run = {"record": record, "trace": reduction, "device": device,
               "config": cell.config, "chips": cell.chips}
        for m in bench["per_layer"]:
            if not applies(m, cell.name):
                continue
            reader = load_module(bench_dir / "metrics" / f"{m['name']}.py",
                                 "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        print(f"trace: {reduction['devices']} device(s), busy {reduction['busy_s']} s "
              f"of {reduction['window_s']} s, collectives {reduction['collective_s']} s "
              f"({reduction['collective_exposed_s']} s with nothing else running), "
              f"{reduction['trace_bytes']} bytes; seconds to stop, read and reduce it "
              f"{reduction['cost_s']}", file=sys.stderr)

    line = {"correct": correct, "attempted": record["attempted"],
            "failed": record["failed"], "metrics": metrics, "device": device}
    if reduction is not None:
        line["breakdown"] = {"device_ops": trace_reduce.top(reduction["op_s"]),
                             "idle_gaps": trace_reduce.top(reduction["gap_s"])}
    line["checks"] = table
    for note in record.get("notes", []):
        print(f"note: {note}", file=sys.stderr)
    print(f"note: every reading {json.dumps(readings)}", file=sys.stderr)
    print(f"note: inside the window {watch.compiles} compile(s) of "
          f"{watch.compile_s} s, {watch.gc_runs} garbage collection(s), the longest "
          f"{watch.gc_max_s} s", file=sys.stderr)
    for name, entry in table.items():
        print(f"check {name}: {entry['value']} (limit {entry['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
