"""A checkout in miniature for CPU rehearsals of the benchmark.

``make_root`` copies ``BENCHMARK.json`` and the benchmark's directory into
a temporary root, links the program's ``src``, and shrinks every
configuration and traffic mix (fewer samples, tenants and compared tenants)
without touching their shapes: the same harness, generators and readers run
at a size a CPU test holds.  ``run`` drives one cell there with the chip check skipped and returns
the parsed last line.
"""
from __future__ import annotations

import contextlib
import io
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"

CELLS = ("creditcard-fit", "cardio-fleet-fit")
TINY_DATASET = {"creditcard": {"n_total": 3000, "n_anomaly": 40},
                "cardio": {"n_total": 400, "n_anomaly": 40}}
TINY_TENANTS = 8
TINY_TRAFFIC = {"compare_tenants": 4}


def make_root(tmp: Path, *, samples: str = "tiny") -> Path:
    """``samples="tiny"`` shrinks every data set; ``"published"`` keeps each
    model's published sample counts and cuts only the tenants."""
    root = Path(tmp) / "checkout"
    root.mkdir()
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(REPO / "src")
    for path in (root / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        if samples == "tiny":
            cfg["dataset"].update(TINY_DATASET[cfg["dataset"]["name"]])
        if cfg.get("tenants", 1) > 1:
            cfg["tenants"] = TINY_TENANTS
        path.write_text(json.dumps(cfg))
    for path in (root / "bench" / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        for key in TINY_TRAFFIC:
            if key in mix:
                mix[key] = min(mix[key], TINY_TRAFFIC[key])
        path.write_text(json.dumps(mix))
    return root


def run(root: Path, workload: str, seed: int = 3, seconds: float = 1.0,
        trace: int = 0) -> tuple[int, dict | None, str]:
    """(exit code, parsed last line or None, standard error)."""
    import importlib.util
    import sys

    spec = importlib.util.spec_from_file_location("bench_run", root / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench_run"] = mod
    spec.loader.exec_module(mod)
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        code = mod.main(["--workload", workload, "--seed", str(seed), "--seconds",
                         str(seconds), "--trace", str(trace)], root=root,
                        require_chip=False)
    lines = out.getvalue().strip().splitlines()
    return code, (json.loads(lines[-1]) if code == 0 and lines else None), err.getvalue()
