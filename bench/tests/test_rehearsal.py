"""Every traffic kind end to end at a tiny size on the CPU, through the same
harness a chip run uses (with its look for a chip skipped), and the
command's outward behaviour."""
import json
import shutil
import subprocess
import sys

import pytest

import rehearse

CELLS = rehearse.CELLS
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.make_root(tmp_path_factory.mktemp("rehearsal"))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(root, cell):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    code, line, err = rehearse.run(root, cell, seed=2**31 + 17)
    assert code == 0, err
    assert list(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) == want and "setup_s" in want
    assert all(e["value"] <= e["limit"] for e in line["checks"].values())
    assert f"check {sorted(line['checks'])[-1]}:" in err.strip().splitlines()[-1]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_gives_per_layer_metrics_and_a_breakdown(root, cell):
    code, line, err = rehearse.run(root, cell, seed=5, trace=1)
    assert code == 0, err
    assert list(line) == KEYS[:5] + ["breakdown", "checks"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    bench = json.loads((root / "BENCHMARK.json").read_text())
    names = {m["name"] for m in bench["per_layer"]}
    assert set(line["metrics"]) <= names
    # host-clock and counter metrics have something to read on the CPU too
    host = {m["name"] for m in bench["per_layer"] if m["source"] != "device_trace"
            and "mfu" not in m["name"] and cell in m["workloads"]}
    assert host <= set(line["metrics"])


def test_same_seed_same_inputs(root):
    import program
    import synth

    cfg = json.loads((root / "bench" / "configs" / "creditcard.json").read_text())
    a = synth.replicas(9, program.shape(cfg), 2)
    b = synth.replicas(9, program.shape(cfg), 2)
    c = synth.replicas(10, program.shape(cfg), 2)
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    assert not (a[0] == c[0]).all()


def test_a_new_cell_needs_only_new_files(root, tmp_path):
    """A configuration and a traffic mix under new names, with their cell,
    limits and entries, run without an edit to any file already there."""
    new = rehearse.make_root(tmp_path)
    before = {p: p.read_bytes() for p in (new / "bench").rglob("*") if p.is_file()}
    cfg = json.loads((new / "bench" / "configs" / "cardio-fleet-1024.json").read_text())
    cfg.update(layer_sizes=[21, 6, 10, 21], tenants=4)
    (new / "bench" / "configs" / "cardio-narrow.json").write_text(json.dumps(cfg))
    mix = {"kind": "fit", "datasets": 2, "compare_tenants": 2}
    (new / "bench" / "traffic" / "fit-two-fleets.json").write_text(json.dumps(mix))
    limits = {"limits": {"score_gap": {"limit": 0.01}, "train_count_gap": {"limit": 0.0}}}
    (new / "bench" / "checks" / "narrow-fit.json").write_text(json.dumps(limits))
    bench = json.loads((new / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "cardio-narrow", "source": "x",
                             "file": "bench/configs/cardio-narrow.json", "reduced": [],
                             "why": "x"})
    bench["workloads"].append({"name": "narrow-fit", "config": "cardio-narrow",
                               "traffic": "fit-two-fleets", "chips": 1, "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "cardio-fleet-fit" in m.get("workloads", ()):
            m["workloads"].append("narrow-fit")
    (new / "BENCHMARK.json").write_text(json.dumps(bench))
    code, line, err = rehearse.run(new, "narrow-fit")
    assert code == 0, err
    assert line["correct"] is True and "fit_samples_per_s" in line["metrics"]
    assert set(line["checks"]) == {"score_gap", "train_count_gap"}
    assert all(p.read_bytes() == data for p, data in before.items())


def _cli(root, *args, env=None):
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"), *args],
                          capture_output=True, text=True, cwd=root, env=env, timeout=300)


def test_without_a_tpu_it_exits_non_zero_and_prints_no_result(root):
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _cli(root, "--workload", "creditcard-fit", "--seed", "1", "--seconds", "1",
             "--trace", "0", env=env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_with_only_the_benchmark_files_it_exits_non_zero(tmp_path):
    bare = tmp_path / "bare"
    bare.mkdir()
    shutil.copy(rehearse.REPO / "BENCHMARK.json", bare / "BENCHMARK.json")
    shutil.copytree(rehearse.BENCH, bare / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(bare, "--workload", "creditcard-fit", "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
