"""The harness sees a broken timed path: each fault a cell can have
(``faults.py``), planted in the program underneath a whole run (chip check
skipped, tiny sizes on the CPU), makes ``correct`` come out false, while the
same run without it is correct."""
import json

import jax
import pytest

import faults
import rehearse


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.make_root(tmp_path_factory.mktemp("faults"))


def _kind(root, cell):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    traffic = {w["name"]: w["traffic"] for w in bench["workloads"]}[cell]
    return json.loads((root / "bench" / "traffic" / f"{traffic}.json").read_text())["kind"]


def _run(root, cell):
    jax.clear_caches()
    code, line, err = rehearse.run(root, cell, seed=23)
    jax.clear_caches()
    assert code == 0, err
    return line


CASES = [(cell, fault) for cell in rehearse.CELLS for fault in faults.BY_KIND["fit"]]


@pytest.mark.parametrize(("cell", "fault"), CASES)
def test_each_fault_makes_the_run_not_correct(root, cell, fault):
    assert fault in faults.BY_KIND[_kind(root, cell)]
    with faults.FAULTS[fault]():
        line = _run(root, cell)
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("cell", rehearse.CELLS)
def test_without_a_fault_the_same_run_is_correct(root, cell):
    line = _run(root, cell)
    assert line["correct"] is True, line["checks"]
