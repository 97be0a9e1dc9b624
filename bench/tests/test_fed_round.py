"""The federated-round cell (traffic kind ``fed_round``) end to end at a tiny
size on the CPU, through the same harness a chip run uses, on 4 forced host
devices: a correct run with ``--trace 0`` and ``--trace 1``, each fault of
``faults_fed_round.py`` making ``correct`` false, and the cell's readers on
a program that has the round's spans and scopes and on one that has not.

Everything runs in one subprocess (the device count is fixed when JAX
starts); the tests read its report."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import rehearse

CELL = "cardio-fed-tree-4chip"
READERS = ("round_host_ms", "reduce_prepare_ms", "round_fit_device_ms", "merge_device_ms",
           "merge_solve_device_ms", "merge_collective_ms", "round_mfu",
           "device_idle_share.round")
# the readers that read the program's spans, scopes or lower_reduce
PROGRAM_READERS = ("reduce_prepare_ms", "round_fit_device_ms", "merge_device_ms",
                   "merge_solve_device_ms", "merge_collective_ms")

_SCRIPT = """
import argparse, json, sys, tempfile
from pathlib import Path
BENCH = Path({bench!r})
for p in (BENCH, BENCH / "tests", BENCH.parent / "src"):
    sys.path.insert(0, str(p))
import jax
import faults
import faults_fed_round
import rehearse
import run as bench_run

CELL = {cell!r}
root = rehearse.make_root(Path(tempfile.mkdtemp()))
report = {{}}
for trace in (0, 1):
    code, line, err = rehearse.run(root, CELL, seed=2**31 + 17, seconds=1.0, trace=trace)
    report[f"trace{{trace}}"] = {{"code": code, "line": line, "err": err[-2000:]}}
for name, fault in faults_fed_round.FAULTS.items():
    with fault():
        code, line, err = rehearse.run(root, CELL, seed=23, seconds=0.5)
    report[name] = {{"code": code, "line": line, "err": err[-2000:]}}
report["faults_of_kind"] = list(faults_fed_round.BY_KIND["fed_round"])

# the readers on a run of the generator, with a trace made to hold every
# operation of the round's programs
sys.path.insert(0, str(root / "bench"))
ns = argparse.Namespace(workload=CELL, seed=5, seconds=0.3, trace=0)
cell, bench = bench_run.load_cell(root, root / "bench", ns)
generator = bench_run.load_module(root / "bench" / "generators" / "fed_round.py",
                                  "fed_round_readers")
state = generator.setup(cell)
record = generator.window(cell, state)
record.pop("outputs")
import round_scopes
readers = {{m["name"]: bench_run.load_module(root / "bench" / "metrics" / f"{{m['name']}}.py",
                                            "reader_" + m["name"].replace(".", "_"))
           for m in bench["per_layer"] if CELL in m.get("workloads", ())}}
base = {{"record": record, "config": cell.config, "chips": cell.chips,
        "device": {{"platform": "tpu", "kind": "TPU v5 lite"}}}}
probe = dict(base, trace={{"op_s": {{"none:none": 0.0}}}})
progs = round_scopes.programs(probe)
# on the CPU the SVD is a LAPACK call; on the chip it loops (while ops)
progs["merge"]["holders"].add("while.999")
progs["merge"]["names"]["while.999"] = "jit(_tree_merge)/shard_map/merge_local/while"
op_s, leaf = {{}}, 0.0
for prog in progs.values():
    for instr in set(prog["names"]) | prog["holders"]:
        op_s[prog["module"] + ":" + instr] = 1e-3
        leaf += 0.0 if instr in prog["holders"] else 1e-3
op_s[round_scopes.DEDUP_MODULE + ":slice.1"] = 1e-3
leaf += 1e-3
solve = sum(1e-3 for instr, op in progs["merge"]["names"].items()
            if instr not in progs["merge"]["holders"]
            and round_scopes.merge_scope(op) == "merge_solve")
trace = {{"op_s": op_s, "devices": 4, "busy_s": leaf, "window_s": 2 * leaf,
         "collective_s": 2e-3, "collective_exposed_s": 1e-3}}
round_scopes._PROGRAMS[id(trace)] = progs  # with the holder put in
with_program = dict(base, trace=trace)
report["readers"] = {{name: r.read(with_program) for name, r in readers.items()}}
report["expect"] = {{"busy_ms": 1e3 * leaf / record["rounds"],
                    "solve_ms": 1e3 * solve / record["rounds"],
                    "holders": len(progs["merge"]["holders"])}}

# the same readers on a program without the round's spans, scopes and
# lower_reduce (the parent commit's)
from repro import obs
from repro.engine import DAEFEngine
lower_reduce = DAEFEngine.lower_reduce
del DAEFEngine.lower_reduce
obs._TABLE.clear()
round_scopes._PROGRAMS.clear()
try:
    report["without"] = {{name: r.read(dict(base, trace=dict(trace)))
                         for name, r in readers.items()}}
finally:
    DAEFEngine.lower_reduce = lower_reduce
print("REPORT " + json.dumps(report))
"""


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = textwrap.dedent(_SCRIPT.format(bench=str(rehearse.BENCH), cell=CELL))
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                          env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(ln for ln in proc.stdout.splitlines() if ln.startswith("REPORT "))
    return json.loads(line[len("REPORT "):])


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_and_is_correct(report, trace):
    got = report[f"trace{trace}"]
    assert got["code"] == 0, got["err"]
    line = got["line"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["count"] == 4
    assert set(line["checks"]) == {"stats_gap", "encoder_gap", "solve_gap",
                                   "train_quantile_gap", "train_count_gap"}
    if trace == 0:
        assert set(line["metrics"]) == {"fit_samples_per_s", "setup_s"}
    else:
        # on the CPU the trace holds no TPU plane: only host readers read
        assert {"round_host_ms", "reduce_prepare_ms"} <= set(line["metrics"])
        assert set(line["metrics"]) <= set(READERS)


@pytest.mark.parametrize("fault", ["no_exchange", "no_merge"])
def test_each_fault_makes_the_run_not_correct(report, fault):
    assert fault in report["faults_of_kind"]
    got = report[fault]
    assert got["code"] == 0, got["err"]
    assert got["line"]["correct"] is False, got["line"]["checks"]


def test_the_readers_read_a_round_with_its_programs(report):
    got, want = report["readers"], report["expect"]
    assert set(got) == set(READERS)
    assert all(got[name] is not None for name in READERS), got
    # leaf operations only: the holders' events, which span their bodies',
    # are left out, and the two programs cover the devices' busy time
    assert want["holders"] > 0
    assert got["round_fit_device_ms"] + got["merge_device_ms"] == pytest.approx(
        want["busy_ms"], rel=1e-9)
    assert got["merge_solve_device_ms"] == pytest.approx(want["solve_ms"], rel=1e-9)
    assert 0 < got["merge_solve_device_ms"] < got["merge_device_ms"]
    assert got["device_idle_share.round"] == pytest.approx(50.0)


def test_holders_are_the_ops_that_hold_others():
    import round_scopes

    text = textwrap.dedent("""\
        ENTRY %main {
          %while.1539 = (s32[]{:T(128)}, f32[128,21,21]{0,1,2:T(8,128)S(1)}) while(%tuple.1859), condition=%c, body=%b
          ROOT %call.7 = f32[2]{0} call(%p), to_apply=%f
          %fusion.3 = f32[2]{0} fusion(%p), kind=kLoop, calls=%fused
          %custom-call.4 = f32[2]{0} custom-call(%p), custom_call_target="Cholesky"
          %conditional.2 = (f32[2]{0}) conditional(%q, %p, %p), branch_computations={%x, %y}
        }
        """)
    assert round_scopes.holders(text) == {"while.1539", "call.7", "conditional.2"}


@pytest.mark.parametrize("name", PROGRAM_READERS)
def test_a_reader_gives_none_on_a_program_without_the_round_spans(report, name):
    assert report["without"][name] is None


@pytest.mark.parametrize("name", sorted(set(READERS) - set(PROGRAM_READERS)))
def test_the_other_readers_read_the_benchmarks_own_clock(report, name):
    assert report["without"][name] is not None
