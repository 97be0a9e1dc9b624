"""The control comes out not correct: the plain reference in bfloat16, put in
the program's place, read by each one-model cell's own check against the
limits in ``checks/``.  Each model keeps its published sample count (the
control's departure grows with it); fleets keep 8 tenants and the serving
rate is cut, so the test holds on the CPU.  On the chip, at each cell's own
size, ``calibrate.py --control-seeds`` reads the same (``PERF.md``)."""
import argparse
import json

import jax
import pytest

import check
import rehearse
import run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.make_root(tmp_path_factory.mktemp("control"), samples="published")


@pytest.mark.parametrize("cell", rehearse.CELLS)
def test_the_control_is_not_correct(root, cell):
    ns = argparse.Namespace(workload=cell, seed=2**31 + 5, seconds=0.5, trace=0)
    c, _ = run.load_cell(root, root / "bench", ns)
    generator = run.load_module(root / "bench" / "generators" / f"{c.traffic['kind']}.py",
                                f"control_{c.traffic['kind']}")
    state = generator.setup(c)
    record = generator.window(c, state)
    outputs = jax.device_get(record.pop("outputs"))
    state.clear()
    lims = check.limits(root / "bench", cell)
    program = check.verdict(generator.readings(c, record, outputs), lims, record["failed"])
    control = check.verdict(generator.readings(c, record, generator.control_outputs(c, record,
                                                                              outputs)),
                            lims, record["failed"])
    assert program[0] is True, json.dumps(program[1])
    assert control[0] is False, json.dumps(control[1])
