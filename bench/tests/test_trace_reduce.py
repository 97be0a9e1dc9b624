"""The trace reduction on a small trace recorded on a TPU v5e
(``record_trace.py``; ``data/trace_v5e_1chip.xplane.pb``), against values
counted by hand from its events.

The events, in ns on the trace's clock.  Host spans: ``bench.window``
50,014,300 + 19,538,521; ``bench.phase.a`` 50,016,960 + 2,454,784;
``bench.phase.b`` 62,989,261 + 6,556,340.  Device programs (``jit__lambda``):
48,980,331 + 19,149; 49,891,193 + 13,266; 50,746,311 + 13,200;
61,907,884 + 13,188, each with ops copy-start (13 ns), copy-done (5,898, 3,
2, 2 ns) and convolution_tanh_fusion (13,231, 13,243, 13,178, 13,168 ns);
the last two programs' ops start at 50,746,313 / 50,746,328 / 50,746,331 and
61,907,887 / 61,907,902 / 61,907,905.  The first two programs lie before the
window opens: on this trace the device's events sit 1.2-1.6 ms before the
host's launch of the same program, so the window clips them.
"""
from pathlib import Path

import pytest

import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    return trace_reduce.reduce(ProfileData.from_file(str(DATA / "trace_v5e_1chip.xplane.pb")))


def test_window_and_busy_time_as_a_union(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(19_538_521e-9)
    # the two programs inside the window: 13 + 2 + 13,178 and 13 + 2 + 13,168
    assert reduced["busy_s"] == pytest.approx(26_376e-9)


def test_time_per_operation_and_per_program(reduced):
    assert reduced["op_s"] == pytest.approx({
        "jit__lambda:copy-start": 26e-9,
        "jit__lambda:copy-done": 4e-9,
        "jit__lambda:convolution_tanh_fusion": 26_346e-9})
    assert reduced["module_s"] == pytest.approx({"jit__lambda": 26_388e-9})


def test_no_collectives_on_one_chip(reduced):
    assert reduced["collective_s"] == 0.0 and reduced["collective_exposed_s"] == 0.0


def test_idle_gaps_are_shared_out_among_the_host_spans_they_overlap(reduced):
    # window start to the first op (732,013): 729,353 in phase.a, 2,660
    # before it; between the ops of the third program 2 + 1, in phase.a; from
    # its end to the fourth (11,148,378): 1,712,235 in phase.a's tail, the
    # rest in no span; between the fourth's ops 2 + 1, in no span; from its
    # end to the window's (7,631,748): 6,556,340 in phase.b, 1,075,408 not.
    assert reduced["gap_s"] == pytest.approx({
        "bench.phase.a": (729_353 + 3 + 1_712_235) * 1e-9,
        "bench.phase.b": 6_556_340e-9,
        "host:other": (2_660 + 11_148_378 - 1_712_235 + 3 + 1_075_408) * 1e-9})
    total = sum(reduced["gap_s"].values()) + reduced["busy_s"]
    assert total == pytest.approx(reduced["window_s"])


def test_collective_time_and_the_part_with_nothing_beside_it():
    iv = trace_reduce.union([(0, 10), (5, 20), (30, 40)])
    assert iv == [(0, 20), (30, 40)]
    assert trace_reduce.covered([(0, 10), (5, 20), (30, 40)]) == 30
    # a collective over [0, 20) and [30, 40) with compute over [5, 12) and [35, 50)
    assert trace_reduce._subtract(iv, trace_reduce.union([(5, 12), (35, 50)])) == 5 + 8 + 5


def test_short_names():
    assert trace_reduce.short_op("%fusion.5 = f32[24,28,28]{2,1,0} fusion(f32[2] %a)") == \
        "fusion.5"
    assert trace_reduce.short_op(
        '%custom-call.26 = (f32[4]) custom-call(%x), custom_call_target="EighTpu"') == \
        "custom-call.26 EighTpu"
    assert trace_reduce.short_module("jit__lambda(6437250063087432127)") == "jit__lambda"
    assert trace_reduce.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0], ["c", 2.0]]
