"""The trace reduction against values counted by hand on a small trace.

Device 0 (window 0-500 ns): ops 100-200 (fusion), 250-300 (all-reduce),
280-350 (convolution), 400-450 (fusion); an asynchronous all-reduce in
flight 300-420; the step program runs 90-360 and 390-460.  Device 1 runs
one ``while`` from 0 to 500 whose body ops run 0-200 and 300-500: the
container is not busy time, its body is.  So device 0 is busy 250 ns,
device 1 400: mean 325, idle share 0.35.  Device 0's collectives cover 250-420 (170
ns), of which 250-280 and 350-400 have no other op (80 ns exposed).
"""
import json
from pathlib import Path

import pytest

from bench.harness.trace import Trace, merge, minus, op_name, total

FIXTURE = Path(__file__).parent / "fixture" / "trace.json"


@pytest.fixture(scope="module")
def tr():
    return Trace.from_dict(json.loads(FIXTURE.read_text()))


def test_window_and_busy_union(tr):
    assert tr.window_s == pytest.approx(500e-9)
    assert tr.busy_s() == pytest.approx(325e-9)
    assert tr.idle_share() == pytest.approx(0.35)


def test_collective_and_exposed(tr):
    assert tr.has_collectives()
    assert tr.collective_s() == pytest.approx(85e-9)        # (170 + 0) / 2
    assert tr.collective_exposed_s() == pytest.approx(40e-9)  # (80 + 0) / 2


def test_program_time_and_runs(tr):
    step = lambda n: n == "jit_step"
    assert tr.biggest_module() == "jit_step"
    assert tr.module_count(step) == pytest.approx(1.5)      # (2 + 1) / 2
    assert tr.module_s(step) == pytest.approx(325e-9)        # (250 + 400) / 2
    assert tr.module_s(lambda n: n == "jit_other") == 0


def test_breakdown(tr):
    ops = dict(tr.top_ops())
    assert ops == pytest.approx({"fusion": 275e-9, "convolution": 35e-9,
                                 "all-reduce": 25e-9})      # no "while"
    gaps = dict(tr.idle_gaps())
    # device 0's gaps: 0-100 and 350-400 fall in input builds, 200-250
    # inside a run of the step, 450-500 in nothing; device 1's 200-300
    # inside its loop's run; halved over the two devices
    assert gaps == pytest.approx({"bench.train.input": 75e-9,
                                  "in_program": 75e-9,
                                  "host_other": 25e-9})


def test_interval_helpers():
    m = merge([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert m == [(0, 3), (5, 9)]
    assert total(m) == 7
    assert minus(m, [(1, 2), (6, 7)]) == 5
    assert minus([(0, 10)], []) == 10
    assert op_name("%all-reduce-start.5 = (f32[8]) x") == "all-reduce-start"
    assert op_name("jit_dc(4921013600371670248)") == "jit_dc"


def test_missing_window_span_is_an_error():
    with pytest.raises(ValueError):
        Trace.from_dict({"devices": {}, "host": [[0, 1, "bench.other"]]})
