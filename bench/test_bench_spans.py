"""The readers of the program's ``llcg.*`` spans and of the two round
programs, on a synthesised trace counted by hand."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import tracereduce as tr  # noqa: E402
from tracereduce import Event, Line, Plane  # noqa: E402

NEW = ["local_phase_ms", "correction_ms", "driver_ms", "idle_read_ms",
       "idle_host_ms"]


def _trace(spans=True, names=("jit_counted_round", "jit_counted_correction")):
    """Window [1000, 2000) ns with 2 rounds.  The round span open when the
    window starts begins before it, the last one runs past its end; the one
    in between, [1010, 1500), holds reads of 200, 120 and (inside the
    evaluation) 40 ns.  Device 0 is busy in [1000, 1250), [1320, 1400),
    [1500, 1700) and [1800, 1950): idle 70 + 100 + 100 + 50 = 320 ns, of
    which the reads cover 70 + (20 + 40) + 50 + 40 = 220 ns."""
    rnd, corr = names
    llcg = [Event("llcg.round", 900, 110),
            Event("llcg.round#step_num=4#", 1010, 490),
            Event("llcg.sample", 1020, 40),
            Event("llcg.correction_draw", 1030, 20),
            Event("llcg.dispatch", 1060, 40),
            Event("llcg.read", 1100, 200),
            Event("llcg.read", 1300, 120),
            Event("llcg.evaluate", 1420, 70),
            Event("llcg.read", 1440, 40),
            Event("llcg.round", 1500, 550),
            Event("llcg.read", 1600, 150),
            Event("llcg.read", 1900, 90)]
    host = Plane("/host:CPU", [Line("python", [
        Event(tr.WINDOW_START, 1000, 0), Event(tr.ROUND_END, 900, 0),
        Event(tr.ROUND_END, 1495, 0), Event(tr.ROUND_END, 2000, 0),
        Event("PjitFunction(counted_round)", 1060, 30),
        *(llcg if spans else [])])])
    dev0 = Plane("/device:TPU:0", [
        Line(tr.OPS_LINE, [Event("fusion.1", 1000, 150),
                           Event("fusion.2", 1140, 110),
                           Event("fusion.3", 1320, 80),
                           Event("fusion.4", 1500, 200),
                           Event("fusion.5", 1800, 150)]),
        Line(tr.MODULES_LINE, [Event(f"{rnd}(3)", 900, 350),
                               Event(f"{corr}(4)", 1320, 80),
                               Event("jit_evaluate(2)", 1500, 200),
                               Event(f"{rnd}(3)", 1800, 150)])])
    return [host, dev0]


def _measured(planes):
    return harness.Measured(tr.window_of(planes), flops_per_round=1e6,
                            chips=1, peak_flops=1e15)


@pytest.mark.parametrize("metric,expected", [
    ("local_phase_ms", (250 + 150) / 2 * 1e-6),
    ("correction_ms", 80 / 2 * 1e-6),
    ("driver_ms", (490 - 200 - 120 - 40) * 1e-6),
    ("idle_read_ms", 220 / 2 * 1e-6),
    ("idle_host_ms", (320 - 220) / 2 * 1e-6),
])
def test_span_readers_by_hand(metric, expected):
    got = harness.load_reader(metric)(_measured(_trace()))
    assert got == pytest.approx(expected)


def test_the_halves_sum_to_the_round_program():
    m = _measured(_trace())
    read = lambda name: harness.load_reader(name)(m)  # noqa: E731
    assert read("local_phase_ms") + read("correction_ms") == pytest.approx(
        read("round_program_ms"))


def test_the_idle_split_sums_to_the_idle_share():
    m = _measured(_trace())
    read = lambda name: harness.load_reader(name)(m)  # noqa: E731
    w = m.window
    idle_ms = (read("device_idle_share") / 100 * (w.end_ns - w.start_ns)
               / w.rounds * 1e-6)
    assert read("idle_read_ms") + read("idle_host_ms") == pytest.approx(
        idle_ms)


@pytest.mark.parametrize("metric", NEW)
def test_span_readers_return_nothing_on_the_parent_trace(metric):
    """A trace with no ``llcg.*`` spans and one program ``jit_counted``
    for both halves, as the program wrote before it named them."""
    parent = _trace(spans=False, names=("jit_counted", "jit_counted"))
    assert harness.load_reader(metric)(_measured(parent)) is None
    assert harness.load_reader(metric)(
        harness.Measured(None, 1e6, 1, 1e15)) is None
