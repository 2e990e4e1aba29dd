"""The trace reduction, the FLOP count and a kernel's roofline share, on a
synthesised trace and on hand counts for tiny models."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import flops  # noqa: E402
import harness  # noqa: E402
import kernel_share  # noqa: E402
import tracereduce as tr  # noqa: E402
from tracereduce import Event, Line, Plane  # noqa: E402


def _trace():
    """Window [1000, 2000) ns with 2 rounds.  Device 0 runs ops in
    [1100, 1300), [1250, 1400) (overlapping), [1600, 1700) and an
    all-reduce in [1800, 1850); the modules attribute them.  The host
    has two annotations that cover the idle gaps."""
    host = Plane("/host:CPU", [
        Line("python", [
            Event(tr.WINDOW_START, 1000, 0),
            Event("bench.evaluate", 1390, 220),
            Event("bench.sample", 1700, 100),
            Event("PjitFunction(counted)", 1420, 30),
            Event(tr.ROUND_END, 1500, 0),
            Event(tr.ROUND_END, 2000, 0),
            Event(tr.ROUND_END, 900, 0)])])     # before the window
    dev0 = Plane("/device:TPU:0", [
        Line(tr.OPS_LINE, [Event("fusion.1", 1100, 200),
                           Event("fusion.2", 1250, 150),
                           Event("fusion.1", 1600, 100),
                           Event("%all-reduce.3 = f32[8]{0} all-reduce("
                                 "f32[8]{0} %p)", 1800, 50),
                           Event("%fusion.4 = f32[8]{0} fusion(f32[8]{0} "
                                 "%all-reduce.3)", 1850, 0),
                           Event("%while.1 = (s32[]) while(s32[] %c)",
                                 1100, 300),
                           Event("fusion.9", 500, 100)]),   # before
        Line(tr.MODULES_LINE, [Event("jit_counted(7)", 1100, 300),
                               Event("jit_evaluate(2)", 1600, 100),
                               Event("jit__device_round(1)", 1800, 50),
                               Event("jit_counted(7)", 1950, 100)])])
    dev1 = Plane("/device:TPU:1", [
        Line(tr.OPS_LINE, [Event("fusion.1", 1000, 1000)])])
    return [host, dev0, dev1, Plane("/host:metadata", [])]


def test_window_markers_and_bounds():
    w = tr.window_of(_trace())
    assert (w.start_ns, w.end_ns, w.rounds) == (1000, 2000, 2)
    assert w.seconds == pytest.approx(1e-6)
    assert sorted(w.ops) == [0, 1]
    assert tr.window_of([Plane("/host:CPU", [])]) is None


def test_busy_is_the_union_of_op_intervals():
    w = tr.window_of(_trace())
    # [1100,1400) + [1600,1700) + [1800,1850) = 300 + 100 + 50
    assert tr.device_busy_ns(w, 0) == 450
    assert tr.device_busy_ns(w, 1) == 1000
    assert tr.merged([Event("a", 0, 10), Event("b", 5, 10),
                      Event("c", 20, 5)], 0, 100) == [(0, 15), (20, 25)]


def test_module_attribution_clips_to_the_window():
    w = tr.window_of(_trace())
    assert tr.module_ns(w, 0, "jit_counted") == 300 + 50
    assert tr.module_ns(w, 0, "jit_evaluate") == 100
    assert tr.module_ns(w, 0, "jit__device_round") == 50
    assert tr.module_ns(w, 0, "jit_nothing") is None


def test_allreduce_time():
    w = tr.window_of(_trace())
    assert tr.ops_ns(w, 0, r"all-reduce") == 50
    assert tr.ops_ns(w, 1, r"all-reduce") is None
    w.async_ops[1] = [Event("all-reduce-start.1", 1200, 30),
                      Event("copy-start.2", 1200, 70)]
    assert tr.ops_ns(w, 1, r"all-reduce") == 30


def test_op_names_and_labels():
    text = ("%fusion.7 = f32[169343,256]{1,0:T(8,128)} fusion(s32[5]{0} "
            "%a, f32[5,256]{1,0} %b), kind=kCustom")
    assert tr.op_name(text) == "fusion.7"
    assert tr.op_label(text) == "fusion.7 = f32[169343,256] fusion"
    assert tr.op_name("copy.3") == tr.op_label("copy.3") == "copy.3"


def test_top_ops_and_named_gaps():
    w = tr.window_of(_trace())
    top = tr.top_ops(w, 0)
    assert top[0] == ["fusion.1", pytest.approx(300e-9)]
    assert not [t for t in top if t[0].startswith("while")]
    gaps = tr.idle_gaps(w, 0)
    # gaps: [1000,1100) 100, [1400,1600) 200, [1700,1800) 100,
    # [1850,2000) 150; the midpoint of the longest (1500) lies in
    # bench.evaluate only
    assert gaps[0] == ["bench.evaluate", pytest.approx(200e-9)]
    assert gaps[1] == ["(no host event)", pytest.approx(150e-9)]
    assert [g[0] for g in gaps[2:]] == ["(no host event)", "bench.sample"]


@pytest.mark.parametrize("metric,expected", [
    ("device_idle_share", 100 * (1 - 450 / 1000)),
    ("round_program_ms", 350 / 2 * 1e-6),
    ("eval_ms", 100 / 2 * 1e-6),
    ("sample_ms", 50 / 2 * 1e-6),
    ("round_mfu", 100 * 2 * 1e6 / (1e-6 * 4 * 1e15)),
])
def test_metric_readers(metric, expected):
    m = harness.Measured(tr.window_of(_trace()), flops_per_round=1e6,
                         chips=4, peak_flops=1e15)
    assert harness.load_reader(metric)(m) == pytest.approx(expected)


@pytest.mark.parametrize("metric", ["device_idle_share", "round_mfu",
                                    "round_program_ms", "eval_ms",
                                    "sample_ms"])
def test_readers_return_nothing_without_a_trace(metric):
    m = harness.Measured(None, flops_per_round=1e6, chips=1,
                         peak_flops=1e15)
    assert harness.load_reader(metric)(m) is None


def test_flops_gcn_by_hand():
    # GBG over 3 → hidden 4 → classes 2, on 10 rows reading 7 edges:
    # G0: 2·7·3 + 2·10·3·4 = 42 + 240; G2: 2·7·4 + 2·10·4·2 = 56 + 160
    assert flops.forward_flops("GBG", 3, 4, 2, rows=10, edges=7) == 498


def test_flops_sage_by_hand():
    # BSBL over 5 → hidden 3 → classes 2, on 4 rows reading 6 edges:
    # S1: 2·6·5 + 4·4·5·3 = 60 + 240; L3: 2·4·3·2 = 48
    assert flops.forward_flops("BSBL", 5, 3, 2, rows=4, edges=6) == 348


def test_round_flops_counts_local_correction_and_eval():
    fwd = lambda r, e: flops.forward_flops("GBG", 3, 4, 2, r, e)  # noqa
    got = flops.round_flops("GBG", 3, 4, 2, part_rows=[5, 6],
                            part_sampled_edges=[4, 3], num_nodes=11,
                            directed_edges=12, local_k=2,
                            correction_steps=1)
    assert got == (3 * 2 * (fwd(5, 4) + fwd(6, 3)) + 3 * fwd(11, 12)
                   + fwd(11, 12))


def test_flops_per_round_is_round_flops_of_the_configuration():
    """arxiv-gbgbg's own files, with the partition's counts as the
    reference reports them for eight machines."""
    cell = harness.load_cell("arxiv.llcg", harness.ROOT)
    ref = {"part_rows": [21168] * 7 + [21167],
           "part_sampled_edges": [180_001 + p for p in range(8)],
           "directed_edges": 2_334_382}
    got = flops.flops_per_round(cell.config, cell.traffic, ref)
    assert got == flops.round_flops(
        "GBGBG", 128, 256, 40, ref["part_rows"], ref["part_sampled_edges"],
        169_343, 2_334_382, local_k=4, correction_steps=1)
    assert flops.kernel_work(cell.config, cell.traffic, ref) == {}


def test_a_configuration_without_counts_is_refused():
    with pytest.raises(harness.BenchError, match="counts"):
        harness.load_counts({"name": "bare", "reference": "x.py"})


def _kernel(work, **kw):
    """Device 0 of ``_trace`` runs ``fusion.1`` 300 ns over 2 rounds:
    1.5e-7 s a round."""
    return harness.Measured(tr.window_of(_trace()), flops_per_round=1e6,
                            chips=1, peak_flops=1e15,
                            work={"k": dict({"pattern": r"^fusion\.1$"},
                                            **work)},
                            peak_hbm_bytes_per_s=1e12, **kw)


@pytest.mark.parametrize("work,expected", [
    # compute-bound: 1.2e8 / 1e15 = 1.2e-7 s over 1e3 / 1e12 = 1e-9 s
    ({"flops": 1.2e8, "bytes": 1e3}, 100 * 1.2e-7 / 1.5e-7),
    # memory-bound: 6e4 / 1e12 = 6e-8 s over 1e6 / 1e15 = 1e-9 s
    ({"flops": 1e6, "bytes": 6e4}, 100 * 6e-8 / 1.5e-7),
])
def test_kernel_share_by_hand(work, expected):
    assert kernel_share.share(_kernel(work), "k") == pytest.approx(expected)


def test_kernel_share_is_none_without_something_to_read():
    work = {"flops": 1e6, "bytes": 6e4}
    m = _kernel(work)
    assert kernel_share.share(m, "other") is None               # no label
    m.work["k"]["pattern"] = r"^edge_softmax"
    assert kernel_share.share(m, "k") is None                   # no match
    m = _kernel(work)
    m.window = None
    assert kernel_share.share(m, "k") is None                   # no trace
    m = _kernel(work)
    m.peak_hbm_bytes_per_s = None
    assert kernel_share.share(m, "k") is None                   # no peak
