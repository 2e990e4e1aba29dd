"""The arxiv-gat configuration on the CPU: one LLCG round of a small
arxiv-gat-shaped GAT through the harness against its plain reference, under
the cell's limits; the bfloat16 control and a planted fault reading over
them; its counts module by hand; and the readers this configuration adds,
on synthesised traces."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import checks  # noqa: E402
import faults  # noqa: E402
import flops  # noqa: E402
import harness  # noqa: E402
import tracereduce as tr  # noqa: E402
from tracereduce import Event, Line, Plane  # noqa: E402

ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
flops_gat = harness._load_named({"counts": "bench/flops_gat.py"}, "counts")


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """Runs here compile into no cache, and leave jax's cache settings as
    they found them."""
    import jax
    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    for k, v in old.items():
        jax.config.update(k, v)


@pytest.fixture(scope="module")
def cell():
    """arxiv-gat.llcg's model at 3 heads of 8 over a 600-node graph, two
    machines, under the cell's own limits."""
    real = harness.load_cell("arxiv-gat.llcg", ROOT)
    config = dict(real.config, name="tiny-gat")
    config["dataset"] = dict(config["dataset"], num_nodes=600,
                             num_classes=5, feature_dim=16)
    config["model"] = dict(config["model"], hidden_dim=8)
    traffic = dict(real.traffic, num_machines=2, local_k=2)
    return harness.Cell("tiny-gat", 1, config, traffic, real.limits,
                        real.end_to_end, real.per_layer)


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("bench_gat_cache"))


def _run(cell, cache_dir):
    return harness.run_cell(cell, 2**31 + 17, 0.2, False, 0.0,
                            require_tpu=False, cache_dir=cache_dir)


def test_gat_round_matches_its_reference(cell, cache_dir):
    res = _run(cell, cache_dir)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["checks"]["sample_mismatch"]["value"] == 0
    assert set(res["metrics"]) == {"round_s", "setup_s"}


def test_gat_bf16_control_is_not_correct(cell, cache_dir):
    arrays = harness.dataset_arrays(cell.config, cache_dir)
    ref = harness.run_reference(cell, arrays, 7)
    control = harness.run_reference(cell, arrays, 7, dtype="bfloat16")
    judged = checks.judge(checks.readings(control, ref), cell.limits)
    assert not checks.all_within(judged), judged


def test_gat_half_batch_is_not_correct(cell, cache_dir):
    with faults.half_batch():
        res = _run(cell, cache_dir)
    assert not res["correct"], res["checks"]


# ------------------------------------------------------------ hand counts
FIVE = {"dataset": {"num_nodes": 5, "num_classes": 2, "feature_dim": 3},
        "model": {"arch": "GAT", "hidden_dim": 4, "num_heads": 2,
                  "num_layers": 2, "residual": True, "self_loop": True,
                  "batch_norm": True, "fused_gat": True}}
TRAFFIC = {"local_k": 2, "correction_steps": 1}
# a five-node path 0-1-2-3-4 stored both ways (8 directed edges), two
# machines {0,1,2} and {3,4}: sampled local edges 4 and 2
FIVE_REF = {"part_rows": [3, 2], "part_sampled_edges": [4, 2],
            "directed_edges": 8}


def test_gat_flops_by_hand():
    # layer 0: 3 → 2·4 = 8 wide; layer 1: 8 → 2·2 = 4 wide.  Per forward
    # over r rows and e edges, slots s = e + r (the self slot):
    #   layer 0: 2·(2·r·3·8) + 2·2·r·8 + 2·s·8 = 96r + 32r + 16s
    #   layer 1: 2·(2·r·8·4) + 2·2·r·4 + 2·s·4 = 128r + 16r + 8s
    fwd = lambda r, e: 272 * r + 24 * (e + r)  # noqa: E731
    assert flops_gat.forward_flops(FIVE["model"], 3, 2, 5, 8) == fwd(5, 8)
    want = 3 * 2 * (fwd(3, 4) + fwd(2, 2)) + 3 * 1 * fwd(5, 8) + fwd(5, 8)
    assert flops_gat.flops_per_round(FIVE, TRAFFIC, FIVE_REF) == want


def test_gat_kernel_work_by_hand():
    # per forward: flops 2·s·(8 + 4); bytes 4·(s·(8+2+1) + r·8) at layer 0
    # and 4·(s·(4+2+1) + r·4) at layer 1
    work = lambda r, e: (24 * (e + r),  # noqa: E731
                         4 * (18 * (e + r) + 12 * r))
    calls = [(2, work(3, 4)), (2, work(2, 2)), (2, work(5, 8))]
    got = flops_gat.kernel_work(FIVE, TRAFFIC, FIVE_REF)
    assert got == {"gat_attention": {
        "pattern": r"^gat_attention",
        "flops": sum(n * f for n, (f, _) in calls),
        "bytes": sum(n * b for n, (_, b) in calls)}}
    plain = dict(FIVE, model=dict(FIVE["model"], fused_gat=False))
    assert flops_gat.kernel_work(plain, TRAFFIC, FIVE_REF) == {}


def test_arxiv_gat_counts_its_published_widths():
    cell = harness.load_cell("arxiv-gat.llcg", ROOT)
    assert flops_gat.layer_widths(cell.config["model"], 128, 40) == [
        (128, 750), (750, 750), (750, 120)]
    assert cell.config["reduced"] == []


# ---------------------------------------------------------------- readers
def _trace(kernel=True):
    """Window [1000, 2000) ns, 2 rounds.  Device 0 runs two calls of the
    attention kernel (100 + 60 ns) and a fusion."""
    host = Plane("/host:CPU", [Line("python", [
        Event(tr.WINDOW_START, 1000, 0), Event(tr.ROUND_END, 1500, 0),
        Event(tr.ROUND_END, 2000, 0)])])
    ops = [Event("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)", 1000, 50)]
    if kernel:
        ops += [Event("%gat_attention.1 = f32[64,6,128]{2,1,0} "
                      "custom-call(s32[64,17]{1,0} %t)", 1100, 100),
                Event("gat_attention.2", 1600, 60)]
    dev0 = Plane("/device:TPU:0", [Line(tr.OPS_LINE, ops)])
    return tr.window_of([host, dev0])


def _measured(window, chips=1):
    return harness.Measured(
        window, flops_per_round=1e6, chips=chips, peak_flops=1e15,
        work={"gat_attention": {"pattern": r"^gat_attention",
                                "flops": 1.2e5, "bytes": 6e4}},
        peak_hbm_bytes_per_s=1e12)


def test_gat_attention_readers_by_hand():
    m = _measured(_trace())
    # 160 ns over 2 rounds
    assert harness.load_reader("gat_attention_ms")(m) == pytest.approx(
        80e-6)
    # memory-bound: 6e4 B / 1e12 B/s = 6e-8 s over 8e-8 s a round
    assert harness.load_reader("gat_attention_roofline")(m) == \
        pytest.approx(75.0)


def test_mfu_reader_by_hand():
    m = _measured(_trace(), chips=4)
    # 2 rounds of 1e6 FLOPs over 1e-6 s × 4 chips × 1e15
    assert harness.load_reader("mfu")(m) == pytest.approx(
        100 * 2e6 / (1e-6 * 4 * 1e15))
    assert harness.load_reader("mfu")(m) == \
        harness.load_reader("round_mfu")(m)


def test_new_readers_are_none_where_their_ops_are_absent():
    m = _measured(_trace(kernel=False))
    for name in ("gat_attention_ms", "gat_attention_roofline"):
        assert harness.load_reader(name)(m) is None, name
    m.work = {}
    assert harness.load_reader("gat_attention_ms")(m) is None
    none = _measured(None)
    for name in ("gat_attention_ms", "gat_attention_roofline", "mfu"):
        assert harness.load_reader(name)(none) is None, name


def test_new_cells_name_their_files():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"]: w for w in spec["workloads"]}
    assert cells["arxiv-gat.llcg"]["chips"] == 1
    gat = harness.load_cell("arxiv-gat.llcg", ROOT)
    assert {m["name"] for m in gat.per_layer} >= {
        "gat_attention_ms", "gat_attention_roofline", "mfu"}
    assert harness.load_counts(gat.config).kernel_work is not None
    gcn = harness.load_cell("arxiv.llcg", ROOT)
    assert flops.kernel_work(gcn.config, gcn.traffic, {}) == {}
