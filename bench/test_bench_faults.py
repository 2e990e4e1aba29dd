"""A whole benchmark run on the CPU at a tiny size: the sound program reads
as correct under the limits of ``arxiv.llcg``, and each planted fault, and
the reference computed in bfloat16 in the program's place, reads as not
correct.  The measurement path itself refuses a backend other than TPU."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import checks  # noqa: E402
import faults  # noqa: E402
import harness  # noqa: E402

ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))


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
def cell(tmp_path_factory):
    """arxiv.llcg's plan and limits over a 600-node graph, two machines."""
    real = harness.load_cell("arxiv.llcg", ROOT)
    config = dict(real.config, name="tiny")
    config["dataset"] = dict(config["dataset"], num_nodes=600,
                             num_classes=5, feature_dim=16)
    config["model"] = dict(config["model"], hidden_dim=16,
                           agg_layout="padded")
    traffic = dict(real.traffic, num_machines=2, local_k=2)
    return harness.Cell("tiny", 1, config, traffic, real.limits,
                        real.end_to_end, real.per_layer)


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("bench_cache"))


def _run(cell, cache_dir, trace=False):
    return harness.run_cell(cell, 2**31 + 11, 0.2, trace, 0.0,
                            require_tpu=False, cache_dir=cache_dir)


@pytest.fixture
def built_models(monkeypatch):
    """The keywords of every ``build_model`` call and the model built."""
    import repro.models.gnn as gnn
    real, calls = gnn.build_model, []

    def spy(*args, **kw):
        calls.append((kw, real(*args, **kw)))
        return calls[-1][1]

    monkeypatch.setattr(gnn, "build_model", spy)
    return calls


def _train(cell, cache_dir):
    return harness.run_program(cell, harness.chips_for(cell, False),
                               2**31 + 13, 0.2, 0.0, harness.CompileMeter(),
                               cache_dir=cache_dir)


def test_sound_run_is_correct(cell, cache_dir):
    res = _run(cell, cache_dir)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"round_s", "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["checks"]["sample_mismatch"]["value"] == 0


def test_traced_run_reports_per_layer_metrics_only(cell, cache_dir):
    res = _run(cell, cache_dir, trace=True)
    assert res["correct"], res["checks"]
    assert not set(res["metrics"]) & {"round_s", "setup_s"}


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_is_not_correct(cell, cache_dir, fault):
    with faults.FAULTS[fault]():
        res = _run(cell, cache_dir)
    assert not res["correct"], res["checks"]


def test_lower_precision_control_is_not_correct(cell, cache_dir):
    arrays = harness.dataset_arrays(cell.config, cache_dir)
    ref = harness.run_reference(cell, arrays, 5)
    control = harness.run_reference(cell, arrays, 5, dtype="bfloat16")
    judged = checks.judge(checks.readings(control, ref), cell.limits)
    assert not checks.all_within(judged), judged


def test_partition_ignores_the_run_seed(cell, cache_dir):
    """Every run seed trains on the traffic's partition, so that a new seed
    meets no new shape and compiles nothing."""
    import repro.core.plan as plan_mod
    arrays = harness.dataset_arrays(cell.config, cache_dir)
    graph = harness.program_dataset(arrays, cell.config).graph
    real = plan_mod.partition_graph

    def parts(seeds):
        return [plan_mod.partition_graph(graph, 2, method="bfs",
                                         seed=s).assignment for s in seeds]

    a, b = parts([1, 2**31 + 3])
    assert (a != b).any()
    with harness.fixed_partition(cell.traffic["partition_seed"]):
        a, b = parts([1, 2**31 + 3])
    assert (a == b).all()
    assert plan_mod.partition_graph is real


def test_model_block_reaches_the_model(cell, cache_dir, built_models):
    trained = _train(cell, cache_dir)
    assert trained.window_rounds >= 1
    [(kw, model)] = built_models
    assert kw == {"hidden_dim": 16, "agg_layout": "padded"}
    assert (model.hidden_dim, model.agg_layout) == (16, "padded")


def test_unknown_model_key_is_refused_by_name(cell, cache_dir):
    config = dict(cell.config, model=dict(cell.config["model"], heads=3))
    bad = harness.Cell("tiny", 1, config, cell.traffic, cell.limits,
                       cell.end_to_end, cell.per_layer)
    with pytest.raises(harness.BenchError, match="heads"):
        _train(bad, cache_dir)


GAT_COUNTS = """
def flops_per_round(config, traffic, ref):
    ds, m = config["dataset"], config["model"]
    return (2 * ds["num_nodes"] * m["hidden_dim"] * traffic["local_k"]
            + ref["directed_edges"])


def kernel_work(config, traffic, ref):
    return {"edge_softmax": {"pattern": r"^edge_softmax",
                             "flops": 5 * ref["directed_edges"],
                             "bytes": 8 * ref["directed_edges"]}}
"""


def test_gat_configuration_needs_new_files_only(cell, cache_dir, tmp_path,
                                                built_models):
    """An operator outside G/S/L/B: its model block reaches the model, it
    trains through the program's entry point, and its work is what its own
    counts module says, in what a metric reader is given."""
    (tmp_path / "gat_counts.py").write_text(GAT_COUNTS)
    config = dict(cell.config, name="tiny-gat",
                  model={"arch": "GAT", "hidden_dim": 8, "fused_gat": False},
                  counts=str(tmp_path / "gat_counts.py"))
    gat = harness.Cell("tiny-gat", 1, config, cell.traffic, cell.limits,
                       cell.end_to_end, cell.per_layer)
    trained = _train(gat, cache_dir)
    assert trained.window_rounds >= 1 and trained.failed == 0
    [(kw, model)] = built_models
    assert kw == {"hidden_dim": 8, "fused_gat": False}
    assert (model.arch, model.fused_gat) == ("GAT", False)

    edges = len(trained.arrays["indices"])
    m = harness.count_work(gat, {"directed_edges": edges}, 1,
                           harness.peak_of("TPU v5e"))
    assert m.flops_per_round == 2 * 600 * 8 * 2 + edges
    assert m.work == {"edge_softmax": {"pattern": r"^edge_softmax",
                                       "flops": 5 * edges,
                                       "bytes": 8 * edges}}
    assert (m.config, m.traffic, m.chips) == (config, cell.traffic, 1)
    assert (m.peak_flops, m.peak_hbm_bytes_per_s) == (197e12, 819e9)
    assert m.window is None


def test_measurement_path_refuses_cpu(capsys, monkeypatch):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    # run.main sets the cache directory in the environment: restore it
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    rc = run.main(["--workload", "arxiv.llcg", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_benchmark_file_names_every_file(capsys):
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in spec["workloads"]:
        c = harness.load_cell(w["name"], ROOT)
        assert c.limits and c.traffic["checked_rounds"] >= 1
    for m in spec["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    for c in spec["configs"]:
        config = json.load(open(os.path.join(ROOT, c["file"])))
        assert config["counts"].startswith("bench/")
        counts = harness.load_counts(config)
        assert callable(counts.flops_per_round)
        assert callable(counts.kernel_work)
