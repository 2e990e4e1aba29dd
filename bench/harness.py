"""One run of one benchmark cell, driven by the files that name it.

A cell of ``BENCHMARK.json`` names a configuration (``configs[].file``: the
dataset, the model, the precision, the plain reference and the module that
counts the round's work), a traffic mix
(``bench/traffic/<traffic>.json``: the training plan, its backend and its
warm-up) and has a file of its own (``bench/workloads/<cell>.json``: the
limits of the numbers that decide ``correct``).  Per-layer metrics are
read by ``bench/metrics/<metric>.py``.  Nothing here names a cell.

A run trains through the program's entry point,
``build_trainer(data, model, plan, backend=..., mesh=...).run()``, in one
call: the first ``warmup_rounds`` rounds are set-up (they compile, and the
first ``checked_rounds`` of them are compared with the reference), then the
measured window runs whole rounds until ``--seconds`` have passed.  The
harness reaches into the run through the program's own per-round hook
(``run_schedule``'s ``checkpoint_hook``), which it passes by wrapping
``repro.core.plan.run_schedule`` for the length of the call; the hook ends
the call when the window closes.  The plain reference runs after the
window, once the program's state is freed.  ``--seed`` seeds the weights,
the sampling and the correction batches; the graph and its partition are
fixed by the configuration and the traffic (:func:`fixed_partition`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import hashlib
import importlib.util
import inspect
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

import checks
import tracereduce
from sbm import sbm_graph

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")
#: rounds a run may schedule past its warm-up; the window closes long before
ROUND_CAP = 20_000
#: Adam's first-moment decay in the program's optimizer: the first
#: correction gradient is the server's first moment after one step / (1 − b1)
ADAM_B1 = 0.9


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class BenchError(RuntimeError):
    """The benchmark's files or the run broke a rule of the harness."""


class WindowClosed(Exception):
    """Raised by the hook to end the training call when the window closes."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict[str, float]
    end_to_end: List[Dict]
    per_layer: List[Dict]


def use_checkout_cache(root: str = ROOT) -> None:
    """Put JAX's persistent compile cache at ``<root>/.jax_cache``, whatever
    the environment names: a fixed path inside the checkout, so that only a
    checkout's first run of a cell compiles and two checkouts share nothing.
    The program's ``enable_compilation_cache`` takes the directory from this
    variable, which JAX reads once, on import: call it before that."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")


def _read_json(path: str) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise BenchError(f"missing benchmark file {path}") from e


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload`` with every file it names."""
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench = os.path.join(root, "bench")
    mine = lambda ms: [m for m in ms  # noqa: E731
                       if workload in m.get("workloads", [workload])]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_read_json(os.path.join(root, conf["file"])),
        traffic=_read_json(os.path.join(bench, "traffic",
                                        w["traffic"] + ".json")),
        limits=_read_json(os.path.join(bench, "workloads",
                                       workload + ".json"))["limits"],
        end_to_end=mine(spec["end_to_end"]),
        per_layer=mine(spec["per_layer"]))


# ------------------------------------------------------------------ data
def dataset_arrays(config: Dict, cache_dir: str = CACHE_DIR) -> Dict:
    """The configuration's graph, built once per checkout and then read
    from a file keyed by the dataset block's content."""
    ds = dict(config["dataset"])
    if ds.pop("generator") != "sbm":
        raise BenchError("only the sbm generator is known")
    key = hashlib.sha256(json.dumps(config["dataset"], sort_keys=True)
                         .encode()).hexdigest()[:16]
    path = os.path.join(cache_dir, f"{config['name']}-{key}.npz")
    if os.path.exists(path):
        with np.load(path) as z:
            return {k: z[k] for k in z.files}
    arrays = sbm_graph(**ds)
    os.makedirs(cache_dir, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp.npz"
    np.savez(tmp, **arrays)
    os.replace(tmp, path)
    return arrays


def program_dataset(arrays: Dict, config: Dict):
    from repro.graph.csr import CSRGraph
    from repro.graph.datasets import SyntheticDataset
    n = len(arrays["indptr"]) - 1
    return SyntheticDataset(
        graph=CSRGraph(indptr=arrays["indptr"], indices=arrays["indices"],
                       num_nodes=n),
        features=arrays["features"], labels=arrays["labels"],
        train_nodes=arrays["train_nodes"], val_nodes=arrays["val_nodes"],
        test_nodes=arrays["test_nodes"],
        num_classes=config["dataset"]["num_classes"], name=config["name"])


@contextlib.contextmanager
def fixed_partition(seed: int):
    """The program partitions the graph with ``seed`` whatever the plan's
    seed.  The partition belongs to the deployment, as the graph does: every
    run of a cell then trains on machines of the same shapes, and finds each
    of its programs, the device sampler's too, in the compile cache."""
    import repro.core.plan as plan_mod
    real = plan_mod.partition_graph

    def partition(graph, num_parts, method="bfs", **_):
        return real(graph, num_parts, method=method, seed=seed)

    plan_mod.partition_graph = partition
    try:
        yield
    finally:
        plan_mod.partition_graph = real


def program_plan(traffic: Dict, seed: int, rounds: int):
    """The traffic's plan: ``repro.core.plan.<algorithm>_plan`` over its
    sizes, with the round draw's placement and the averaging codec."""
    import repro.core.plan as P
    make = getattr(P, f"{traffic['algorithm']}_plan")
    plan = make(P.DistConfig(
        num_machines=traffic["num_machines"], rounds=rounds,
        local_k=traffic["local_k"],
        correction_steps=traffic["correction_steps"],
        batch_size=traffic["batch_size"],
        server_batch_size=traffic["server_batch_size"],
        fanout=traffic["fanout"], lr=traffic["lr"],
        partition_method=traffic["partition"], seed=seed))
    return dataclasses.replace(
        plan,
        sampler=dataclasses.replace(plan.sampler,
                                    placement=traffic["sampler_placement"]),
        comm=dataclasses.replace(plan.comm,
                                 compression=traffic["compression"]))


# ------------------------------------------------------------ measuring
class CompileMeter:
    """Backend compile seconds and count, and persistent-cache hits and
    misses, as reported through :mod:`jax.monitoring`."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> Dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


def _host(tree):
    import jax
    return jax.tree_util.tree_map(lambda x: np.asarray(x), tree)


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class _AnnotatedProgram:
    """The engine program, each round's dispatch inside a host span."""

    def __init__(self, program):
        self._program = program

    def __getattr__(self, attr):
        return getattr(self._program, attr)

    def run_round(self, *args):
        with _annotate("bench.run_round"):
            return self._program.run_round(*args)


class RunTap:
    """The per-round hook ``run_schedule`` drives, and the window it times.

    ``after_round(r)`` runs right after round r is dispatched and
    ``commit(r)`` once its evaluation has blocked.  Rounds up to
    ``checked`` leave their inputs, the server's first optimizer moment and
    the last weights behind for the comparison; round ``warmup`` ends the
    set-up; every later round ends inside the window, and the first to end
    ``seconds`` after it started closes it.
    """

    def __init__(self, checked: int, warmup: int, seconds: float,
                 meter: CompileMeter, trace_dir: Optional[str] = None,
                 mesh=None):
        if warmup < checked:
            raise BenchError("warmup_rounds must cover checked_rounds")
        self.checked, self.warmup, self.seconds = checked, warmup, seconds
        self.meter, self.trace_dir, self.mesh = meter, trace_dir, mesh
        self.program = None
        self.params0 = self.grad1 = self.params_last = None
        self.inputs: Dict[int, Any] = {}
        self.samples: Dict[int, Dict] = {}
        self.slices_off_chip = 0
        self.t_start = self.t_end = None
        self.window_rounds = 0
        self.hist = None
        self.counters: Dict = {}
        self._server1 = self._last = self._first_inputs = None
        self._evaluate = None
        self.probes: Dict[str, float] = {}

    @contextlib.contextmanager
    def installed(self):
        """Pass this hook into ``run_schedule`` for the length of a run."""
        import repro.core.plan as plan_mod
        original = plan_mod.run_schedule
        signature = inspect.signature(original)

        def tapped(*args, **kwargs):
            bound = signature.bind(*args, **kwargs)
            a = bound.arguments
            if a.get("checkpoint_hook") is not None:
                raise BenchError("the benchmark's plans take no checkpoint")
            self.program = a["program"]
            self.params0 = _host(a["init_params"])
            self._params0_device = a["init_params"]
            self._evaluate = a["evaluate"]
            a["program"] = _AnnotatedProgram(self.program)
            a["sample_fn"] = self._sampled(a["sample_fn"])
            a["evaluate"] = self._evaluated(a["evaluate"])
            a["checkpoint_hook"] = self
            return original(*bound.args, **bound.kwargs)

        plan_mod.run_schedule = tapped
        try:
            yield self
        finally:
            plan_mod.run_schedule = original
            self.program = self._evaluate = self._params0_device = None

    def _sampled(self, fn):
        def sample(r, k):
            with _annotate("bench.sample"):
                inputs = fn(r, k)
            if r <= self.checked:
                self.inputs[r] = inputs
            return inputs
        return sample

    def _evaluated(self, fn):
        def evaluate(params):
            with _annotate("bench.evaluate"):
                return fn(params)
        return evaluate

    def after_round(self, r: int, state) -> None:
        if r == 1:
            self._server1 = self.program.snapshot_state(state)["server"]
        if r == self.checked:
            self._last = state.params

    def _off_chip(self, arrays) -> int:
        """Machine slices that are not on their own chip of the mesh."""
        devices = list(self.mesh.devices.flat)
        bad = 0
        for arr in arrays:
            seen = set()
            for shard in arr.addressable_shards:
                p = shard.index[0].start or 0
                width = (shard.index[0].stop or arr.shape[0]) - p
                seen.add(p)
                bad += int(width != 1 or shard.device != devices[p])
            bad += len(devices) - len(seen)
        return bad

    def commit(self, r: int, state, hist) -> None:
        if r <= self.checked:
            inp = self.inputs.pop(r)
            if r == 1:
                self._first_inputs = inp
            if self.mesh is not None and r == 1:
                sampler = self.program.sampler
                self.slices_off_chip = self._off_chip(
                    [inp.tables, inp.masks, inp.batches, sampler.feats_j,
                     sampler.labels_j])
            self.samples[r] = {
                "tables": np.asarray(inp.tables),
                "masks": np.asarray(inp.masks),
                "batches": np.asarray(inp.batches),
                "corr_batches": (np.asarray(inp.corr_batches)
                                 if inp.corr_batches is not None
                                 else np.zeros((0,), np.int32))}
        if r == 1 and self._server1 is not None:
            self.grad1 = {layer: {k: np.asarray(v) / (1.0 - ADAM_B1)
                                  for k, v in sub.items()}
                          for layer, sub in self._server1.mu.items()}
            self._server1 = None
        if r == self.checked:
            self.params_last = _host(self._last)
            self._last = None
        if r == self.warmup:
            self._compiles0 = self.meter.snapshot()["compiles"]
            if self.trace_dir is not None:
                _start_trace(self.trace_dir)
            with _annotate(tracereduce.WINDOW_START):
                pass
            self.t_start = time.perf_counter()
        elif r > self.warmup:
            with _annotate(tracereduce.ROUND_END):
                pass
            self.window_rounds += 1
            now = time.perf_counter()
            if now - self.t_start >= self.seconds:
                self.t_end = now
                if self.trace_dir is not None:
                    import jax
                    jax.profiler.stop_trace()
                self.hist = hist
                prog = self.program
                self.counters = {
                    "num_retraces": prog.num_retraces,
                    "num_corr_retraces": prog.num_corr_retraces,
                    "sampler_retraces": prog.sampler.num_sampler_retraces,
                    "window_compiles": (self.meter.snapshot()["compiles"]
                                        - self._compiles0)}
                self.probes = self._probe(state)
                self.counters["probe_compiles"] = (
                    self.meter.snapshot()["compiles"] - self._compiles0
                    - self.counters["window_compiles"])
                raise WindowClosed

    def _probe(self, state) -> Dict[str, float]:
        """Losses at the initial weights, each read before any optimizer
        step, through the window's own compiled programs, so that round-off
        which Adam's first steps amplify does not reach them:
        ``first_loss``, the first local step's, mean over machines;
        ``first_corr_loss``, the correction's; ``first_eval_loss``, the
        evaluation's.  The first two run round 1 again from the initial
        weights with round 1's inputs, with every local step after the first
        masked, and then with every local step masked, so that the averaged
        weights the correction starts from are the initial ones (the
        program's K-bucketing flag: a masked step neither updates nor
        counts)."""
        import jax
        from repro.core.engine import EngineState
        inp = self._first_inputs
        self._first_inputs = None
        valid = (np.ones((inp.tables.shape[1],), np.float32)
                 if inp.step_valid is None else np.asarray(inp.step_valid))

        def rerun(steps: np.ndarray) -> Dict:
            probe = dataclasses.replace(
                inp, step_valid=jax.device_put(steps * valid))
            # round 1's own initial weights, placed as round 1 had them, so
            # the probe reuses round 1's compiled programs
            _, metrics = self.program.run_round(
                EngineState(params=self._params0_device,
                            local_opt_state=state.local_opt_state),
                None, None, probe)
            return metrics

        first = np.zeros_like(valid)
        first[0] = 1.0
        return {"first_loss": float(rerun(first)["local_loss"]),
                "first_corr_loss": float(
                    rerun(np.zeros_like(valid))["corr_loss"]),
                "first_eval_loss": float(
                    self._evaluate(self._params0_device)[0])}

    def program_summary(self) -> Dict:
        """The program's side of the comparison, as the reference's."""
        h, c = self.hist, self.checked
        corr = dict(zip(h.meta["corr_rounds"], h.meta["corr_loss"]))
        return {"local_loss": h.meta["local_loss"][:c],
                "corr_loss": [corr.get(r, float("nan"))
                              for r in range(1, c + 1)],
                "eval_loss": h.train_loss[:c], "val_score": h.val_score[:c],
                **{k: [self.samples[r][k] for r in range(1, c + 1)]
                   for k in ("tables", "masks", "batches", "corr_batches")},
                **self.probes, "grad1": self.grad1, "params0": self.params0,
                "params_last": self.params_last}


def _start_trace(log_dir: str) -> None:
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


# --------------------------------------------------------------- result
@dataclasses.dataclass
class Measured:
    """What a per-layer metric reader is given: the traced window, the
    round's model FLOPs and per-kernel work from the configuration's counts
    module (``work``: ``{label: {"pattern", "flops", "bytes"}}``, per round
    and chip), the chips, and each chip's peaks; ``config`` and ``traffic``
    are the cell's files."""

    window: Optional[tracereduce.Window]
    flops_per_round: Optional[float]
    chips: int
    peak_flops: Optional[float]
    config: Dict = dataclasses.field(default_factory=dict)
    traffic: Dict = dataclasses.field(default_factory=dict)
    work: Dict[str, Dict] = dataclasses.field(default_factory=dict)
    peak_hbm_bytes_per_s: Optional[float] = None

    @staticmethod
    def first_device(w: tracereduce.Window) -> int:
        return min(set(w.ops) | set(w.modules))


def load_reader(metric: str):
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _load_named(config: Dict, key: str):
    """The module at the path the configuration gives under ``key``."""
    if key not in config:
        raise BenchError(f"configuration {config.get('name')!r} names no "
                         f"{key!r} module")
    path = os.path.join(ROOT, config[key])
    spec = importlib.util.spec_from_file_location(
        f"bench_{key}_" + os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(config: Dict):
    return _load_named(config, "reference")


def load_counts(config: Dict):
    """The configuration's counts module: ``flops_per_round(config,
    traffic, ref)`` and ``kernel_work(config, traffic, ref)``."""
    return _load_named(config, "counts")


def peak_of(kind: str) -> Dict:
    table = _read_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def chips_for(cell: Cell, require_tpu: bool = True) -> List:
    """The devices the cell runs on; raises :class:`NoAccelerator` where
    JAX has no TPU (unless ``require_tpu`` is off, as in the benchmark's
    own CPU tests) or fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devices[0].platform!r}")
    if len(devices) < cell.chips:
        raise NoAccelerator(f"the cell needs {cell.chips} chips, JAX found "
                            f"{len(devices)}")
    return devices[:cell.chips]


@dataclasses.dataclass
class Trained:
    """What a run of the program leaves for the rest of the harness."""

    arrays: Dict
    summary: Dict
    window_s: float
    window_rounds: int
    setup_s: float
    failed: int
    memory_peak_bytes: int
    slices_off_chip: Optional[int]


def model_options(model: Dict) -> Dict:
    """The configuration's model block, but ``arch``, as ``build_model``'s
    keywords; a key that ``GNNModel`` does not take is refused by name.
    The data gives the feature and class counts."""
    from repro.models.gnn.model import GNNModel
    taken = ({f.name for f in dataclasses.fields(GNNModel)}
             - {"arch", "feature_dim", "num_classes"})
    options = {k: v for k, v in model.items() if k != "arch"}
    unknown = sorted(set(options) - taken)
    if unknown:
        raise BenchError(f"model key(s) {unknown} are not GNNModel's; it "
                         f"takes {sorted(taken)}")
    return options


def run_program(cell: Cell, used: List, seed: int, seconds: float,
                t_process: float, meter: CompileMeter,
                trace_dir: Optional[str] = None,
                cache_dir: str = CACHE_DIR) -> Trained:
    """Set-up, warm-up and the measured window, in one ``run()`` call."""
    from repro.core.plan import build_trainer
    from repro.models.gnn import build_model

    tr, cfg = cell.traffic, cell.config
    options = model_options(cfg["model"])
    t_data = time.perf_counter()
    arrays = dataset_arrays(cfg, cache_dir)
    data = program_dataset(arrays, cfg)
    model = build_model(cfg["model"]["arch"], data.feature_dim,
                        data.num_classes, **options)
    mesh = None
    if tr["backend"] == "shard_map":
        from jax.sharding import Mesh
        if tr["num_machines"] != len(used):
            raise BenchError("shard_map runs one machine per chip")
        mesh = Mesh(np.asarray(used), ("machine",))
    plan = program_plan(tr, seed, tr["warmup_rounds"] + ROUND_CAP)
    t_build = time.perf_counter()
    trainer = build_trainer(data, model, plan, backend=tr["backend"],
                            mesh=mesh)
    tap = RunTap(tr["checked_rounds"], tr["warmup_rounds"], seconds, meter,
                 trace_dir=trace_dir, mesh=mesh)
    import jax
    t_run = time.perf_counter()
    try:
        with tap.installed(), fixed_partition(tr["partition_seed"]), \
                jax.default_matmul_precision(cfg["matmul_precision"]):
            trainer.run()
        raise BenchError(f"{ROUND_CAP} rounds ended before the window")
    except WindowClosed:
        pass
    run_wall = time.perf_counter() - t_run
    window_s = tap.t_end - tap.t_start
    hist = tap.hist
    w0, w1 = tr["warmup_rounds"], tr["warmup_rounds"] + tap.window_rounds
    span_sum = float(sum(hist.meta["round_seconds"][w0:w1]))
    if not span_sum <= window_s <= run_wall:
        raise BenchError(f"window clock disagrees: rounds sum {span_sum}, "
                         f"window {window_s}, run() {run_wall}")
    failed = sum(1 for a, b in zip(hist.meta["local_loss"][w0:w1],
                                   hist.train_loss[w0:w1])
                 if not (np.isfinite(a) and np.isfinite(b)))
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    out = Trained(arrays=arrays, summary=tap.program_summary(),
                  window_s=window_s, window_rounds=w1 - w0,
                  setup_s=tap.t_start - t_process, failed=failed,
                  memory_peak_bytes=peak,
                  slices_off_chip=(tap.slices_off_chip if mesh is not None
                                   else None))
    log(f"[bench] window rounds={w1 - w0} window_s={window_s:.6f} "
        f"round_s={window_s / (w1 - w0):.6f} rounds_sum_s={span_sum:.6f} "
        f"run_s={run_wall:.3f} setup_s={out.setup_s:.3f} "
        f"memory_peak_bytes={peak} val_score={hist.val_score[w1 - 1]:.4f}")
    warm_s = float(sum(hist.meta["round_seconds"][:w0]))
    log(f"[bench] setup imports_s={t_data - t_process:.3f} "
        f"data_s={t_build - t_data:.3f} build_s={t_run - t_build:.3f} "
        f"run_setup_s={tap.t_start - t_run - warm_s:.3f} "
        f"warmup_rounds_s={warm_s:.3f}")
    log("[bench] window round_seconds " + " ".join(
        f"{x:.4f}" for x in hist.meta["round_seconds"][:w1]))
    log("[bench] counters " + " ".join(
        f"{k}={v}" for k, v in dict(tap.counters, **meter.snapshot()).items()))
    del trainer, tap, hist
    gc.collect()
    return out


def run_reference(cell: Cell, arrays: Dict, seed: int, **kw) -> Dict:
    """The configuration's plain reference through the checked rounds."""
    cfg, tr = cell.config, cell.traffic
    t = time.perf_counter()
    ref = load_reference(cfg).run_reference(
        {"indptr": arrays["indptr"], "indices": arrays["indices"]},
        dict(arrays, num_classes=cfg["dataset"]["num_classes"]),
        cfg["model"], tr, seed, rounds=tr["checked_rounds"], **kw)
    log(f"[bench] reference_s={time.perf_counter() - t:.3f}")
    return ref


def compared(trained: Trained, ref: Dict) -> Dict[str, float]:
    values = checks.readings(trained.summary, ref)
    if trained.slices_off_chip is not None:
        values["slices_off_chip"] = float(trained.slices_off_chip)
    for key in ("local_loss", "corr_loss", "eval_loss"):
        log(f"[bench] {key} program={trained.summary[key]} "
            f"reference={ref[key]}")
    log("[bench] readings " + " ".join(f"{k}={v:.6g}"
                                       for k, v in values.items()))
    return values


def count_work(cell: Cell, ref: Dict, chips: int,
               peak: Optional[Dict]) -> Measured:
    """The round's work as the configuration's counts module gives it, in
    a :class:`Measured` that still lacks its window."""
    counts = load_counts(cell.config)
    args = (cell.config, cell.traffic, ref)
    per_round = counts.flops_per_round(*args)
    work = counts.kernel_work(*args)
    for label, w in work.items():
        if set(w) != {"pattern", "flops", "bytes"}:
            raise BenchError(f"kernel_work[{label!r}] needs pattern, flops "
                             f"and bytes; has {sorted(w)}")
    log(f"[bench] flops_per_round={per_round}")
    return Measured(None, float(per_round), chips,
                    peak["bf16_flops_per_s"] if peak else None,
                    config=cell.config, traffic=cell.traffic, work=work,
                    peak_hbm_bytes_per_s=(peak["hbm_bytes_per_s"] if peak
                                          else None))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, require_tpu: bool = True,
             cache_dir: str = CACHE_DIR) -> Dict:
    """One run of ``cell``; returns the result line's object."""
    from repro.core.plan import enable_compilation_cache

    used = chips_for(cell, require_tpu)
    peak = peak_of(used[0].device_kind) if require_tpu else None
    cache = enable_compilation_cache()
    meter = CompileMeter()
    log(f"[bench] cell={cell.name} seed={seed} seconds={seconds} "
        f"trace={int(trace)} device={used[0].device_kind} chips={len(used)} "
        f"compilation_cache={cache}")
    trace_dir = (os.path.join(cache_dir, "trace", cell.name) if trace
                 else None)
    trained = run_program(cell, used, seed, seconds, t_process, meter,
                          trace_dir=trace_dir, cache_dir=cache_dir)
    ref = run_reference(cell, trained.arrays, seed)
    judged = checks.judge(compared(trained, ref), cell.limits)
    counted = count_work(cell, ref, len(used), peak)
    import jax
    result: Dict[str, Any] = {
        "correct": checks.all_within(judged),
        "attempted": trained.window_rounds, "failed": trained.failed,
        "metrics": {},
        "device": {"platform": used[0].platform,
                   "kind": used[0].device_kind, "count": len(jax.devices()),
                   "memory_peak_bytes": trained.memory_peak_bytes}}
    if not trace:
        e2e = {"round_s": trained.window_s / trained.window_rounds,
               "setup_s": trained.setup_s}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    else:
        t_read = time.perf_counter()
        planes = tracereduce.load_trace(trace_dir)
        log("[bench] trace planes " + "; ".join(
            f"{p.name}: " + ", ".join(f"{ln.name}={len(ln.events)}"
                                      for ln in p.lines[:8])
            for p in planes))
        window = tracereduce.window_of(planes)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"[bench] trace_read_s={time.perf_counter() - t_read:.3f}")
        if window is not None and not (window.ops or window.modules):
            window = None       # no device in the trace (the CPU backend)
        measured = dataclasses.replace(counted, window=window)
        for m in cell.per_layer:
            v = load_reader(m["name"])(measured)
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
        if window is not None:
            devs = sorted(set(window.ops) | set(window.modules))
            result["device"]["busy_s"] = 1e-9 * float(np.mean(
                [tracereduce.device_busy_ns(window, x) for x in devs]))
            result["device"]["window_s"] = window.seconds
            result["breakdown"] = {
                "device_ops": tracereduce.top_ops(window, devs[0]),
                "idle_gaps": tracereduce.idle_gaps(window, devs[0])}
    result["checks"] = judged
    for k, c in judged.items():
        log(f"check {k} = {c['value']!r} limit {c['limit']!r}")
    return result
