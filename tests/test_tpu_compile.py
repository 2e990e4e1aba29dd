"""The main path's Pallas kernels and LLCG round program, compiled for a TPU
v5e chip that is described, not attached: nothing runs, but the chip's
compiler must accept every program at the widths ``chip_smoke.py`` runs.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine import EngineConfig, RoundProgram
from repro.core.machine import make_loss_fn
from repro.graph.datasets import rmat_graph
from repro.graph.sampling import DeviceCSR, sample_round_device
from repro.kernels.edge_softmax import block_rows, gat_attention, lane_rows
from repro.kernels.linear_scan import linear_scan_chunked
from repro.kernels.quantize import dequantize_rows, quantize_rows
from repro.kernels.spmm import spmm_bcsr
from repro.models.gnn import build_model
from repro.models.gnn.agg import bucketed_operands
from repro.optim import adam

# ogbn-arxiv widths (see chip_smoke.py): 128-d features, 40 classes, GBGBG
# at hidden 256, P=8 machines, K=4, fanout 10, batch 32
FEAT, CLASSES, HIDDEN, P, K, FANOUT, BATCH = 128, 40, 256, 8, 4, 10, 32
# nodes per machine, cut from arxiv's 21,168 so the round compiles in
# seconds; the node count is a scale, not a width
NODES = 2048
# the arxiv graph's largest degree (the sampler's key width)
DMAX = 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off around them
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, s):
    """(function, abstract args) of one kernel at the chip run's widths."""
    i8, i32 = jnp.int8, jnp.int32
    if name.startswith("quantize") or name.startswith("dequantize"):
        rows, cols = name.split("-")[1].split("x")
        shape = (P, int(rows) * int(cols))   # one leaf, flattened per machine
        if name.startswith("quantize"):
            return (lambda x, u: quantize_rows(x, u, interpret=False,
                                               block_r=8),
                    (_sds(s, shape), _sds(s, shape)))
        return (lambda q, sc: dequantize_rows(q, sc, interpret=False,
                                              block_r=8),
                (_sds(s, shape, i8), _sds(s, (P, 1))))
    if name == "spmm":                       # 8,192-node slice, d=256
        return (lambda c, v, h: spmm_bcsr(c, v, h, interpret=False,
                                          block_d=128),
                (_sds(s, (1024, 16), i32), _sds(s, (1024, 16, 8, 128)),
                 _sds(s, (8192, HIDDEN))))
    if name == "edge_softmax":             # GAT: 3 heads of 250, 16-wide
        heads, width, sub = 3, 17, lane_rows(750)   # bucket + the self slot
        rows = block_rows(width, sub)
        return (lambda t, a, z: gat_attention(
                    t, a, z, heads=heads, head_dim=250, block_rows=rows,
                    interpret=False),
                (_sds(s, (64 * rows, width), i32),
                 _sds(s, (64 * rows, width * heads)),
                 _sds(s, (NODES, sub, 128))))
    strict = name == "linear_scan_strict"    # rwkv6-1.6b: 32 heads of 64
    bh, t, d = 64, 256, 64
    return (lambda q, k, v, w, h, u: linear_scan_chunked(
                q, k, v, w, h, u, interpret=False, chunk=64, strict=strict),
            tuple(_sds(s, (bh, t, d)) for _ in range(4))
            + (_sds(s, (bh, d, d)), _sds(s, (bh, d))))


@pytest.mark.parametrize("name", [
    "quantize-128x256", "quantize-256x256", "quantize-256x40",
    "dequantize-128x256", "dequantize-256x256", "dequantize-256x40",
    "spmm", "edge_softmax", "linear_scan", "linear_scan_strict",
])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_llcg_round_compiles_for_v5e_at_arxiv_width(one_chip):
    """The vmap LLCG local round (K steps per machine + averaging)."""
    model = build_model("GBGBG", FEAT, CLASSES, hidden_dim=HIDDEN)
    program = RoundProgram(model, adam(1e-2), adam(1e-2),
                           EngineConfig(num_machines=P, mode="local",
                                        backend="vmap",
                                        with_correction=True))
    params = jax.eval_shape(lambda: model.init(0))
    opt_state = jax.eval_shape(
        lambda p: program.init_state(p).local_opt_state, params)
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: _sds(one_chip, x.shape, x.dtype), tree)
    s = one_chip
    args = (place(params), place(opt_state),
            _sds(s, (P, NODES, FEAT)), _sds(s, (P, NODES), jnp.int32),
            _sds(s, (P, K, NODES, FANOUT), jnp.int32),
            _sds(s, (P, K, NODES, FANOUT)),
            _sds(s, (P, K, BATCH), jnp.int32), _sds(s, (P, K, BATCH)),
            _sds(s, (K,)))
    compiled = program._round.lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_bucketed_correction_step_compiles_for_v5e(one_chip):
    """The correction's gradient over degree-bucketed full-neighbor tables,
    zero-degree (width-0) bucket included, at arxiv width, with the
    zero-width stand-in for the single table that the plan passes."""
    graph = rmat_graph(num_nodes=NODES, num_edges=4 * NODES, seed=0).graph
    agg = bucketed_operands(graph)
    assert agg.buckets.tables[0].shape[1] == 0
    model = build_model("GBGBG", FEAT, CLASSES, hidden_dim=HIDDEN)
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: _sds(one_chip, x.shape, x.dtype), tree)
    s = one_chip
    args = (place(jax.eval_shape(lambda: model.init(0))),
            _sds(s, (NODES, FEAT)), _sds(s, (NODES, 0), jnp.int32),
            _sds(s, (NODES, 0)), _sds(s, (BATCH,), jnp.int32),
            _sds(s, (NODES,), jnp.int32), _sds(s, (BATCH,)), place(agg))
    compiled = jax.jit(jax.grad(make_loss_fn(model))).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


# one compiled HLO instruction: its result's element type and dims, and the
# jax primitive it was lowered from
_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* .*"
                        r'op_name="[^"]*/(\w+)"')


def test_device_sampler_draws_tables_without_a_gather(one_chip):
    """The round's neighbor tables are picked out of the per-row id table:
    no gather produces a ``(P, K, NODES, FANOUT)`` table's elements (the
    CSR gather did, one scalar HBM lookup per sampled slot)."""
    s = one_chip
    i32 = jnp.int32
    dcsr = DeviceCSR(nbr=_sds(s, (P, NODES, DMAX), i32),
                     degrees=_sds(s, (P, NODES), i32),
                     train_nodes=_sds(s, (P, 1024), i32),
                     train_counts=_sds(s, (P,), i32),
                     fanouts=_sds(s, (P,), i32), dmax=DMAX)
    compiled = jax.jit(sample_round_device, static_argnums=(2, 3, 4)).lower(
        dcsr, _sds(s, (2,), jnp.uint32), K, FANOUT, BATCH).compile()
    table = P * K * NODES * FANOUT
    gathers = [m.group(0) for m in map(_HLO_INSTR.match,
                                        compiled.as_text().splitlines())
               if m and m.group(2) == "gather"
               and math.prod(int(d) for d in m.group(1).split(",") if d)
               == table]
    assert not gathers, gathers[:3]
