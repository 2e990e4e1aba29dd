"""Model FLOPs of one LLCG round of the multi-head GAT stack, and the work
of its attention kernel: the counts module of the configurations whose
model is ``GAT`` (``"counts"`` in the configuration file).

It counts as ``flops.py`` counts, on real rows and real edges:

* a dense product counts 2·rows·d_in·d_out (``z = h W``, and ``h R`` with
  the residual projection);
* the scores count 2·rows·H·F each (``z·a_src``, ``z·a_dst``);
* the attention counts 2·slots·H·F over the slots it reads: the sampled
  edges, Σ min(deg, fanout), on a machine's local graph, every directed
  edge on the full graph, and one slot per row for the node itself with
  ``self_loop``;
* forward and backward count three forwards, for the P·K local steps and
  the S correction steps; the evaluation counts one forward, once a round;
* BatchNorm, activations, the softmax and the loss are not counted.

``kernel_work`` gives the Pallas kernel's work a round (``gat_attention``,
the forward's neighbour gather and weighted sum; the backward is XLA): per
call 2·slots·H·F operations, and the bytes it must move at least: each
slot's row of ``z`` (H·F float32), its H weights and its id, and each row's
H·F outputs.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

#: the kernel's operations in a chip trace: ``pallas_call(name=...)``
#: names the custom call's instruction ``gat_attention.<n>``
KERNEL_PATTERN = r"^gat_attention"


def layer_widths(model: Dict, d_in: int, classes: int) -> List[tuple]:
    """``(d_in, H·F)`` of each layer."""
    heads, out, d = model["num_heads"], [], d_in
    for layer in range(model["num_layers"]):
        last = layer == model["num_layers"] - 1
        hf = heads * (classes if last else model["hidden_dim"])
        out.append((d, hf))
        d = hf
    return out


def forward_flops(model: Dict, d_in: int, classes: int, rows: int,
                  edges: int) -> int:
    """One forward over ``rows`` nodes whose attention reads ``edges``
    neighbour slots."""
    slots = edges + rows * int(model.get("self_loop", False))
    products = 2 if model.get("residual", False) else 1
    total = 0
    for d, hf in layer_widths(model, d_in, classes):
        total += products * 2 * rows * d * hf + 2 * 2 * rows * hf
        total += 2 * slots * hf
    return total


def round_flops(model: Dict, d_in: int, classes: int,
                part_rows: Sequence[int], part_sampled_edges: Sequence[int],
                num_nodes: int, directed_edges: int, local_k: int,
                correction_steps: int) -> int:
    """Model FLOPs of one round (module docstring)."""
    fwd = lambda rows, edges: forward_flops(  # noqa: E731
        model, d_in, classes, rows, edges)
    local = sum(fwd(r, e) for r, e in zip(part_rows, part_sampled_edges,
                                          strict=True))
    full = fwd(num_nodes, directed_edges)
    return 3 * local_k * local + 3 * correction_steps * full + full


def flops_per_round(config: Dict, traffic: Dict, ref: Dict) -> int:
    ds = config["dataset"]
    return round_flops(config["model"], ds["feature_dim"], ds["num_classes"],
                       ref["part_rows"], ref["part_sampled_edges"],
                       ds["num_nodes"], ref["directed_edges"],
                       traffic["local_k"], traffic["correction_steps"])


def attention_work(model: Dict, d_in: int, classes: int, rows: int,
                   edges: int) -> Dict[str, int]:
    """The kernel's operations and least bytes for one forward over
    ``rows`` nodes and ``edges`` neighbour slots, every layer."""
    heads = model["num_heads"]
    slots = edges + rows * int(model.get("self_loop", False))
    flops = nbytes = 0
    for _, hf in layer_widths(model, d_in, classes):
        flops += 2 * slots * hf
        nbytes += 4 * (slots * (hf + heads + 1) + rows * hf)
    return {"flops": flops, "bytes": nbytes}


def kernel_work(config: Dict, traffic: Dict, ref: Dict) -> Dict[str, Dict]:
    """``gat_attention``'s work a round on one chip: the forwards of the
    P·K local steps, the S correction steps and the evaluation, where the
    model runs the kernel (``fused_gat``); empty where it does not."""
    model, ds = config["model"], config["dataset"]
    if not model.get("fused_gat", False):
        return {}
    work = lambda rows, edges: attention_work(  # noqa: E731
        model, ds["feature_dim"], ds["num_classes"], rows, edges)
    calls = [(traffic["local_k"], work(r, e))
             for r, e in zip(ref["part_rows"], ref["part_sampled_edges"],
                             strict=True)]
    calls.append((traffic["correction_steps"] + 1,
                  work(ds["num_nodes"], ref["directed_edges"])))
    return {"gat_attention": {
        "pattern": KERNEL_PATTERN,
        "flops": sum(n * w["flops"] for n, w in calls),
        "bytes": sum(n * w["bytes"] for n, w in calls)}}
