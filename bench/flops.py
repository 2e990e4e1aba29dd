"""Model FLOPs of one LLCG round: the work the algorithm needs, whatever
implements it, so that dropping padding or a kernel cannot push the
utilisation past the peak.

* a dense product counts 2·rows·d_in·d_out on the real, unpadded rows;
* a mean aggregation counts 2·edges·d over the edges it reads: the sampled
  ones, Σ min(deg, fanout), on a machine's local graph, and every directed
  edge on the full graph;
* forward and backward count three forwards, for the P·K local steps and
  the S correction steps; the evaluation counts one forward;
* the correction and the evaluation count once per round, even where every
  chip repeats them;
* BatchNorm, activations and the loss are not counted.

It is the counts module of the configurations whose operators are the
paper's ``G``/``S``/``L``/``B`` (``"counts"`` in the configuration file):
the harness calls :func:`flops_per_round` and :func:`kernel_work`.
"""
from __future__ import annotations

from typing import Dict, Sequence


def forward_flops(arch: str, d_in: int, hidden: int, classes: int,
                  rows: int, edges: int) -> int:
    """One forward over ``rows`` nodes whose aggregations read ``edges``."""
    last = max(i for i, op in enumerate(arch) if op != "B")
    total, d = 0, d_in
    for i, op in enumerate(arch):
        if op == "B":
            continue
        d_out = classes if i == last else hidden
        if op == "G":
            total += 2 * edges * d + 2 * rows * d * d_out
        elif op == "S":
            total += 2 * edges * d + 2 * 2 * rows * d * d_out
        elif op == "L":
            total += 2 * rows * d * d_out
        else:
            raise ValueError(f"no FLOP count for op {op!r} of {arch!r}")
        d = d_out
    return total


def round_flops(arch: str, d_in: int, hidden: int, classes: int,
                part_rows: Sequence[int], part_sampled_edges: Sequence[int],
                num_nodes: int, directed_edges: int, local_k: int,
                correction_steps: int) -> int:
    """Model FLOPs of one round (module docstring)."""
    fwd = lambda rows, edges: forward_flops(  # noqa: E731
        arch, d_in, hidden, classes, rows, edges)
    local = sum(fwd(r, e) for r, e in zip(part_rows, part_sampled_edges,
                                          strict=True))
    full = fwd(num_nodes, directed_edges)
    return 3 * local_k * local + 3 * correction_steps * full + full


def flops_per_round(config: Dict, traffic: Dict, ref: Dict) -> int:
    """:func:`round_flops` of the configuration's model on its graph, with
    the partition's rows and sampled edges as the reference counted them."""
    model, ds = config["model"], config["dataset"]
    return round_flops(model["arch"], ds["feature_dim"], model["hidden_dim"],
                       ds["num_classes"], ref["part_rows"],
                       ref["part_sampled_edges"], ds["num_nodes"],
                       ref["directed_edges"], traffic["local_k"],
                       traffic["correction_steps"])


def kernel_work(config: Dict, traffic: Dict, ref: Dict) -> Dict[str, Dict]:
    """No kernel of these operators has a roofline share of its own: their
    aggregations are XLA gathers, not kernels."""
    return {}
