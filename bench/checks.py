"""The comparison that decides ``correct``: the program's first rounds
against the plain reference's, number by number.

Both sides are summarised in one form: per round the mean local loss, the
correction loss and the evaluation loss; the sampled tables, masks and
batches; the first correction gradient; the weights before round 1 and
after the last compared round.  Each number below is compared with its
limit from the cell's file (``bench/workloads/<cell>.json``).

* ``sample_mismatch``: entries of the sampled neighbor tables, masks, local
  batches and correction batches (as sets) that differ.  Exact: limit 0.
* ``first_loss_gap`` / ``first_corr_gap`` / ``first_eval_gap``: the
  relative gap of the loss at the initial weights, before any optimizer
  step, of the first local step (mean over machines), of the correction and
  of the evaluation.
* ``local_loss_gap`` / ``corr_loss_gap`` / ``eval_loss_gap``: the largest
  relative gap of the round's loss over the compared rounds.
* ``grad_norm_gap``: by the worst leaf, the gap between the two sides'
  norms of the first correction gradient, over the larger of the
  reference's norm of that leaf and of the median leaf.
* ``update_norm_gap``: the same for the norm of each leaf's change over the
  compared rounds.

Leaves whose first reference gradient is under a thousandth of the median
leaf's move under Adam by round-off alone; both norm gaps leave them out.
"""
from __future__ import annotations

from typing import Dict, Iterable, Mapping

import numpy as np

#: a leaf whose reference gradient norm is under this share of the median
#: leaf's is left out of the norm gaps
ROUNDOFF_LEAF_SHARE = 1e-3


def flat_leaves(tree: Mapping) -> Dict[str, np.ndarray]:
    """``{"layer/param": array}`` of a two-level param dict."""
    return {f"{layer}/{k}": np.asarray(v, np.float64)
            for layer, sub in tree.items() for k, v in sub.items()}


def _rel_gap(got: Iterable[float], want: Iterable[float]) -> float:
    got, want = np.asarray(list(got), float), np.asarray(list(want), float)
    if got.shape != want.shape:
        return float("inf")
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want),
                                                        1e-30)))


def _norm_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray],
              keep: Iterable[str]) -> float:
    keep = list(keep)
    if set(got) != set(want) or not keep:
        return float("inf")
    g = {k: float(np.linalg.norm(got[k])) for k in keep}
    w = {k: float(np.linalg.norm(want[k])) for k in keep}
    med = float(np.median(list(w.values())))
    return max(abs(g[k] - w[k]) / max(w[k], med, 1e-30) for k in keep)


def sample_mismatch(prog: Dict, ref: Dict) -> int:
    bad = 0
    for key in ("tables", "masks", "batches"):
        for a, b in zip(prog[key], ref[key], strict=True):
            a, b = np.asarray(a), np.asarray(b)
            bad += (a.size if a.shape != b.shape
                    else int(np.sum(a != b)))
    for a, b in zip(prog["corr_batches"], ref["corr_batches"], strict=True):
        a, b = np.sort(np.asarray(a), -1), np.sort(np.asarray(b), -1)
        bad += a.size if a.shape != b.shape else int(np.sum(a != b))
    return bad


def readings(prog: Dict, ref: Dict) -> Dict[str, float]:
    """Every compared number of one run (see the module docstring)."""
    rounds = len(ref["local_loss"])
    grad_ref = flat_leaves(ref["grad1"])
    norms = {k: float(np.linalg.norm(v)) for k, v in grad_ref.items()}
    med = float(np.median(list(norms.values())))
    keep = [k for k, v in norms.items() if v >= ROUNDOFF_LEAF_SHARE * med]

    def change(side):
        before = flat_leaves(side["params0"])
        return {k: v - before[k]
                for k, v in flat_leaves(side["params_last"]).items()}

    out = {
        "sample_mismatch": float(sample_mismatch(prog, ref)),
        **{f"{name}_gap": _rel_gap([prog[key]], [ref[key]])
           for name, key in (("first_loss", "first_loss"),
                             ("first_corr", "first_corr_loss"),
                             ("first_eval", "first_eval_loss"))},
        "local_loss_gap": _rel_gap(prog["local_loss"][:rounds],
                                   ref["local_loss"]),
        "corr_loss_gap": _rel_gap(prog["corr_loss"][:rounds],
                                  ref["corr_loss"]),
        "eval_loss_gap": _rel_gap(prog["eval_loss"][:rounds],
                                  ref["eval_loss"]),
        "grad_norm_gap": _norm_gap(flat_leaves(prog["grad1"]), grad_ref,
                                   keep),
        "update_norm_gap": _norm_gap(change(prog), change(ref), keep),
    }
    return {k: (v if np.isfinite(v) else float("inf"))
            for k, v in out.items()}


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, Dict]:
    """``{name: {"value", "limit"}}`` for every limited number; a number
    without a limit in the cell's file is not compared."""
    return {k: {"value": values.get(k, float("inf")), "limit": float(lim)}
            for k, lim in limits.items()}


def all_within(checked: Dict[str, Dict]) -> bool:
    return bool(checked) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checked.values())
