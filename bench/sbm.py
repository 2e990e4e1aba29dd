"""The benchmark's own copy of the SBM graph generator.

``sbm_graph`` and ``csr_from_edges`` are copied verbatim (apart from
returning plain arrays) from the program's ``repro.graph.datasets.sbm_graph``
and ``repro.graph.csr.CSRGraph.from_edges``, so that no change to the
program can change the benchmark's graph.  The dataset is a fixed function
of the configuration's ``dataset`` block: its ``seed`` is part of the
configuration, like a real dataset, and is not the run's ``--seed``.
"""
from __future__ import annotations

import numpy as np


def csr_from_edges(num_nodes, src, dst, symmetrize=True, dedup=True):
    """CSR ``(indptr int64, indices int32)`` from an edge list; optionally
    symmetrized and deduplicated, self loops dropped, rows sorted."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    if symmetrize:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    keep = src != dst
    src, dst = src[keep], dst[keep]
    if dedup and src.size:
        key = src * num_nodes + dst
        key = np.unique(key)
        src, dst = key // num_nodes, key % num_nodes
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    indptr = np.zeros(num_nodes + 1, dtype=np.int64)
    np.add.at(indptr, src + 1, 1)
    indptr = np.cumsum(indptr)
    return indptr.astype(np.int64), dst.astype(np.int32)


def _split(n, rng, train=0.6, val=0.2):
    perm = rng.permutation(n)
    n_tr, n_va = int(train * n), int(val * n)
    return perm[:n_tr], perm[n_tr: n_tr + n_va], perm[n_tr + n_va:]


def sbm_graph(num_nodes=1024, num_classes=8, feature_dim=32, avg_degree=12.0,
              homophily=0.9, feature_snr=0.5, seed=0):
    """Stochastic block model with Gaussian class-mean features.

    Returns a dict of arrays: ``indptr``, ``indices``, ``features``,
    ``labels``, ``train_nodes``, ``val_nodes``, ``test_nodes``.
    """
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, num_classes, size=num_nodes).astype(np.int32)
    deg = np.maximum(1, rng.poisson(avg_degree, size=num_nodes))
    src_list, dst_list = [], []
    nodes_by_class = [np.flatnonzero(labels == c) for c in range(num_classes)]
    for v in range(num_nodes):
        c = labels[v]
        k = deg[v]
        same = rng.random(k) < homophily
        n_same = int(same.sum())
        if nodes_by_class[c].size > 1 and n_same:
            tgt = rng.choice(nodes_by_class[c], size=n_same)
            src_list.append(np.full(n_same, v)); dst_list.append(tgt)
        n_cross = k - n_same
        if n_cross:
            tgt = rng.integers(0, num_nodes, size=n_cross)
            src_list.append(np.full(n_cross, v)); dst_list.append(tgt)
    src = np.concatenate(src_list); dst = np.concatenate(dst_list)
    indptr, indices = csr_from_edges(num_nodes, src, dst)
    means = rng.standard_normal((num_classes, feature_dim)) * feature_snr
    feats = means[labels] + rng.standard_normal((num_nodes, feature_dim))
    feats = feats.astype(np.float32)
    tr, va, te = _split(num_nodes, rng)
    return {"indptr": indptr, "indices": indices, "features": feats,
            "labels": labels, "train_nodes": tr, "val_nodes": va,
            "test_nodes": te}
