"""Plain reference of LLCG training (Ramezani et al., ICLR 2022, Alg. 2).

Straightforward ``jax.numpy``, written from the algorithm and from the
documented semantics of the configuration, importing nothing of the program
under test and taking nothing it made: the partition, the local views, the
sampled neighbor tables and batches, the initial weights, every training
step and the evaluation are computed here from the graph and ``--seed``.

One LLCG round (``rounds`` of them from the initial weights):

1. every machine p of P starts a fresh Adam from the server weights and
   takes K steps on its own partition, each on a mini-batch of B local
   train nodes with up to ``fanout`` sampled local neighbors per node
   (cut edges are invisible to the machine);
2. the server averages the P machines' weights;
3. the server takes S Adam steps (its Adam state persists across rounds) on
   mini-batches of B_S global train nodes with every neighbor;
4. the server evaluates the full-graph, full-neighbor loss and accuracy on
   the validation nodes.

Semantics the program documents and this reference follows, so that the
same seed draws the same data:

* bfs partition: balanced multi-seed BFS growth from P seeds drawn by
  ``numpy.random.default_rng(partition_seed)``, the traffic's fixed seed,
  not the run's; machine p holds its nodes in ascending global id, and its
  local graph keeps only in-partition edges.
* a machine's view is padded to the largest partition's row count; padded
  rows have zero features and no neighbors, and BatchNorm's statistics are
  taken over every row of the view, padded ones included.
* device sampling stream: round key ``fold_in(PRNGKey(seed), r)``, machine
  key ``fold_in(round, p)``, step key ``fold_in(machine, s)``; neighbors
  ranked by the keys of ``bits(fold_in(step, 0), (n_pad, dmax))``, batch
  by ``bits(fold_in(step, 1), (t_pad,))`` (with replacement from
  ``randint(fold_in(step, 2))`` when the pool is smaller than B), each
  keeping the smallest keys in order with the lower index first on ties.
* correction batches: B_S of the global train nodes without replacement,
  the B_S smallest of ``default_rng(seed + 1).random((S, n_train))`` per
  round.
* weights: Glorot-normal matrices drawn in layer order from
  ``default_rng(seed)``, zero biases, BatchNorm gamma 1 and beta 0.

Every matrix product runs at ``highest`` precision.  ``dtype`` puts the
whole computation in another type (the benchmark's lower-precision control
runs it in bfloat16), and ``precision`` computes the dense products at a
lower precision (:func:`products`; read beside the control).
"""
from __future__ import annotations

from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

#: rows of at most this width are ranked by packing the slot index into the
#: key; wider rows rank the keys themselves with a lower-index tie-break
PACKED_RANK_MAX_WIDTH = 128
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
BN_EPS = 1e-5


# ------------------------------------------------------------- partition
def bfs_partition(indptr, indices, num_parts: int, seed: int) -> np.ndarray:
    """Balanced multi-seed BFS growth: the smallest part below the target
    size ceil(N/P) takes one BFS layer at a time, stopping at the target."""
    rng = np.random.default_rng(seed)
    n = len(indptr) - 1
    target = int(np.ceil(n / num_parts))
    part = -np.ones(n, dtype=np.int32)
    sizes = [0] * num_parts
    frontiers: List[List[int]] = [[] for _ in range(num_parts)]
    for p, s in enumerate(rng.choice(n, size=num_parts, replace=False)):
        part[s] = p
        sizes[p] = 1
        frontiers[p] = [int(s)]
    left = n - num_parts
    order = list(range(num_parts))
    nbrs = indices.tolist()
    ptr = indptr.tolist()
    while left > 0:
        order.sort(key=lambda q: sizes[q])
        grew = False
        for p in order:
            if sizes[p] >= target and any(sizes[q] < target
                                          for q in range(num_parts)):
                continue
            nxt: List[int] = []
            for v in frontiers[p]:
                for u in nbrs[ptr[v]:ptr[v + 1]]:
                    if part[u] < 0:
                        part[u] = p
                        sizes[p] += 1
                        left -= 1
                        nxt.append(u)
                        grew = True
                        if sizes[p] >= target:
                            break
                if sizes[p] >= target:
                    break
            frontiers[p] = nxt or frontiers[p]
            if left == 0:
                break
        if not grew:
            for v in np.flatnonzero(part < 0):
                p = int(np.argmin(sizes))
                part[v] = p
                sizes[p] += 1
            left = 0
    return part


def machine_views(graph: Dict, data: Dict, part: np.ndarray, P: int,
                  batch_size: int):
    """Each machine's local CSR (cut edges dropped, ascending local ids),
    padded features/labels and local train pool, stacked over machines."""
    indptr, indices = graph["indptr"], graph["indices"]
    n = len(indptr) - 1
    row = np.repeat(np.arange(n), np.diff(indptr))
    nodes = [np.flatnonzero(part == p) for p in range(P)]
    n_pad = max(len(v) for v in nodes)
    d = data["features"].shape[1]
    feats = np.zeros((P, n_pad, d), np.float32)
    labels = np.zeros((P, n_pad), np.int32)
    locs, pools = [], []
    for p, v in enumerate(nodes):
        new = -np.ones(n, np.int64)
        new[v] = np.arange(len(v))
        keep = (new[row] >= 0) & (new[indices] >= 0)
        r, c = new[row[keep]], new[indices[keep]]      # already row-sorted
        deg = np.bincount(r, minlength=len(v))
        indptr_p = np.concatenate([[0], np.cumsum(deg)])
        locs.append((indptr_p, c.astype(np.int32)))
        feats[p, :len(v)] = data["features"][v]
        labels[p, :len(v)] = data["labels"][v]
        pool = new[np.intersect1d(data["train_nodes"], v)]
        pool = pool[pool >= 0]
        if pool.size == 0:
            pool = np.arange(min(4, len(v)))
        pools.append(pool.astype(np.int32))
    e_pad = max(max(len(c) for _, c in locs), 1)
    t_pad = max(max(len(t) for t in pools), batch_size, 1)
    dmax = max(max(int(np.diff(ip).max(initial=0)) for ip, _ in locs), 1)
    idx = np.zeros((P, e_pad), np.int32)
    starts = np.zeros((P, n_pad), np.int32)
    degs = np.zeros((P, n_pad), np.int32)
    train = np.zeros((P, t_pad), np.int32)
    for p, ((ip, c), t) in enumerate(zip(locs, pools)):
        idx[p, :len(c)] = c
        starts[p, :len(ip) - 1] = ip[:-1]
        degs[p, :len(ip) - 1] = np.diff(ip)
        train[p, :len(t)] = t
    return dict(nodes=nodes, n_pad=n_pad, feats=feats, labels=labels,
                indices=idx, starts=starts, degrees=degs, train=train,
                counts=np.array([len(t) for t in pools], np.int32), dmax=dmax)


# -------------------------------------------------------------- sampling
def smallest_keys(bits, valid, width: int):
    """Slot indices of the ``width`` smallest random keys per row, valid
    slots before invalid ones, lower index first on equal keys."""
    dmax = bits.shape[-1]
    w = min(width, dmax)
    slot = jnp.arange(dmax, dtype=jnp.uint32)
    if dmax <= PACKED_RANK_MAX_WIDTH:
        ib = max(int(dmax - 1).bit_length(), 1)
        keys = jnp.where(valid, ((bits >> (1 + ib)) << ib) | slot,
                         jnp.uint32(1 << 31) | slot)
    else:
        keys = jnp.where(valid, bits >> 1, jnp.uint32(0xFFFFFFFF))
    sel = jnp.argsort(keys, axis=-1, stable=True)[..., :w].astype(jnp.int32)
    if w < width:
        sel = jnp.pad(sel, [(0, 0)] * (sel.ndim - 1) + [(0, width - w)])
    return sel


def _sample_step(step_key, indices, starts, degrees, train, count, *,
                 fanout: int, dmax: int, batch_size: int):
    """One machine-step: (n_pad, fanout) table and mask, (B,) batch."""
    n_pad, e_pad = starts.shape[0], indices.shape[0]
    bits = jax.random.bits(jax.random.fold_in(step_key, 0), (n_pad, dmax),
                           dtype=jnp.uint32)
    col = jnp.arange(dmax, dtype=jnp.int32)
    sel = smallest_keys(bits, col[None, :] < degrees[:, None], fanout)
    ok = (jnp.arange(fanout)[None, :]
          < jnp.minimum(degrees, fanout)[:, None])
    pos = jnp.clip(starts[:, None] + sel, 0, e_pad - 1)
    table = jnp.where(ok, indices[pos], 0).astype(jnp.int32)
    t_pad = train.shape[0]
    bbits = jax.random.bits(jax.random.fold_in(step_key, 1), (t_pad,),
                            dtype=jnp.uint32)
    wor = smallest_keys(bbits, jnp.arange(t_pad) < count, batch_size)
    rep = jax.random.randint(jax.random.fold_in(step_key, 2), (batch_size,),
                             0, jnp.maximum(count, 1))
    pick = jnp.where(count >= batch_size, wor[:batch_size], rep)
    return table, ok.astype(jnp.float32), train[pick].astype(jnp.int32)


_sample_step_jit = jax.jit(_sample_step, static_argnames=(
    "fanout", "dmax", "batch_size"))


def sample_round(views: Dict, seed: int, r: int, K: int, fanout: int,
                 batch_size: int):
    """Round r's (P, K, n_pad, fanout) tables/masks and (P, K, B) batches."""
    step = _sample_step_jit
    key_r = jax.random.fold_in(jax.random.PRNGKey(seed), r)
    tabs, msks, bats = [], [], []
    for p in range(views["feats"].shape[0]):
        kp = jax.random.fold_in(key_r, p)
        out = [step(jax.random.fold_in(kp, s), views["indices"][p],
                    views["starts"][p], views["degrees"][p],
                    views["train"][p], views["counts"][p], fanout=fanout,
                    dmax=views["dmax"], batch_size=batch_size)
               for s in range(K)]
        tabs.append(np.stack([np.asarray(o[0]) for o in out]))
        msks.append(np.stack([np.asarray(o[1]) for o in out]))
        bats.append(np.stack([np.asarray(o[2]) for o in out]))
    return np.stack(tabs), np.stack(msks), np.stack(bats)


# ----------------------------------------------------------------- model
def init_params(arch: str, d_in: int, hidden: int, classes: int,
                seed: int) -> Dict[str, Dict[str, np.ndarray]]:
    """Glorot-normal weights in layer order; the last non-BatchNorm layer
    maps to the classes, every other one to ``hidden``."""
    rng = np.random.default_rng(seed)

    def glorot(shape):
        scale = np.sqrt(2.0 / (shape[0] + shape[-1]))
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    last = max(i for i, op in enumerate(arch) if op != "B")
    params, d = {}, d_in
    for i, op in enumerate(arch):
        name = f"{op.lower()}{i}"
        if op == "B":
            params[name] = {"gamma": np.ones(d, np.float32),
                            "beta": np.zeros(d, np.float32)}
            continue
        d_out = classes if i == last else hidden
        if op == "S":
            params[name] = {"w_self": glorot((d, d_out)),
                            "w_nbr": glorot((d, d_out)),
                            "b": np.zeros(d_out, np.float32)}
        else:
            params[name] = {"w": glorot((d, d_out)),
                            "b": np.zeros(d_out, np.float32)}
        d = d_out
    return params


def _head(x):
    """The upper 16 bits of each float32: a bfloat16 value, exactly, which
    no compiler may widen back (it is not a conversion)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _three_pass(a, b):
    a1, b1 = _head(a), _head(b)
    a2, b2 = _head(a - a1), _head(b - b1)
    return a1 @ b1 + (a1 @ b2 + a2 @ b1)


def _one_pass(a, b):
    return _head(a) @ _head(b)


def _with_backward(product: Callable) -> Callable:
    """``product`` in the forward pass and in both backward products."""
    @jax.custom_vjp
    def mm(a, b):
        return product(a, b)

    def fwd(a, b):
        return product(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        return product(g, b.T), product(a.T, g)

    mm.defvjp(fwd, bwd)
    return mm


def products(precision: str) -> Callable:
    """The dense float32 product ``a @ b`` at a named precision, the same
    on every platform and in the backward pass too: ``highest`` is the
    float32 product; ``high`` splits each operand into a bfloat16 head and
    a bfloat16 tail and sums the three largest partial products (the
    three-pass product); ``default`` multiplies the heads once (the
    one-pass product).  Heads and tails are truncated, not rounded; the
    partial products are exact in float32."""
    if precision == "highest":
        return jnp.matmul
    return _with_backward({"high": _three_pass,
                           "default": _one_pass}[precision])


def forward(params, arch: str, h, mean_nbrs: Callable,
            mm: Callable = jnp.matmul):
    """Logits of every row.  G: relu(mean_nbrs(h) W + b); S: relu(h W_self
    + mean_nbrs(h) W_nbr + b); L: h W + b; B: batch statistics over the
    rows; no relu after the last non-BatchNorm layer."""
    last = max(i for i, op in enumerate(arch) if op != "B")
    for i, op in enumerate(arch):
        p = params[f"{op.lower()}{i}"]
        if op == "B":
            mu = jnp.mean(h, axis=0, keepdims=True)
            var = jnp.mean(jnp.square(h - mu), axis=0, keepdims=True)
            h = (h - mu) / jnp.sqrt(var + BN_EPS) * p["gamma"] + p["beta"]
            continue
        if op == "G":
            h = mm(mean_nbrs(h), p["w"]) + p["b"]
        elif op == "S":
            h = mm(h, p["w_self"]) + mm(mean_nbrs(h), p["w_nbr"]) + p["b"]
        else:
            h = mm(h, p["w"]) + p["b"]
        if i != last:
            h = jax.nn.relu(h)
    return h


def batch_loss(logits, labels, batch, weight):
    """Weighted mean cross-entropy of the batch rows."""
    logp = jax.nn.log_softmax(logits[batch], axis=-1)
    nll = -jnp.take_along_axis(logp, labels[batch][:, None], axis=-1)[:, 0]
    return jnp.sum(nll * weight) / jnp.maximum(jnp.sum(weight), 1)


def sampled_mean(table, mask):
    def mean_nbrs(h):
        s = jnp.sum(h[table] * mask[..., None], axis=1)
        return s / jnp.maximum(jnp.sum(mask, axis=1), 1)[:, None]
    return mean_nbrs


def full_mean(row, col, deg):
    def mean_nbrs(h):
        s = jax.ops.segment_sum(h[col], row, num_segments=deg.shape[0])
        return s / jnp.maximum(deg, 1)[:, None]
    return mean_nbrs


def adam_step(params, mu, nu, t, grads, lr):
    """One Adam step (bias-corrected, eps outside the square root)."""
    mu = jax.tree_util.tree_map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g,
                                mu, grads)
    nu = jax.tree_util.tree_map(
        lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, nu, grads)
    c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS),
        params, mu, nu)
    return params, mu, nu


def mean_over_machines(machine_params: List):
    return jax.tree_util.tree_map(lambda *x: sum(x) / len(x), *machine_params)


def make_steps(arch: str, lr: float, B: int, B_S: int, dtype=jnp.float32,
               precision: str = "highest"):
    """The jitted local step, correction step and evaluation, to be traced
    at ``highest`` matmul precision (``precision`` picks the products).
    The full graph ``(feats, labels, row, col, deg)`` goes in as an
    argument: closed over, it would be compiled in as constants."""
    weight = lambda b: jnp.ones(b, dtype)  # noqa: E731
    mm = products(precision)

    @jax.jit
    def local_step(params, mu, nu, t, feats, labels, table, mask, batch):
        def loss_fn(q):
            logits = forward(q, arch, feats, sampled_mean(table, mask), mm)
            return batch_loss(logits, labels, batch, weight(B))
        loss, g = jax.value_and_grad(loss_fn)(params)
        params, mu, nu = adam_step(params, mu, nu, t, g, lr)
        return params, mu, nu, loss

    @jax.jit
    def corr_step(params, mu, nu, t, batch, full):
        feats, labels, row, col, deg = full

        def loss_fn(q):
            logits = forward(q, arch, feats, full_mean(row, col, deg), mm)
            return batch_loss(logits, labels, batch, weight(B_S))
        loss, g = jax.value_and_grad(loss_fn)(params)
        params, mu, nu = adam_step(params, mu, nu, t, g, lr)
        return params, mu, nu, loss, g

    @jax.jit
    def evaluate(params, val, full):
        feats, labels, row, col, deg = full
        logits = forward(params, arch, feats, full_mean(row, col, deg), mm)
        loss = batch_loss(logits, labels, val, jnp.ones(val.shape[0], dtype))
        acc = jnp.mean((jnp.argmax(logits[val], -1) == labels[val])
                       .astype(jnp.float32))
        return loss, acc

    return local_step, corr_step, evaluate


# ------------------------------------------------------------------ run
def run_reference(graph: Dict, data: Dict, model: Dict, plan: Dict,
                  seed: int, rounds: int, dtype=jnp.float32,
                  precision: str = "highest") -> Dict:
    """LLCG from the seed through ``rounds`` rounds.

    Returns per round the mean local loss over machines and steps, the
    mean correction loss, the evaluation loss and accuracy, and the sampled
    tables, masks and batches (local and correction); the first local
    step's loss (mean over machines), and the correction's and the
    evaluation's at the initial weights; the first correction gradient; the
    weights before round 1 and after the last round; and the partition's
    row counts and sampled edges per machine.
    """
    arch, P = model["arch"], plan["num_machines"]
    K, S, F = plan["local_k"], plan["correction_steps"], plan["fanout"]
    B, B_S, lr = plan["batch_size"], plan["server_batch_size"], plan["lr"]
    indptr, indices = graph["indptr"], graph["indices"]
    n = len(indptr) - 1
    part = bfs_partition(indptr, indices, P, plan["partition_seed"])
    views = machine_views(graph, data, part, P, B)
    cast = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jnp.asarray(x, dtype), t)
    p0 = init_params(arch, data["features"].shape[1], model["hidden_dim"],
                     data["num_classes"], seed)
    deg = np.diff(indptr)
    row = jnp.asarray(np.repeat(np.arange(n), deg), jnp.int32)
    col = jnp.asarray(indices)
    full_deg = jnp.asarray(deg, dtype)
    feats_all = jnp.asarray(data["features"], dtype)
    labels_all = jnp.asarray(data["labels"])
    val = jnp.asarray(data["val_nodes"])
    corr_rng = np.random.default_rng(seed + 1)
    train_all = np.asarray(data["train_nodes"])

    local_step, corr_step, evaluate = make_steps(arch, lr, B, B_S, dtype,
                                                 precision)
    full = (feats_all, labels_all, row, col, full_deg)

    out = {"local_loss": [], "corr_loss": [], "eval_loss": [],
           "val_score": [], "tables": [], "masks": [], "batches": [],
           "corr_batches": []}
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)  # noqa
    with jax.default_matmul_precision("highest"):
        params = cast(p0)
        s_mu, s_nu, s_t = zeros(params), zeros(params), 0
        feats_v = [jnp.asarray(views["feats"][p], dtype) for p in range(P)]
        labels_v = [jnp.asarray(views["labels"][p]) for p in range(P)]
        for r in range(1, rounds + 1):
            tables, masks, batches = sample_round(views, seed, r, K, F, B)
            machine_params, losses = [], []
            for p in range(P):
                q, mu, nu = params, zeros(params), zeros(params)
                for s in range(K):
                    q, mu, nu, loss = local_step(
                        q, mu, nu, s + 1, feats_v[p], labels_v[p],
                        jnp.asarray(tables[p, s]),
                        jnp.asarray(masks[p, s], dtype),
                        jnp.asarray(batches[p, s]))
                    losses.append(float(loss))
                machine_params.append(q)
            params = mean_over_machines(machine_params)
            keys = corr_rng.random((S, train_all.size))
            cb = train_all[np.argsort(keys, axis=1)[:, :B_S]]
            if r == 1:
                # the correction's and the evaluation's losses at the
                # initial weights, before any optimizer step
                p0c = cast(p0)
                out["first_corr_loss"] = float(corr_step(
                    p0c, zeros(p0c), zeros(p0c), 1, jnp.asarray(cb[0]),
                    full)[3])
                out["first_eval_loss"] = float(evaluate(p0c, val, full)[0])
            closs = []
            for s in range(S):
                s_t += 1
                params, s_mu, s_nu, loss, g = corr_step(
                    params, s_mu, s_nu, s_t, jnp.asarray(cb[s]), full)
                closs.append(float(loss))
                if s_t == 1:
                    out["grad1"] = jax.tree_util.tree_map(
                        lambda x: np.asarray(x, np.float32), g)
            if r == 1:
                out["first_loss"] = float(np.mean(losses[::K]))
            eloss, acc = evaluate(params, val, full)
            out["local_loss"].append(float(np.mean(losses)))
            out["corr_loss"].append(float(np.mean(closs)))
            out["eval_loss"].append(float(eloss))
            out["val_score"].append(float(acc))
            out["tables"].append(tables)
            out["masks"].append(masks)
            out["batches"].append(batches)
            out["corr_batches"].append(cb.astype(np.int32))
    to_np = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: np.asarray(x, np.float32), t)
    out["params0"] = to_np(p0)
    out["params_last"] = to_np(params)
    out["part_rows"] = [len(v) for v in views["nodes"]]
    out["part_sampled_edges"] = [
        int(np.minimum(views["degrees"][p], F).sum()) for p in range(P)]
    out["directed_edges"] = int(len(indices))
    return out
