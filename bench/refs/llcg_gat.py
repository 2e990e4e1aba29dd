"""Plain reference of LLCG training (Ramezani et al., ICLR 2022, Alg. 2) of
the multi-head residual GAT (Veličković et al., arXiv:1710.10903; the
ogbn-arxiv stack of Wang et al., arXiv:2103.13355).

Straightforward ``jax.numpy``, written from the equations below, importing
nothing of the program under test.  The partition, the local views, the
device sampling stream, the correction batches, Adam and the loss come
from the GCN reference beside it (``llcg_gnn.py``), whose docstring gives
their semantics; this file adds the model.

Layer l, H heads of width F (the classes at the last layer):

* ``z = h W_l`` as ``(N, H, F)``, no bias;
* ``s_src[j] = z[j]·a_src``, ``s_dst[i] = z[i]·a_dst``, per head;
* ``e[i, j] = LeakyReLU_0.2(s_dst[i] + s_src[j])`` for j in the row's
  neighbours and, with ``self_loop``, i itself;
* ``α = softmax_j e[i, ·]`` over the row's valid slots (none: zero);
* ``o[i] = Σ_j α[i, j] z[j] + h_i R_l`` (``R_l`` with ``residual``);
* between layers ``h' = ReLU(BN(concat_k o[i, k]))`` with ``batch_norm``,
  else ``ELU(concat_k o[i, k] + b)``; at the last ``mean_k o[i, k] + b``.

Weights: Glorot-normal from ``numpy.random.default_rng(seed)``, per layer
``W``, ``R`` (with ``residual``), ``a_dst``, ``a_src`` (shape ``(H, F)``, or
``(F,)`` for one head); BatchNorm gamma 1 and beta 0; zero biases.
BatchNorm's statistics run over every row of the view, padded ones
included.  A machine's rows are its local view with the sampled
``(n_pad, fanout)`` table; the server's are the whole graph with every
neighbour.  The attention runs in blocks of :data:`BLOCK_ROWS` rows, each
recomputed in the backward pass (``jax.checkpoint``), so that no more than
a block's gathered neighbour rows is held at once.

Every matrix product runs at ``highest`` precision; ``dtype`` and
``precision`` act as in ``llcg_gnn.py``.
"""
from __future__ import annotations

import importlib.util
import os
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


base = _sibling("llcg_gnn")

#: rows per attention block: the block's gathered neighbour rows are what
#: the reference holds of the slab at once
BLOCK_ROWS = 2048
NEGATIVE_SLOPE = 0.2


# ----------------------------------------------------------------- model
def init_params(model: Dict, d_in: int, classes: int, seed: int) -> Dict:
    """Glorot-normal weights in the order of the module docstring."""
    rng = np.random.default_rng(seed)

    def glorot(shape):
        scale = np.sqrt(2.0 / (shape[0] + shape[-1]))
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    heads, layers = model["num_heads"], model["num_layers"]
    params, d = {}, d_in
    for layer in range(layers):
        last = layer == layers - 1
        f = classes if last else model["hidden_dim"]
        a_shape = (f,) if heads == 1 else (heads, f)
        p = {"w": glorot((d, heads * f))}
        if model.get("residual", False):
            p["r"] = glorot((d, heads * f))
        p["a_dst"] = glorot(a_shape)
        p["a_src"] = glorot(a_shape)
        if last:
            p["b"] = np.zeros(f, np.float32)
        elif model.get("batch_norm", False):
            params[f"bn{layer}"] = {"gamma": np.ones(heads * f, np.float32),
                                    "beta": np.zeros(heads * f, np.float32)}
        else:
            p["b"] = np.zeros(heads * f, np.float32)
        params[f"gat{layer}"] = p
        d = heads * f
    return params


def attention(z, s_src, s_dst, table, mask, heads: int):
    """``Σ_j α[i, j] z[j]`` per head for every row of ``table``/``mask``
    (the row's slots, the self slot included), a block of rows at a time."""
    n, d = z.shape
    nb = -(-n // BLOCK_ROWS)
    pad = nb * BLOCK_ROWS - n
    rows = jnp.arange(nb * BLOCK_ROWS).reshape(nb, BLOCK_ROWS)
    tab = jnp.pad(table, ((0, pad), (0, 0))).reshape(nb, BLOCK_ROWS, -1)
    msk = jnp.pad(mask, ((0, pad), (0, 0))).reshape(nb, BLOCK_ROWS, -1)
    s_dst = jnp.pad(s_dst, ((0, pad), (0, 0)))

    @jax.checkpoint
    def block(args):
        r, t, m = args
        e = jax.nn.leaky_relu(s_dst[r][:, None, :] + s_src[t],
                              NEGATIVE_SLOPE)                 # (b, w, H)
        e = jnp.where(m[..., None] > 0, e, -1e30)
        alpha = jax.nn.softmax(e, axis=1) * m[..., None]
        zj = z[t].reshape(*t.shape, heads, d // heads)
        return jnp.sum(alpha[..., None] * zj, axis=1).reshape(-1, d)

    return jax.lax.map(block, (rows, tab, msk)).reshape(-1, d)[:n]


def forward(params, model: Dict, h, table, mask, mm: Callable = jnp.matmul):
    """Logits of every row; ``table``/``mask`` are the rows' neighbour
    slots, the self slot added here with ``self_loop``."""
    heads, layers = model["num_heads"], model["num_layers"]
    n = h.shape[0]
    if model.get("self_loop", False):
        table = jnp.concatenate(
            [jnp.arange(n, dtype=table.dtype)[:, None], table], axis=1)
        mask = jnp.concatenate([jnp.ones((n, 1), mask.dtype), mask], axis=1)
    for layer in range(layers):
        p = params[f"gat{layer}"]
        z = mm(h, p["w"])
        z3 = z.reshape(n, heads, -1)
        s_src = jnp.sum(z3 * p["a_src"].reshape(heads, -1), axis=-1)
        s_dst = jnp.sum(z3 * p["a_dst"].reshape(heads, -1), axis=-1)
        o = attention(z, s_src, s_dst, table, mask, heads)
        if "r" in p:
            o = o + mm(h, p["r"])
        if layer == layers - 1:
            return o.reshape(n, heads, -1).mean(axis=1) + p["b"]
        if model.get("batch_norm", False):
            bn = params[f"bn{layer}"]
            mu = jnp.mean(o, axis=0, keepdims=True)
            var = jnp.mean(jnp.square(o - mu), axis=0, keepdims=True)
            o = (o - mu) / jnp.sqrt(var + base.BN_EPS) * bn["gamma"] \
                + bn["beta"]
            h = jax.nn.relu(o)
        else:
            h = jax.nn.elu(o + p["b"])


def full_table(indptr, indices):
    """Every node's neighbours as an ``(N, max_degree)`` table in CSR order
    and its mask; padding slots read node 0 under mask 0."""
    n = len(indptr) - 1
    deg = np.diff(indptr)
    width = max(int(deg.max(initial=0)), 1)
    col = np.arange(width)[None, :]
    ok = col < deg[:, None]
    pos = np.where(ok, indptr[:-1, None] + col, 0)
    table = np.where(ok, indices[np.minimum(pos, len(indices) - 1)], 0)
    return table.astype(np.int32), ok.astype(np.float32)


def make_steps(model: Dict, lr: float, B: int, B_S: int, dtype=jnp.float32,
               precision: str = "highest"):
    """The jitted local step, correction step and evaluation (traced at
    ``highest`` matmul precision; ``precision`` picks the products)."""
    weight = lambda b: jnp.ones(b, dtype)  # noqa: E731
    mm = base.products(precision)

    @jax.jit
    def local_step(params, mu, nu, t, feats, labels, table, mask, batch):
        def loss_fn(q):
            logits = forward(q, model, feats, table, mask, mm)
            return base.batch_loss(logits, labels, batch, weight(B))
        loss, g = jax.value_and_grad(loss_fn)(params)
        params, mu, nu = base.adam_step(params, mu, nu, t, g, lr)
        return params, mu, nu, loss

    @jax.jit
    def corr_step(params, mu, nu, t, batch, full):
        feats, labels, table, mask = full

        def loss_fn(q):
            logits = forward(q, model, feats, table, mask, mm)
            return base.batch_loss(logits, labels, batch, weight(B_S))
        loss, g = jax.value_and_grad(loss_fn)(params)
        params, mu, nu = base.adam_step(params, mu, nu, t, g, lr)
        return params, mu, nu, loss, g

    @jax.jit
    def evaluate(params, val, full):
        feats, labels, table, mask = full
        logits = forward(params, model, feats, table, mask, mm)
        loss = base.batch_loss(logits, labels, val,
                               jnp.ones(val.shape[0], dtype))
        acc = jnp.mean((jnp.argmax(logits[val], -1) == labels[val])
                       .astype(jnp.float32))
        return loss, acc

    return local_step, corr_step, evaluate


# ------------------------------------------------------------------ run
def run_reference(graph: Dict, data: Dict, model: Dict, plan: Dict,
                  seed: int, rounds: int, dtype=jnp.float32,
                  precision: str = "highest") -> Dict:
    """LLCG from the seed through ``rounds`` rounds; the same record as
    ``llcg_gnn.run_reference`` returns, with ``part_sampled_edges`` the
    sampled slots per machine and ``directed_edges`` the graph's edges."""
    P = plan["num_machines"]
    K, S, F = plan["local_k"], plan["correction_steps"], plan["fanout"]
    B, B_S, lr = plan["batch_size"], plan["server_batch_size"], plan["lr"]
    indptr, indices = graph["indptr"], graph["indices"]
    part = base.bfs_partition(indptr, indices, P, plan["partition_seed"])
    views = base.machine_views(graph, data, part, P, B)
    cast = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jnp.asarray(x, dtype), t)
    p0 = init_params(model, data["features"].shape[1], data["num_classes"],
                     seed)
    table, mask = full_table(indptr, indices)
    full = (jnp.asarray(data["features"], dtype),
            jnp.asarray(data["labels"]), jnp.asarray(table),
            jnp.asarray(mask, dtype))
    val = jnp.asarray(data["val_nodes"])
    corr_rng = np.random.default_rng(seed + 1)
    train_all = np.asarray(data["train_nodes"])
    local_step, corr_step, evaluate = make_steps(model, lr, B, B_S, dtype,
                                                 precision)

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
            tables, masks, batches = base.sample_round(views, seed, r, K, F,
                                                       B)
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
            params = base.mean_over_machines(machine_params)
            keys = corr_rng.random((S, train_all.size))
            cb = train_all[np.argsort(keys, axis=1)[:, :B_S]]
            if r == 1:
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
