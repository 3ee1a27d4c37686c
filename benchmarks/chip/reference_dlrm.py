"""Plain reference for the DLRM configurations, and their operation counts.

The paper's model (arXiv:2205.04702 §V, the MLPerf DLRM shape): a bottom MLP
over the dense features, one sum-pooled embedding bag per table, a dot
interaction of the bottom output with the bags (upper triangle, no
diagonal), and a top MLP to one logit, under binary cross-entropy, trained
by plain SGD on every parameter, embedding rows included.

Written from that description in ``jax.numpy``: no cache, planner, kernel
or program module. The embedding table is a parameter like the others; only
the rows a run touches are held, as one array indexed through ``np.unique``.
The MLP initialisation is He-normal weights and zero biases, drawn from the
key in the order the model file documents, so the same key gives the same
starting point as the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

import traffic_gen


def mlp_dims(cfg: dict):
    n = cfg["num_tables"] + 1
    inter = n * (n - 1) // 2 + cfg["bottom_mlp"][-1]
    bottom = [cfg["num_dense_features"]] + list(cfg["bottom_mlp"])
    top = [inter] + list(cfg["top_mlp"])
    return bottom, top


def init_mlps(cfg: dict, key, dtype=jnp.float32):
    """He-normal weights, zero biases: key -> (bottom key, top key), then
    one key per layer."""
    bottom, top = mlp_dims(cfg)

    def one(k, dims):
        ks = jax.random.split(k, len(dims) - 1)
        return [
            {"w": jax.random.normal(kk, (a, b), dtype) * math.sqrt(2.0 / a),
             "b": jnp.zeros((b,), dtype)}
            for kk, a, b in zip(ks, dims[:-1], dims[1:])
        ]

    kb, kt = jax.random.split(key)
    return {"bottom": one(kb, bottom), "top": one(kt, top)}


def _mlp(layers, x, last_linear):
    for i, lyr in enumerate(layers):
        x = x @ lyr["w"] + lyr["b"]
        if not (last_linear and i == len(layers) - 1):
            x = jnp.maximum(x, 0)
    return x


def loss_fn(params, idx, dense, label, half: bool = False):
    """Mean BCE of one batch. ``idx`` (B, T, L) indexes ``params["rows"]``.
    ``half`` leaves out the second half of the batch (a planted fault)."""
    if half:
        n = idx.shape[0] // 2
        idx, dense, label = idx[:n], dense[:n], label[:n]
    bags = params["rows"][idx].sum(axis=2)  # (B, T, D)
    b = _mlp(params["mlps"]["bottom"], dense, last_linear=False)
    feats = jnp.concatenate([b[:, None, :], bags], axis=1)  # (B, T+1, D)
    inter = jnp.einsum("bid,bjd->bij", feats, feats)
    iu, ju = np.triu_indices(feats.shape[1], k=1)
    z = jnp.concatenate([b, inter[:, iu, ju]], axis=-1)
    logit = _mlp(params["mlps"]["top"], z, last_linear=True)[:, 0]
    logit = logit.astype(jnp.float32)
    label = label.astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logit, 0) - logit * label
        + jnp.log1p(jnp.exp(-jnp.abs(logit)))
    )


def _sgd_step(params, idx, dense, label, lr, half):
    loss, g = jax.value_and_grad(loss_fn)(params, idx, dense, label, half)
    lr = jnp.asarray(lr, jax.tree.leaves(params)[0].dtype)
    return jax.tree.map(lambda p, gg: p - lr * gg.astype(p.dtype), params, g), loss


_sgd_jit = jax.jit(_sgd_step, static_argnames=("half",))


def train(cfg: dict, key, uniq_ids: np.ndarray, rows0: np.ndarray, batches,
          lr: float, keep: dict, *, dtype=jnp.float32, precision="highest",
          half: bool = False):
    """Train ``len(batches)`` SGD steps from the seed's starting point.

    ``uniq_ids``: the sorted global ids the batches touch; ``rows0`` their
    initial rows. ``batches``: [(global ids (B, T, L), dense, label)].
    ``keep``: {steps done: {name: positions in ``uniq_ids``}}, the states
    to return. Returns every step's loss, the MLPs at the start and after
    each kept step (``{n: tree}``), and the kept rows (``{(n, name):
    rows}``), as numpy float32. ``dtype``/``precision`` select the
    arithmetic: float32 at ``highest`` is the reference; bfloat16 at the
    default precision is the control.
    """
    pad = -len(uniq_ids) % (1 << 18)  # a few fixed shapes, cached compiles
    rows = np.concatenate([rows0, np.zeros((pad, rows0.shape[1]), rows0.dtype)])
    params = {"mlps": init_mlps(cfg, key),
              "rows": jnp.asarray(rows)}
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    out = {"loss": [], "mlps": {0: _np_tree(params["mlps"])}, "rows": {}}
    with jax.default_matmul_precision(precision):
        for n, (ids, dense, label) in enumerate(batches, 1):
            idx = np.searchsorted(uniq_ids, ids).astype(np.int32)
            params, loss = _sgd_jit(
                params, jnp.asarray(idx), jnp.asarray(dense, dtype),
                jnp.asarray(label), lr, half,
            )
            out["loss"].append(float(loss))
            if n in keep:
                out["mlps"][n] = _np_tree(params["mlps"])
                for name, pos in keep[n].items():
                    out["rows"][(n, name)] = np.asarray(
                        jnp.take(params["rows"], jnp.asarray(pos), axis=0),
                        np.float32)
    return out


def _np_tree(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)


# ---------------------------------------------------------------------- #
# operation and byte counts, from shapes
# ---------------------------------------------------------------------- #
def forward_macs_per_example(cfg: dict) -> int:
    """Multiply-adds of one example's forward pass: both MLPs and the dot
    interaction (the full (T+1) x (T+1) product the model computes)."""
    bottom, top = mlp_dims(cfg)
    mlp = sum(a * b for a, b in zip(bottom[:-1], bottom[1:]))
    mlp += sum(a * b for a, b in zip(top[:-1], top[1:]))
    n = cfg["num_tables"] + 1
    return mlp + n * n * cfg["embed_dim"]


def model_flops_per_example(cfg: dict) -> int:
    """Training FLOPs per example: forward, and backward at twice the
    forward (grads of activations and of weights)."""
    return 3 * 2 * forward_macs_per_example(cfg)


def train_step_cost(cfg: dict, n_unique: float, n_fill: float):
    """(FLOPs, bytes) one [Train] step and its scratchpad fill need at
    least. Bytes: every lookup's row read by the bag gather, each unique
    row read and written by its update, each filled row read from staging
    and written to its slot, and the MLP parameters read and written.
    FLOPs: the model's training FLOPs, the bag sums and the row updates."""
    row = cfg["embed_dim"] * 4
    b = cfg["batch_size"]
    lookups = b * sum(traffic_gen.pooling(cfg))
    bottom, top = mlp_dims(cfg)
    n_params = sum(a * c + c for a, c in zip(bottom[:-1], bottom[1:]))
    n_params += sum(a * c + c for a, c in zip(top[:-1], top[1:]))
    bytes_ = (lookups * row + 2 * n_unique * row + 2 * n_fill * row
              + 2 * 4 * n_params + b * (cfg["num_dense_features"] + 1) * 4)
    flops = (model_flops_per_example(cfg) * b
             + 2 * lookups * cfg["embed_dim"])
    return flops, bytes_
