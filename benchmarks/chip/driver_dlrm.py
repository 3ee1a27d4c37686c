"""Builds and reads the system under test for the DLRM configurations.

The calls are those of ``repro.launch.train: train_dlrm``: a
``HostEmbeddingTable`` over the benchmark's rows, a ``DLRMTrainer``, the
runtime from ``make_runtime``, and ``pipe.run`` over a ``LookaheadStream``.
The configuration passes deployment facts only (shapes, rows, slots,
precision, lr); every implementation choice is the program's default.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_take_rows = jax.jit(lambda storage, slots: jnp.take(storage, slots, axis=0))


def build(cfg: dict, key, data: np.ndarray):
    """(host table, trainer, runtime) for one configuration."""
    from repro.configs.base import DLRMConfig
    from repro.core.dlrm_runtime import DLRMTrainer
    from repro.core.host_table import HostEmbeddingTable
    from repro.core.runtime import make_runtime

    dc = DLRMConfig(
        name=cfg["name"],
        num_tables=cfg["num_tables"],
        rows_per_table=cfg["rows_per_table"],
        embed_dim=cfg["embed_dim"],
        lookups_per_table=cfg["lookups_per_table"],
        num_dense_features=cfg["num_dense_features"],
        bottom_mlp=tuple(cfg["bottom_mlp"]),
        top_mlp=tuple(cfg["top_mlp"]),
        batch_size=cfg["batch_size"],
        param_dtype=cfg["param_dtype"],
        precision=cfg["precision"],
    )
    host = HostEmbeddingTable(dc.total_rows, dc.embed_dim, data=data)
    trainer = DLRMTrainer(dc, key, lr=cfg["lr"])
    pipe = make_runtime(
        cfg["runtime"], host, trainer.train_fn,
        num_slots=cfg["num_slots"],
        past_window=dc.past_window, future_window=dc.future_window,
    )
    return host, trainer, pipe


def run(pipe, batches):
    """Drive the runtime over an iterator of (ids, payload) until it ends."""
    from repro.data.lookahead import LookaheadStream

    stream = LookaheadStream(batches)
    return pipe.run(stream, lookahead_fn=stream.peek_ids)


def losses(stats):
    """The step losses the runtime returned, as device arrays."""
    return [s.aux["loss"] for s in stats]


def counters(metrics) -> dict:
    """Current values of the runtime's ``cache.*`` counters."""
    out = {}
    for rec in metrics.snapshot():
        if (rec.get("kind") == "counter" and rec["name"].startswith("cache.")
                and "table" not in rec["labels"]):
            out[rec["name"]] = out.get(rec["name"], 0) + rec["value"]
    return out


def rows_now(pipe, ids: np.ndarray) -> np.ndarray:
    """The scratchpad's current rows of the sorted global ``ids``, read on
    the device through the planner's slot map, without writing anything
    back to the host tier. Every id must be resident."""
    s2i = np.asarray(pipe.planner.slot_to_id)
    live = np.flatnonzero(s2i >= 0)
    order = np.argsort(s2i[live], kind="stable")
    held = s2i[live][order]
    pos = np.minimum(np.searchsorted(held, ids), held.size - 1)
    if not np.array_equal(held[pos], ids):
        raise RuntimeError("a checked row is not in the scratchpad")
    slots = live[order][pos]
    pad = np.zeros(-slots.size % (1 << 16), slots.dtype)  # few shapes
    out = _take_rows(pipe.storage, jnp.asarray(np.concatenate([slots, pad])))
    return np.asarray(out)[: slots.size]


def step_value(stat):
    """The device value that is ready when a step has finished."""
    return stat.aux["loss"]
