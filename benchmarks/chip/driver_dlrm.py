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

import traffic_gen

_take_rows = jax.jit(lambda storage, slots: jnp.take(storage, slots, axis=0))


def build(cfg: dict, key, data: np.ndarray):
    """(host table, trainer, runtime) for one configuration, with every
    padded operand shape warmed up (:func:`warm`). Tables of unequal rows
    (``table_rows``) are fused by the program into one row space under one
    slot pool; unequal ``pooling`` raises NotImplementedError."""
    from repro.configs.base import DLRMConfig
    from repro.core.dlrm_runtime import DLRMTrainer
    from repro.core.host_table import HostEmbeddingTable
    from repro.core.runtime import make_runtime

    pool = traffic_gen.pooling(cfg)
    if len(set(pool)) > 1:
        raise NotImplementedError(
            f"{cfg['name']}: pooling differs between tables ({pool}); the "
            "program takes one lookups_per_table for every table, as one "
            "(B, T, L) block of ids (ROADMAP Reach 2: ragged bags)")
    shape = ({"table_rows": tuple(traffic_gen.table_rows(cfg))}
             if "table_rows" in cfg else
             {"rows_per_table": cfg["rows_per_table"]})
    dc = DLRMConfig(
        name=cfg["name"],
        num_tables=cfg["num_tables"],
        **shape,
        embed_dim=cfg["embed_dim"],
        lookups_per_table=pool[0],
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
    warm(pipe, cfg["batch_size"] * sum(pool))
    return host, trainer, pipe


def pad_sizes(pipe, n_max: int) -> list:
    """Every length to which the runtime pads a victim read or a fill of
    1..``n_max`` rows."""
    from repro.core.plan import pad_len

    out, n = [], 1
    while n <= n_max:
        out.append(pad_len(n, pipe.pad_buckets))
        n = out[-1] + 1
    return out


def warm(pipe, n_max: int) -> None:
    """Compile, or load from the cache, [Collect]'s victim read and
    [Insert]'s fill at every padded length of up to ``n_max`` rows, the ids
    of one step. How many rows a step misses and evicts depends on the seed
    (the first step that evicts takes anything from one victim to a step's
    misses), so a seed can reach a length that the seeds before it did not.
    Its run would then compile in set-up, and a process that has compiled
    runs the window faster (the host's heap; PERF.md). Each fill writes the
    rows just read to the out-of-range sentinel slot: the scratchpad is left
    as it was, and no host block of rows is made that would move the heap."""
    from repro.core import scratchpad as sp

    for p in pad_sizes(pipe, n_max):
        rows = sp.read(pipe.storage, np.zeros(p, np.int32))
        pipe.storage = sp.fill(pipe.storage, np.full(p, pipe.num_slots, np.int32),
                               rows, kernel=pipe.kernel)
    jax.block_until_ready(pipe.storage)


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
