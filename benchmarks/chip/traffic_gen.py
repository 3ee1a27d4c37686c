"""The benchmark's own copy of the synthetic DLRM traffic generator, and the
producer process that runs it ahead of the trainer.

Copied from ``repro.data.synthetic`` (``zipf_ranks``, ``_coprime_scatter``,
``dlrm_batches``, ``dlrm_batches_group``) so that no later change to the
program can move the yardstick. Ranks come from a Zipf(s) inverse CDF and
are scattered over the id space by a bijective multiplicative hash, so hot
rows are not contiguous. ``test_bench.py`` checks that this copy yields the
program's ids for a seed.

A configuration gives equal tables (``num_tables`` x ``rows_per_table``, a
scalar ``lookups_per_table``) or, with the optional keys ``table_rows`` and
``pooling``, the rows and the lookups of each table.

This module imports numpy only: the producer is started with ``spawn`` and
never touches JAX, so it cannot hold the chip.
"""
from __future__ import annotations

import math
import multiprocessing as mp
import queue
import time

import numpy as np

#: the paper's locality classes (Fig. 3): random / low (Alibaba) / medium /
#: high (Criteo-like), as Zipf exponents
LOCALITY_S = {"random": 0.0, "low": 0.37, "medium": 0.77, "high": 0.95}

_SCATTER_PRIME = 2_654_435_761  # Knuth multiplicative hash


def coprime_scatter(ranks: np.ndarray, n: int) -> np.ndarray:
    """Bijective rank -> id map (the prime is bumped until coprime with n)."""
    p = _SCATTER_PRIME
    while math.gcd(p, n) != 1:
        p += 2
    return (ranks.astype(np.int64) * p) % n


def zipf_ranks(rng: np.random.Generator, n_rows: int, size, s: float):
    """Zipf(s) popularity ranks (rank 0 is the hottest); s <= 0 is uniform."""
    if s <= 0.0:
        return rng.integers(0, n_rows, size=size, dtype=np.int64)
    u = rng.random(size=size)
    return np.minimum(
        (n_rows * u ** (1.0 / (1.0 - s))).astype(np.int64), n_rows - 1
    )


def sample_ids(rng, n_rows: int, size, s: float) -> np.ndarray:
    ranks = zipf_ranks(rng, n_rows, size, s)
    return ranks if s <= 0.0 else coprime_scatter(ranks, n_rows)


def table_rows(cfg: dict) -> list:
    """Rows of each table."""
    if "table_rows" in cfg:
        return [int(r) for r in cfg["table_rows"]]
    return [int(cfg["rows_per_table"])] * int(cfg["num_tables"])


def pooling(cfg: dict) -> list:
    """Lookups per sample of each table."""
    if "pooling" in cfg:
        return [int(n) for n in cfg["pooling"]]
    return [int(cfg["lookups_per_table"])] * int(cfg["num_tables"])


def row_offsets(cfg: dict) -> np.ndarray:
    """(T + 1,) int64: the first global id of each table, then the total."""
    return np.concatenate([[0], np.cumsum(table_rows(cfg))]).astype(np.int64)


def table_of(offsets: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """The table of each global id, for ``offsets`` from :func:`row_offsets`
    (``ids // rows_per_table`` where the tables are equal)."""
    return np.searchsorted(offsets, ids, side="right") - 1


def _label(rng, dense):
    # CTR label correlated with the dense features (a learnable signal)
    logits = dense[:, 0] - 0.5 * dense[:, 1]
    return (
        rng.random(dense.shape[0]) < 1.0 / (1.0 + np.exp(-logits))
    ).astype(np.float32)


def dlrm_batches(*, seed, num_tables, rows_per_table, lookups_per_table,
                 batch_size, s, num_dense_features, table_rows=None,
                 pooling=None):
    """Endless stream of (global ids int64, {"dense", "label"}), one numpy
    Generator drawn in step order.

    Without ``table_rows`` and ``pooling``, ids are (B, T, L) drawn as one
    block, as ``repro.data.synthetic.dlrm_batches`` draws them; global id =
    table * rows_per_table + local id. With them, each table draws its own
    (B, L_t) ids from its own Zipf over its own rows, table by table, as
    ``repro.data.synthetic.dlrm_batches_group`` draws them; global id =
    the table's first row + local id. Ids are (B, T, L) where every table
    has the same pooling, else (B, sum of L_t) with each table's columns
    together, in table order. The dense features and labels follow."""
    rng = np.random.default_rng(seed)
    if table_rows is None and pooling is None:
        offs = (np.arange(num_tables, dtype=np.int64) * rows_per_table)[
            None, :, None
        ]

        def draw():
            return sample_ids(
                rng, rows_per_table,
                (batch_size, num_tables, lookups_per_table), s,
            ) + offs
    else:
        rows = table_rows or [rows_per_table] * num_tables
        pool = pooling or [lookups_per_table] * num_tables
        starts = np.concatenate([[0], np.cumsum(rows)[:-1]]).astype(np.int64)
        join = np.stack if len(set(pool)) == 1 else np.concatenate

        def draw():
            return join([sample_ids(rng, r, (batch_size, n), s) + o
                         for r, n, o in zip(rows, pool, starts)], axis=1)
    while True:
        ids = draw()
        dense = rng.standard_normal(
            (batch_size, num_dense_features)
        ).astype(np.float32)
        yield ids, {"dense": dense, "label": _label(rng, dense)}


def stream_kwargs(cfg: dict, mix: dict, seed: int) -> dict:
    """Generator arguments for one (configuration, traffic mix, seed)."""
    kw = dict(
        seed=seed,
        num_tables=cfg["num_tables"],
        rows_per_table=cfg.get("rows_per_table"),
        lookups_per_table=cfg.get("lookups_per_table"),
        batch_size=cfg["batch_size"],
        s=LOCALITY_S[mix["locality"]],
        num_dense_features=cfg["num_dense_features"],
    )
    if "table_rows" in cfg or "pooling" in cfg:
        kw.update(table_rows=table_rows(cfg), pooling=pooling(cfg))
    return kw


def _produce(kwargs, q, stop):
    """Producer body: generate batches in order until told to stop."""
    for item in dlrm_batches(**kwargs):
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                break
            except queue.Full:
                continue
        if stop.is_set():
            return


class Producer:
    """Runs :func:`dlrm_batches` in a spawned process, ``depth`` batches
    ahead. ``get()`` returns the next batch and adds the time it waited to
    ``wait_s``; ``close()`` stops the process and waits for it."""

    def __init__(self, kwargs: dict, depth: int = 8):
        ctx = mp.get_context("spawn")
        self._q = ctx.Queue(maxsize=depth)
        self._stop = ctx.Event()
        self._proc = ctx.Process(
            target=_produce, args=(kwargs, self._q, self._stop), daemon=True
        )
        self._proc.start()
        self.wait_s = 0.0

    def get(self):
        t0 = time.perf_counter()
        while True:
            try:
                item = self._q.get(timeout=1.0)
                break
            except queue.Empty:
                if not self._proc.is_alive():
                    raise RuntimeError("the batch producer died")
        self.wait_s += time.perf_counter() - t0
        return item

    def close(self):
        self._stop.set()
        # drain before joining: a writer blocked on a full pipe never exits
        deadline = time.monotonic() + 30
        while self._proc.is_alive() and time.monotonic() < deadline:
            try:
                self._q.get(timeout=0.1)
            except queue.Empty:
                pass
        self._proc.join(timeout=5)
        if self._proc.is_alive():
            self._proc.kill()
            self._proc.join()
        self._q.close()
        self._q.join_thread()
