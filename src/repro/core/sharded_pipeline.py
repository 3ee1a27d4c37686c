"""Multi-device ScratchPipe (paper §VI-G): table-wise model parallelism.

The paper argues ScratchPipe extends to multi-GPU by instantiating one cache
manager per embedding-table partition — each device treats its partition as
an independent table, so no inter-device RAW hazards or index reordering
arise. ``ShardedScratchPipe`` realizes that: the global row space is range-
partitioned into N shards, each with its own host-table slice, Planner, and
scratchpad Storage; a mini-batch's ids are bucketed per shard and every
shard runs the same 6-stage schedule in lockstep. The [Train] stage receives
per-shard (storage, slots) so the model's gather/scatter runs against the
device that owns each row — on a real mesh the shards live on different
chips; here they are N independent buffers, which preserves all scheduling
and correctness semantics (tests/test_sharded_pipeline.py: bit-tight vs the
single-manager runtime).

Partitioning is either uniform (``num_shards`` equal ranges — the original
API) or follows a :class:`~repro.core.table_group.TableGroup`
(``from_group``: one cache manager per embedding table, the paper's natural
multi-table placement, with per-table scratchpad budgets).
"""
from __future__ import annotations

from typing import Any, Callable, Iterator, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core.host_table import HostEmbeddingTable, HostTraffic
from repro.core.pipeline import ScratchPipe, StepStats
from repro.core.runtime import register_runtime
from repro.core.table_group import TableGroup


class ShardedScratchPipe:
    def __init__(
        self,
        host_table: HostEmbeddingTable,
        num_slots: Union[int, Sequence[int]],
        num_shards: int,
        train_fn: Callable[[Sequence, Sequence, Any], Tuple[Sequence, Any]],
        *,
        past_window: int = 3,
        future_window: int = 2,
        policy: str = "lru",
        boundaries: Optional[Sequence[int]] = None,
        executor: str = "sync",
        planner: str = "host",
        pad_buckets: Optional[Sequence[int]] = None,
        kernel: str = "xla",
        precision: Union[str, Sequence[str], None] = None,
        tracer=None,
        metrics=None,
        supervise=None,
    ):
        """``train_fn(storages, slots_per_shard, batch)`` ->
        (new_storages, aux). ``num_slots`` is the per-shard scratchpad size
        (int: same for every shard; sequence: one per shard).
        ``boundaries`` (len num_shards+1) range-partitions the global row
        space; default: equal split (the table must then shard evenly).
        ``precision`` is the per-shard replica precision (str: uniform;
        sequence: one per shard — each manager owns its storage array, so
        MIXED per-table precisions are realized here, where the single-array
        ScratchPipe cannot). Per-shard ``num_slots`` stay NOMINAL (fp32-row
        byte budgets); each manager applies its own capacity multiplier."""
        rows = host_table.rows
        if boundaries is None:
            assert rows % num_shards == 0, (rows, num_shards)
            step = rows // num_shards
            boundaries = [i * step for i in range(num_shards + 1)]
        assert len(boundaries) == num_shards + 1, (len(boundaries), num_shards)
        assert boundaries[0] == 0 and boundaries[-1] == rows, boundaries
        self.boundaries = np.asarray(boundaries, dtype=np.int64)
        shard_rows = np.diff(self.boundaries)
        self.rows_per_shard = (
            int(shard_rows[0]) if len(set(shard_rows.tolist())) == 1 else None
        )
        self.num_shards = num_shards
        if isinstance(num_slots, int):
            num_slots = [num_slots] * num_shards
        assert len(num_slots) == num_shards, (num_slots, num_shards)
        if precision is None or isinstance(precision, str):
            precision = [precision or "fp32"] * num_shards
        precision = list(precision)
        assert len(precision) == num_shards, (precision, num_shards)
        self.precisions = tuple(precision)
        self.train_fn = train_fn
        self._pending: dict = {}

        def shard_train_fn(shard_idx):
            def fn(storage, slots, batch):
                # collect all shards' [Train] inputs; fire on the last shard
                self._pending[shard_idx] = (storage, slots)
                if len(self._pending) < self.num_shards:
                    return storage, None
                storages = [self._pending[i][0] for i in range(self.num_shards)]
                slots_all = [self._pending[i][1] for i in range(self.num_shards)]
                self._pending = {}
                new_storages, aux = self.train_fn(storages, slots_all, batch)
                for i, pipe in enumerate(self.pipes):
                    if i != shard_idx:
                        pipe.storage = new_storages[i]
                return new_storages[shard_idx], aux

            return fn

        # per-shard host table views (shared backing array: zero-copy slices)
        self.pipes: List[ScratchPipe] = []
        for i in range(num_shards):
            lo, hi = int(self.boundaries[i]), int(self.boundaries[i + 1])
            ht = HostEmbeddingTable(hi - lo, host_table.dim, data=host_table.data[lo:hi])
            self.pipes.append(
                ScratchPipe(
                    ht,
                    int(num_slots[i]),
                    shard_train_fn(i),
                    past_window=past_window,
                    future_window=future_window,
                    policy=policy,
                    executor=executor,
                    # planner="device": one device-resident PlanState per
                    # shard manager — per-shard id streams are variable
                    # length, which the device planner absorbs via its
                    # monotone pad buckets
                    planner=planner,
                    pad_buckets=pad_buckets,
                    # per-shard [Insert] fills run the same kernel axis; the
                    # [Train] kernels ride inside the caller's train_fn
                    kernel=kernel,
                    precision=precision[i],
                    tracer=tracer,
                    metrics=metrics,
                    # per-shard metric cells: same names, one label apart
                    obs_labels={"shard": str(i)},
                    # each shard manager gets its own watchdog over its own
                    # worker/d2h pools (repro.runtime.supervision)
                    supervise=supervise,
                )
            )

    @classmethod
    def from_group(
        cls,
        host_table: HostEmbeddingTable,
        num_slots: int,
        group: TableGroup,
        train_fn,
        **kw,
    ) -> "ShardedScratchPipe":
        """One cache manager per embedding table; ``num_slots`` total slots
        split into per-table budgets by the group's hot-set weights. Each
        table's ``precision`` (TableSpec) selects its manager's replica
        format — the supported route to MIXED per-table precisions — unless
        an explicit ``precision=`` kw overrides it."""
        assert host_table.rows == group.total_rows, (
            host_table.rows,
            group.total_rows,
        )
        kw.setdefault("precision", [t.precision for t in group.tables])
        return cls(
            host_table,
            group.slot_budgets(num_slots),
            group.num_tables,
            train_fn,
            boundaries=group.offsets.tolist(),
            **kw,
        )

    def _bucket(self, ids: np.ndarray) -> List[np.ndarray]:
        """Row ids -> per-shard LOCAL ids. ScratchPipe plans per table
        partition, so each shard receives only ids in its range; shapes vary
        per shard, which the per-shard [Train] slots reflect."""
        out = []
        flat = np.asarray(ids).ravel()
        for i in range(self.num_shards):
            lo, hi = int(self.boundaries[i]), int(self.boundaries[i + 1])
            mine = flat[(flat >= lo) & (flat < hi)] - lo
            out.append(mine)
        return out

    def run(self, stream: Iterator, lookahead_fn=None) -> List[StepStats]:
        """Lockstep: every shard advances one pipeline cycle per mini-batch
        round; the global [Train] fires once all shards reach their [Train]
        stage for the same batch. Returns the last shard's per-step stats
        (its aux carries the global loss)."""
        items = list(stream)  # materialize (lockstep needs aligned views)
        shard_streams = []
        for i in range(self.num_shards):
            shard_streams.append(
                [(self._bucket(np.asarray(ids))[i], batch) for ids, batch in items]
            )

        def look(i):
            def fn(k):
                nxt = self.pipes[i].planner._cycle + 1
                arr = shard_streams[i]
                return [arr[nxt + j][0] for j in range(k) if nxt + j < len(arr)]

            return fn

        outs: List[List[StepStats]] = [[] for _ in range(self.num_shards)]
        for step in range(len(items)):
            for i, pipe in enumerate(self.pipes):
                ids, batch = shard_streams[i][step]
                st = pipe.run_one_cycle(ids, batch, look(i))
                if st is not None:
                    outs[i].append(st)
        while any(p._window for p in self.pipes):
            for i, pipe in enumerate(self.pipes):
                if pipe._window:
                    st = pipe.drain_one_cycle()
                    if st is not None:
                        outs[i].append(st)
        self._barrier()
        return outs[-1]

    def _barrier(self) -> None:
        """Quiesce every shard's background (overlapped-executor) work."""
        for pipe in self.pipes:
            pipe._barrier()

    def close(self) -> None:
        """Release every shard's overlapped-executor worker threads."""
        for pipe in self.pipes:
            pipe.close()

    def run_one_cycle(self, ids, batch, lookahead_fn=None) -> Optional[StepStats]:
        """Admit one mini-batch (global ids) to every shard and advance each
        one cycle. ``lookahead_fn(k)`` yields upcoming GLOBAL id batches;
        they are bucketed per shard. Returns the last shard's completed
        StepStats (aux carries the global loss), or None while filling."""
        buckets = self._bucket(np.asarray(ids))
        fut_cache: dict = {}  # k -> per-batch bucket lists (bucket once,
        # not once per shard: S shards would otherwise redo the S-way scan)

        def look(i):
            def fn(k):
                if k not in fut_cache:
                    fut_cache[k] = [
                        self._bucket(np.asarray(b)) for b in lookahead_fn(k)
                    ]
                return [bb[i] for bb in fut_cache[k]]

            return fn

        st_last: Optional[StepStats] = None
        for i, pipe in enumerate(self.pipes):
            st = pipe.run_one_cycle(
                buckets[i], batch, look(i) if lookahead_fn else None
            )
            if i == self.num_shards - 1:
                st_last = st
        return st_last

    def drain_one_cycle(self) -> Optional[StepStats]:
        """Advance every shard one cycle without a new batch (lockstep
        drain). Returns the last shard's completed StepStats, if any."""
        st_last: Optional[StepStats] = None
        for i, pipe in enumerate(self.pipes):
            if pipe._window:
                st = pipe.drain_one_cycle()
                if i == self.num_shards - 1:
                    st_last = st
        return st_last

    def flush_to_host(self):
        for pipe in self.pipes:
            pipe.flush_to_host()

    # -- checkpoint/restart (crash-consistent, ANY lockstep boundary) ------ #
    def state_arrays(self) -> dict:
        """Per-shard delegation with shard-indexed keys (``shard<i>_<key>``).
        Must be called between lockstep cycles (every shard has fired or
        none has) — the aggregated [Train] input dict is then empty, which
        is asserted. Each shard's snapshot contains its own host-table
        SLICE, planner state, scratchpad, and hold window, so a restored
        N-shard run is bit-identical to the uninterrupted one."""
        assert not self._pending, "checkpoint only between lockstep cycles"
        out: dict = {}
        for i, pipe in enumerate(self.pipes):
            for k, v in pipe.state_arrays().items():
                out[f"shard{i}_{k}"] = v
        return out

    def load_state_arrays(self, arrays: dict) -> None:
        """Split shard-indexed keys and delegate. Shard host tables load
        IN PLACE, so the shared global backing array stays consistent."""
        self._pending = {}
        for i, pipe in enumerate(self.pipes):
            prefix = f"shard{i}_"
            sub = {
                k[len(prefix):]: v
                for k, v in arrays.items()
                if k.startswith(prefix)
            }
            if not sub:
                raise KeyError(f"checkpoint has no arrays for shard {i}")
            pipe.load_state_arrays(sub)

    @property
    def stats(self) -> List[StepStats]:
        """Last shard's per-step stats (its aux carries the global loss)."""
        return self.pipes[-1].stats

    def traffic(self) -> dict:
        """Aggregated byte counters across all shard managers."""
        agg = {k: HostTraffic() for k in ("host", "pcie", "hbm")}
        for pipe in self.pipes:
            for k, t in pipe.traffic().items():
                agg[k].read += t.read
                agg[k].written += t.written
        return agg


@register_runtime("sharded")
def _make_sharded(
    host_table,
    train_fn,
    *,
    num_slots,
    table_group=None,
    num_shards=None,
    slot_budgets=None,
    **kw,
) -> ShardedScratchPipe:
    """table_group: one shard per table (per-table budgets; explicit
    ``slot_budgets`` override the proportional split); otherwise a uniform
    ``num_shards`` range partition."""
    if table_group is not None:
        kw.setdefault("precision", [t.precision for t in table_group.tables])
        if slot_budgets is not None:
            return ShardedScratchPipe(
                host_table,
                list(slot_budgets),
                table_group.num_tables,
                train_fn,
                boundaries=table_group.offsets.tolist(),
                **kw,
            )
        return ShardedScratchPipe.from_group(
            host_table, num_slots, table_group, train_fn, **kw
        )
    if slot_budgets is not None:
        raise TypeError("sharded: slot_budgets requires table_group")
    return ShardedScratchPipe(
        host_table, num_slots, num_shards or 1, train_fn, **kw
    )
