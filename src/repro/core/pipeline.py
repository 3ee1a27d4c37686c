"""ScratchPipe: the pipelined always-hit embedding cache runtime (paper §IV).

Six-stage pipeline over mini-batches, one training iteration completing per
pipeline cycle at steady state:

    [Plan] -> [Collect] -> [Exchange] -> [Insert] -> [Train(fwd+bwd+update)]

Stage execution inside a cycle is deliberately ordered ADVERSARIALLY w.r.t.
the paper's RAW hazards — [Collect] of the newest in-flight batch runs
*before* [Insert]/[Train] of older batches — so any hold-window bug surfaces
as stale data instead of being masked by sequential execution. With the
paper's window (3 past + current + 2 future) execution is equivalent to
sequential training (tested bit-tight in tests/test_scratchpipe_properties).

``train_fn(storage, slots, batch) -> (storage, aux)`` is the [Train] stage —
any jitted computation that gathers from the scratchpad with ``slots`` and
updates those rows in place (DLRM step, LM embedding step, ...).

Executors (wall-clock fast path — see DESIGN.md "Wall-clock path"):

  * ``executor="sync"`` (default) — every stage of every in-flight batch
    runs on the calling thread in the hazard-adversarial order above. This
    is the engine the hazard property tests run against.
  * ``executor="overlapped"`` — the host-side [Collect] gather and [Insert]
    write-back run on a single background worker thread, and the [Exchange]
    d2h read of victim rows runs on a d2h thread, so the blocking
    device-sync leaves the critical path. Submission order equals the sync
    engine's execution order, and host-table operations all run on ONE
    worker, so every host read/write interleaving is identical to sync —
    the two executors are bit-identical (asserted in tests/test_fastpath).
    Completion is checked where the row is provably retired: a victim's
    write-back is submitted at its batch's [Insert] cycle, and the earliest
    batch that could re-gather that row from host [Collect]s one full cycle
    later (its [Plan] sits outside the future window, else the slot could
    not have been evicted) — by which point the ordered worker queue has
    the write-back ahead of the gather.

Dispatch discipline: empty-operand device calls are skipped outright
(zero-miss / zero-evict cycles launch nothing), [Insert]-fill can fuse into
the [Train] dispatch (``fused_train_fn``), and variable-length index
operands are padded to power-of-two buckets. The ``kernel="xla"|"pallas"``
axis selects the device-primitive implementation for the runtime's own
dispatches (the [Insert] fill here; the [Train] stage's gather/scatter
kernels ride inside ``train_fn``/``fused_train_fn`` — build the trainer
with the same ``kernel=``). Pad buckets double as the Pallas grid sizes, so
"pallas" keeps the same one-executable-per-bucket discipline — or a trace-derived adaptive
bucket set (``pad_buckets=``, see repro.traces.profiling.derive_pad_buckets)
— via drop-mode scatters / sliced reads, so the number of distinct XLA
executables stays O(log batch) instead of one per miss count.

Planner placement (``planner=``): ``"host"`` (default) runs the numpy
Planner on CPU; ``"device"`` keeps PlanState on-accelerator
(repro.core.plan_jax.DevicePlanner) — raw ids are all that cross h2d each
cycle, the dense id->slot translate feeds [Train] without ever visiting the
host, and only the small miss/evict vectors sync back for the
[Exchange]/host-table stages (overlapped with [Train] on the d2h worker
under ``executor="overlapped"``). Bit-identical to the host planner
(tests/test_device_planner.py).

The runtime also keeps per-tier byte counters ([Collect]/[Insert] host bytes,
[Exchange] PCIe bytes, [Train] HBM bytes) — these feed the calibrated
bandwidth model reproducing the paper's latency figures. Counters always
track LOGICAL (unpadded) bytes and are updated unconditionally, so both
executors and both dispatch paths report identical traffic. What actually
crosses the link, padding included, is counted only with a metrics registry
installed (``cache.h2d_rows``/``_bytes``, ``cache.d2h_rows``/``_bytes``).

Mixed precision (``precision="fp32"|"fp16"|"int8"``, core/quantize.py): the
host table keeps fp32 masters; the scratchpad holds quantized replicas.
``num_slots`` is then a BYTE budget in fp32-row units — fp16 holds 2x, int8
4x resident rows in the same allocation. Master rows quantize inside the
[Collect] gather (worker thread under overlapped; the h2d already moves
small rows), evictions dequantize on write-back, and the pcie/hbm counters
track the replica row size (== the fp32 size at fp32, so the default path's
counters are bitwise unchanged). Pair with a trainer built with the same
``precision=`` so [Train] uses the dequantizing gather.

h2d staging (fp32 replicas): [Collect] gathers the missed rows straight into
the head of a reused host block already padded to the operand's bucket, and
[Exchange] hands that whole block to ``jax.device_put`` — no fresh gather
output, no pad copy. The rows past the real ones are stale rows of an
earlier batch; their fill slots are the ``num_slots`` sentinel, which every
fill discards. A block is written again only after the transfer last put
from it has finished (``_StagingRing.wait``). Quantized replicas keep the
gather -> quantize -> ``pad_rows`` path.
"""
from __future__ import annotations

import collections
import dataclasses
from concurrent.futures import Future, ThreadPoolExecutor
from typing import (
    Any, Callable, Deque, Dict, Iterator, List, Optional, Sequence, Tuple,
)

import jax
import numpy as np

from repro.checkpoint.pack import pack_blob, unpack_blob
from repro.core import quantize as qz
from repro.core import scratchpad as sp
from repro.core.host_table import HostEmbeddingTable, HostTraffic
from repro.core.plan import Planner, PlanResult, pad_index, pad_len, pad_rows
from repro.core.runtime import register_runtime
from repro.core.table_group import TableGroup
from repro.obs import NULL_SPAN, resolve as obs_resolve
from repro.runtime.supervision import (
    OpSupervisor,
    SupervisedOp,
    SupervisePolicy,
    TransientOpError,
)


@dataclasses.dataclass
class StepStats:
    step: int
    n_lookups: int
    n_unique: int
    n_hits: int
    n_miss: int
    n_evict: int
    hit_lookups: int = 0  # lookup-level (non-unique) hit count
    by_table: Any = None  # per-table {hits, misses} (multi-table runs only)
    aux: Any = None

    @property
    def hit_rate(self) -> float:
        return self.n_hits / max(self.n_unique, 1)


@dataclasses.dataclass
class _InFlight:
    ids: np.ndarray
    batch: Any
    plan: Optional[PlanResult] = None
    host_rows: Optional[np.ndarray] = None  # [Collect] host->staging
    host_rows_f: Optional[SupervisedOp] = None  # overlapped: pending gather
    staging: Optional["_StagingBlock"] = None  # fp32: block host_rows heads
    evicted_dev: Optional[jax.Array] = None  # [Collect] device victim read
    fetched_dev: Optional[jax.Array] = None  # [Exchange] h2d
    evicted_host: Optional[np.ndarray] = None  # [Exchange] d2h
    evicted_host_f: Optional[SupervisedOp] = None  # overlapped: pending d2h
    stage: int = 0  # stages completed: 1=planned .. 4=inserted


#: PlanResult fields serialized per in-flight entry by the mid-stream
#: checkpoint (accessing them on a lazy DevicePlanResult triggers its one
#: d2h materialize, so a captured plan is always a plain host structure).
_PLAN_FIELDS = (
    "step", "slots", "miss_ids", "fill_slots", "evict_slots", "evict_ids",
    "n_unique", "n_hits", "hits_by_table", "misses_by_table",
)


# Operand padding now lives in repro.core.plan (shared by the pipeline, the
# device planner, and the static cache); these module-level aliases keep the
# pre-refactor import surface working.
_pad_len = pad_len
_pad_index = pad_index
_pad_rows = pad_rows


def _d2h_slice(arr, n: int):
    """d2h-worker task: sync the victim-row device read and drop padding.
    An int8 scratchpad reads back a (payload, scale) pair — both components
    cross d2h quantized; the host dequantizes at write-back."""
    if isinstance(arr, tuple):
        return tuple(np.asarray(a)[:n] for a in arr)
    return np.asarray(arr)[:n]


def _link_size(block) -> Tuple[int, int]:
    """(rows, bytes) of a padded row block that crosses the host-device
    link: one array, or an int8 scratchpad's (payload, scale) pair."""
    parts = block if isinstance(block, tuple) else (block,)
    return int(parts[0].shape[0]), sum(int(a.nbytes) for a in parts)


#: host staging blocks per runtime. A block is written at [Collect], put at
#: [Exchange] the next cycle and filled from the cycle after, so with three
#: its transfer has a whole cycle to finish before [Collect] writes it again.
STAGING_RING = 3


class _StagingBlock:
    """One reused host block of padded missed rows, and the device array
    last put from it, which has to be ready before the block is rewritten."""

    __slots__ = ("buf", "last_put")

    def __init__(self, buf: np.ndarray):
        self.buf = buf
        self.last_put: Optional[jax.Array] = None


class _StagingRing:
    """[Collect]'s gather target and [Exchange]'s h2d source for plain
    (unquantized) rows: :data:`STAGING_RING` blocks of one padded length,
    taken in ring order and allocated, and touched, once. A change of the
    padded length (bucket) drops them and allocates anew.

    ``counters`` holds the registry's ``staging_{allocs,reuses,waits}``
    counters when one is installed. ``put_copies``: the CPU backend's ``device_put``
    aliases a numpy array instead of copying it, so a reused block would
    rewrite a live ``jax.Array``; there the block is put as a copy."""

    def __init__(self, dim: int, dtype, buckets, *, put_copies: bool):
        self._dim = dim
        self._dtype = np.dtype(dtype)
        self._buckets = buckets
        self.put_copies = put_copies
        self.counters: Optional[Dict[str, Any]] = None
        self._len = 0
        self._blocks: List[_StagingBlock] = []
        self._next = 0

    def _count(self, name: str) -> None:
        if self.counters is not None:
            self.counters[name].inc()

    def take(self, n: int) -> _StagingBlock:
        """The next block for ``n`` rows, in ring order (main thread)."""
        p = pad_len(n, self._buckets)
        if p != self._len:
            self._len, self._blocks, self._next = p, [], 0
        i = self._next
        self._next = (i + 1) % STAGING_RING
        if i < len(self._blocks):
            self._count("staging_reuses")
            return self._blocks[i]
        buf = np.empty((p, self._dim), self._dtype)
        buf.fill(0)  # fault its pages in now, not in a step's gather
        block = _StagingBlock(buf)
        self._blocks.append(block)
        self._count("staging_allocs")
        return block

    def wait(self, block: _StagingBlock) -> None:
        """Return once the transfer last put from ``block`` has finished, so
        the block may be written (the host worker under overlapped)."""
        dev, block.last_put = block.last_put, None
        if dev is not None and not dev.is_ready():
            self._count("staging_waits")
            dev.block_until_ready()

    def source(self, block: _StagingBlock) -> np.ndarray:
        """What [Exchange] hands to ``device_put``: the whole padded block."""
        return block.buf.copy() if self.put_copies else block.buf


class ScratchPipe:
    def __init__(
        self,
        host_table: HostEmbeddingTable,
        num_slots: int,
        train_fn: Callable[[jax.Array, jax.Array, Any], Tuple[jax.Array, Any]],
        *,
        past_window: int = 3,
        future_window: int = 2,
        policy: str = "lru",
        pipelined: bool = True,
        storage_dtype=None,
        precision: Optional[str] = None,
        table_group: Optional[TableGroup] = None,
        slot_budgets=None,
        executor: str = "sync",
        fused_train_fn: Optional[Callable] = None,
        memoize_plan: bool = True,
        planner: str = "host",
        pad_buckets: Optional[Sequence[int]] = None,
        kernel: str = "xla",
        tracer=None,
        metrics=None,
        obs_labels: Optional[Dict[str, str]] = None,
        supervise: Optional[SupervisePolicy] = None,
    ):
        if executor not in ("sync", "overlapped"):
            raise ValueError(f"unknown executor {executor!r}")
        if planner not in ("host", "device"):
            raise ValueError(f"unknown planner placement {planner!r}")
        self.kernel = sp._check_kernel(kernel)
        self.host = host_table
        self.train_fn = train_fn
        self.fused_train_fn = fused_train_fn
        self.pipelined = pipelined
        self.executor = executor
        self.planner_placement = planner
        self.pad_buckets = tuple(sorted(pad_buckets)) if pad_buckets else None
        self.table_group = table_group
        # -- replica precision (core/quantize.py) --------------------------- #
        # ``num_slots`` is the BYTE budget in fp32-row units: a reduced
        # precision multiplies the resident row count (fp16 2x, int8 4x)
        # instead of shrinking the allocation. Explicit ``precision=`` must
        # agree with the table group's (uniform) per-table precision; mixed
        # per-table precisions need ShardedScratchPipe (one storage array
        # here = one dtype).
        group_prec = (
            table_group.uniform_precision() if table_group is not None else None
        )
        if precision is None:
            precision = group_prec or "fp32"
        elif group_prec is not None and precision != group_prec:
            raise ValueError(
                f"precision={precision!r} conflicts with the table group's "
                f"uniform precision {group_prec!r}"
            )
        self.precision = qz.check_precision(precision)
        if self.precision != "fp32" and storage_dtype is not None:
            raise ValueError(
                "storage_dtype is the fp32-path experiment knob; "
                "reduced precision is selected with precision= alone"
            )
        eff_slots = num_slots * qz.SLOT_MULTIPLIER[self.precision]
        if not pipelined:  # straw-man (§IV-B): depth-1, no hazards possible
            past_window, future_window = 0, 0
        if table_group is not None:
            if table_group.total_rows != host_table.rows:
                raise ValueError(
                    f"table_group covers {table_group.total_rows} rows, "
                    f"host table has {host_table.rows}"
                )
            budgets = (
                list(slot_budgets)
                if slot_budgets is not None
                else table_group.precision_slot_budgets(num_slots)
            )
            if sum(budgets) > eff_slots:
                raise ValueError(
                    f"slot budgets {budgets} exceed num_slots={eff_slots}"
                )
            row_offsets = table_group.offsets
            slot_ranges = table_group.slot_ranges(budgets)
        else:
            row_offsets = slot_ranges = None
        # -- telemetry (strictly opt-in; see repro.obs) --------------------- #
        # Resolved ONCE here; with both unset the hot loop sees only
        # `is None` branches and the shared NULL_SPAN singleton.
        self._tracer, self._metrics = obs_resolve(tracer, metrics)
        if planner == "device":
            # [Plan] state lives on-accelerator; raw ids are what cross h2d
            # each cycle, and the dense id->slot translate never runs on host
            from repro.core.plan_jax import DevicePlanner

            self.planner = DevicePlanner(
                host_table.rows,
                eff_slots,
                past_window=past_window,
                future_window=future_window,
                policy=policy,
                row_offsets=row_offsets,
                slot_ranges=slot_ranges,
                pad_buckets=self.pad_buckets,
            )
        else:
            self.planner = Planner(
                host_table.rows,
                eff_slots,
                past_window=past_window,
                future_window=future_window,
                policy=policy,
                row_offsets=row_offsets,
                slot_ranges=slot_ranges,
                memoize=memoize_plan,
                tracer=self._tracer,
            )
        import jax.numpy as jnp

        dt = storage_dtype or jnp.dtype(host_table.data.dtype.name)
        self.storage = sp.make_storage(
            eff_slots, host_table.dim, dt, precision=self.precision
        )
        self.num_slots = eff_slots
        self.nominal_slots = num_slots  # the fp32-row byte budget
        # bytes ONE replica row moves over pcie/hbm (== host.row_bytes at
        # fp32, so the default path's counters are bitwise unchanged)
        self._row_bytes = qz.row_bytes(
            host_table.dim, self.precision, host_table.data.dtype.itemsize
        )
        self.pcie = HostTraffic()  # read = d2h, written = h2d
        self.hbm = HostTraffic()  # device-side traffic ([Train] + fills)
        self._window: Deque[_InFlight] = collections.deque()
        self._stats: List[StepStats] = []
        self.future_window = future_window
        # overlapped executor: ONE ordered host worker (gathers and
        # write-backs interleave exactly as the sync engine executes them)
        # plus a d2h thread that absorbs the blocking device sync.
        self._host_pool: Optional[ThreadPoolExecutor] = None
        self._d2h_pool: Optional[ThreadPoolExecutor] = None
        self._pending: Deque[SupervisedOp] = collections.deque()
        if executor == "overlapped":
            self._host_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="scratchpipe-host"
            )
            self._d2h_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="scratchpipe-d2h"
            )
        # Pool-submitted work is span-wrapped at construction (not per
        # cycle), so spans land on the worker/d2h thread that runs them and
        # the on-path allocates no closures in the loop either.
        self._gather_fn = self.host.gather
        if self.precision != "fp32":
            # master -> replica quantization runs INSIDE the gather fn, so
            # under executor="overlapped" it lands on the host worker thread
            # (off the critical path) and the h2d transfer below already
            # moves the small quantized rows.
            def _gather_quantized(ids, _g=self.host.gather, _p=self.precision):
                return qz.quantize_rows_np(_g(ids), _p)

            self._gather_fn = _gather_quantized
        self._writeback_fn = self._writeback
        self._d2h_slice_fn = _d2h_slice
        if self._tracer is not None:
            self._gather_fn = self._tracer.wrap(
                "collect.gather", self._gather_fn, cat="host"
            )
            self._writeback_fn = self._tracer.wrap(
                "insert.writeback", self._writeback, cat="host"
            )
            self._d2h_slice_fn = self._tracer.wrap(
                "exchange.d2h", _d2h_slice, cat="d2h"
            )
        self._ring: Optional[_StagingRing] = None
        if self.precision == "fp32":  # quantized rows keep the pad copy
            self._ring = _StagingRing(
                host_table.dim,
                host_table.data.dtype,
                self.pad_buckets,
                put_copies=next(iter(self.storage.devices())).platform == "cpu",
            )
        self._mc = None
        if self._metrics is not None:
            self._setup_metrics(dict(obs_labels or {}))
        # -- supervised execution (repro.runtime.supervision) --------------- #
        # Only meaningful for the overlapped executor: the sync engine has
        # no worker threads to watch. With supervise=None the op plumbing
        # below reduces to the plain future semantics (result / raise).
        self.supervise = supervise
        self._sv: Optional[OpSupervisor] = None
        if supervise is not None and executor == "overlapped":
            self._sv = OpSupervisor(
                supervise, metrics=self._metrics, tracer=self._tracer
            )

    def _setup_metrics(self, labels: Dict[str, str]) -> None:
        """Eagerly create counter cells and register lazy gauges. Byte
        gauges read the existing unconditional HostTraffic totals at
        snapshot time; occupancy/memo gauges probe planner state the same
        way — nothing here adds per-cycle work."""
        m = self._metrics
        labels.setdefault("runtime", "scratchpipe" if self.pipelined else "strawman")
        self._mc = {
            k: m.counter(f"cache.{k}", **labels)
            for k in ("cycles", "lookups", "unique", "hits", "misses",
                      "evicts", "fills",
                      # rows and bytes handed to the link, padding included
                      "h2d_rows", "h2d_bytes", "d2h_rows", "d2h_bytes")
        }
        if self._ring is not None:
            # h2d staging blocks: allocated, reused, and reuses that found
            # the block's last transfer not yet finished
            for k in ("staging_allocs", "staging_reuses", "staging_waits"):
                self._mc[k] = m.counter(f"cache.{k}", **labels)
            self._ring.counters = self._mc
        self._tbl_counters = None
        if self.table_group is not None:
            self._tbl_counters = [
                (m.counter("cache.hits", table=t.name, **labels),
                 m.counter("cache.misses", table=t.name, **labels))
                for t in self.table_group.tables
            ]
        m.gauge("scratchpad.bytes", fn=lambda: sp.storage_bytes(self.storage),
                dtype=self.precision, **labels)
        m.gauge("traffic.pcie.h2d_bytes", fn=lambda: self.pcie.written, **labels)
        m.gauge("traffic.pcie.d2h_bytes", fn=lambda: self.pcie.read, **labels)
        m.gauge("traffic.hbm.read_bytes", fn=lambda: self.hbm.read, **labels)
        m.gauge("traffic.hbm.written_bytes", fn=lambda: self.hbm.written, **labels)
        m.gauge("traffic.host.read_bytes",
                fn=lambda: self.host.traffic.read, **labels)
        m.gauge("traffic.host.written_bytes",
                fn=lambda: self.host.traffic.written, **labels)
        m.gauge("planner.occupancy", fn=lambda: self.planner.occupancy, **labels)
        m.gauge("planner.hold_occupancy", fn=self._hold_occupancy, **labels)
        m.gauge("planner.memo.hits", fn=lambda: self._memo_counts()[0], **labels)
        m.gauge("planner.memo.misses", fn=lambda: self._memo_counts()[1], **labels)

    def _hold_occupancy(self) -> int:
        """Slots currently held by the RAW window (hold register != 0)."""
        h = getattr(self.planner, "hold", None)
        if h is not None:  # host planner: numpy shift register
            return int(np.count_nonzero(h))
        states = getattr(self.planner, "_states", None)
        if states:  # device planner: per-table on-accelerator registers
            return int(sum(int(np.count_nonzero(np.asarray(s.hold)))
                           for s in states))
        return 0

    def _memo_counts(self) -> Tuple[int, int]:
        """(hits, misses) of the planner's per-batch memo (host planner
        digest cache / device planner prep cache)."""
        for attr in ("_digests", "_prep"):
            c = getattr(self.planner, attr, None)
            if c is not None:
                return c.hits, c.misses
        return (0, 0)

    def _span(self, name: str, cat: str = "train"):
        t = self._tracer
        return NULL_SPAN if t is None else t.span(name, cat)

    # ------------------------------------------------------------------ #
    # overlapped-executor plumbing
    # ------------------------------------------------------------------ #
    def _submit_host(self, fn, *args) -> SupervisedOp:
        if self._host_pool is None:
            # degraded mid-run: execute inline (sync semantics)
            return SupervisedOp.completed(fn, args, fn(*args))
        op = SupervisedOp(fn, args)
        op.future = self._host_pool.submit(fn, *args)
        self._pending.append(op)
        # reap retired work each cycle: surfaces worker exceptions promptly
        # and keeps the pending deque from growing with the run length
        while self._pending and self._pending[0].probe_done():
            head = self._pending[0]
            if self._sv is None:
                self._pending.popleft().result_now()
                continue
            try:
                head.wait(self._sv.policy.op_timeout)
            except TransientOpError as e:
                self._sv.note_failure(e)
                self._recover_pending()
                break
            self._pending.popleft()
        return op

    def _barrier(self) -> None:
        """Wait for every outstanding background operation (host gathers,
        write-backs, d2h copies). Called at run/drain boundaries and before
        anything reads host-table or traffic state from outside the
        pipeline's own ordered schedule. Under supervision a failed or
        stalled op triggers ordered inline recovery instead of raising."""
        if self._sv is None:
            while self._pending:
                self._pending.popleft().result_now()
            return
        while self._pending:
            head = self._pending[0]
            try:
                head.wait(self._sv.policy.op_timeout)
            except TransientOpError as e:
                self._sv.note_failure(e)
                self._recover_pending()
                return
            self._pending.popleft()

    def _op_result(self, op: SupervisedOp):
        """Resolve a host-queue op on the MAIN thread. Under supervision this
        settles every EARLIER op first (submission order), so a failure
        upstream of ``op`` is recovered before a value computed against
        tainted host state could be consumed."""
        if self._sv is None:
            return op.result_now()
        while not op.settled and self._pending:
            head = self._pending[0]
            try:
                head.wait(self._sv.policy.op_timeout)
            except TransientOpError as e:
                self._sv.note_failure(e)
                self._recover_pending()
                break
            self._pending.popleft()
        return op.value if op.settled else op.result_now()

    def _recover_pending(self) -> None:
        """Ordered recovery of the host-op queue after a failure/timeout:
        every op from the first failed one onward is recomputed INLINE in
        original submission order. Host ops are pure reads (gather) or
        idempotent writes keyed by evict ids (scatter), so the replay
        reproduces the sync engine's host-table interleaving exactly —
        bit-parity survives the fault. Retries are bounded by the policy;
        repeated incidents degrade the pipe to the sync executor."""
        sv = self._sv
        with self._span("ft.recover", cat="host"):
            poisoned = False
            while self._pending:
                op = self._pending.popleft()
                if not poisoned:
                    try:
                        op.wait(sv.policy.op_timeout)
                        continue
                    except TransientOpError as e:
                        sv.note_failure(e)
                        poisoned = True
                # quiesce before replaying: never run the op inline while a
                # (stalled) worker might still be executing it
                f = op.future
                if f is not None and not f.done() and not f.cancel():
                    try:
                        op.wait(sv.policy.op_timeout * 5)
                    except TransientOpError:
                        pass
                if not op.settled:
                    sv.run_inline(op)
        if sv.note_incident():
            self._degrade_to_sync()

    def _degrade_to_sync(self) -> None:
        """Graceful degradation after repeated worker faults: settle every
        in-flight op, abandon the pools, and run all subsequent stages
        inline (``executor="sync"``). Output is unchanged — sync order IS
        the reference order — only overlap is lost."""
        if self._host_pool is None and self._d2h_pool is None:
            return
        self._sv.note_degraded()
        for e in self._window:
            if e.host_rows_f is not None:
                e.host_rows = (
                    e.host_rows_f.value
                    if e.host_rows_f.settled
                    else self._sv.value_or_inline(e.host_rows_f)
                )
                e.host_rows_f = None
            if e.evicted_host_f is not None:
                e.evicted_host = (
                    e.evicted_host_f.value
                    if e.evicted_host_f.settled
                    else self._sv.value_or_inline(e.evicted_host_f)
                )
                e.evicted_host_f = None
        pools = [p for p in (self._host_pool, self._d2h_pool) if p is not None]
        self._host_pool = self._d2h_pool = None
        self.executor = "sync"
        for p in pools:
            # queued work (e.g. device-plan materializes) still completes;
            # the threads then exit — nothing new is ever submitted
            p.shutdown(wait=False)

    def _dequant(self, rows):
        """replica -> master: dequantize written-back rows (identity at
        fp32). Runs host-side, on the worker thread under overlapped."""
        if self.precision == "fp32":
            return rows
        return qz.dequantize_rows_np(rows, self.precision)

    def _d2h_value(self, d2h):
        """Resolve a d2h staging value: a SupervisedOp (overlapped — with
        inline recompute under supervision; the victim device read is pure,
        so a recompute is byte-identical), a plain Future, or an already
        materialized host array."""
        if isinstance(d2h, SupervisedOp):
            if self._sv is None or d2h.settled:
                return d2h.result_now()
            return self._sv.value_or_inline(d2h)
        if isinstance(d2h, Future):
            return d2h.result()
        return d2h

    def _writeback(self, evict_ids: np.ndarray, d2h) -> None:
        """Host-worker task: wait for the victims' d2h, then scatter. Runs
        strictly after every earlier-submitted gather (one ordered worker)."""
        self.host.scatter(evict_ids, self._dequant(self._d2h_value(d2h)))

    def close(self) -> None:
        """Quiesce and release the overlapped executor's worker threads.
        Idempotent; a no-op for the sync executor. Long-lived processes that
        build many runtimes should call this (the threads are non-daemon and
        otherwise live until interpreter exit)."""
        self._barrier()
        for pool in (self._host_pool, self._d2h_pool):
            if pool is not None:
                pool.shutdown(wait=True)
        self._host_pool = self._d2h_pool = None

    # ------------------------------------------------------------------ #
    # stages
    # ------------------------------------------------------------------ #
    def _stage_plan(self, entry: _InFlight, lookahead: List[np.ndarray]):
        with self._span("plan"):
            entry.plan = self.planner.plan(entry.ids, lookahead)
            if self._d2h_pool is not None and hasattr(
                entry.plan, "start_materialize"
            ):
                # device planner + overlapped executor: pull the miss/evict
                # ids back on the d2h worker so the sync overlaps [Train]
                entry.plan.start_materialize(self._d2h_pool, tracer=self._tracer)

    def _collect_into(self, ids: np.ndarray, block: _StagingBlock):
        """[Collect] into a staging block: wait out the block's last
        transfer, then gather the rows over its head. Pure given the host
        table, so a supervised inline recompute rewrites the same bytes."""
        self._ring.wait(block)
        return self._gather_fn(ids, out=block.buf[: ids.size])

    def _stage_collect(self, entry: _InFlight):
        with self._span("collect"):
            p = entry.plan
            if p.miss_ids.size:
                fn, args = self._gather_fn, (p.miss_ids,)
                if self._ring is not None:
                    # taken here, in ring order, even when a worker gathers
                    entry.staging = self._ring.take(p.miss_ids.size)
                    fn, args = self._collect_into, (p.miss_ids, entry.staging)
                if self._host_pool is not None:
                    entry.host_rows_f = self._submit_host(fn, *args)
                else:
                    entry.host_rows = fn(*args)  # host read
            if p.evict_slots.size:
                # pad victim reads to the pow-2 bucket (slot 0 is always safe
                # to read); the d2h side slices the real rows back out
                entry.evicted_dev = sp.read(
                    self.storage, pad_index(p.evict_slots, 0, self.pad_buckets)
                )
            self.hbm.read += p.evict_slots.size * self._row_bytes

    def _stage_exchange(self, entry: _InFlight):
        with self._span("exchange"):
            p = entry.plan
            mc = self._mc
            if p.miss_ids.size:
                rows = (
                    self._op_result(entry.host_rows_f)
                    if entry.host_rows_f is not None
                    else entry.host_rows
                )
                block = entry.staging
                with self._span("exchange.pad", cat="host"):
                    if block is not None:  # gathered into a padded block
                        rows = self._ring.source(block)
                    elif isinstance(rows, tuple):  # int8: (payload, scale)
                        rows = tuple(pad_rows(r, self.pad_buckets) for r in rows)
                    else:  # quantized, or restored from a checkpoint
                        rows = pad_rows(rows, self.pad_buckets)
                with self._span("exchange.h2d", cat="h2d"):
                    entry.fetched_dev = jax.device_put(rows)
                if block is not None:
                    block.last_put = entry.fetched_dev
                if mc is not None:
                    n, nbytes = _link_size(rows)
                    mc["h2d_rows"].inc(n)
                    mc["h2d_bytes"].inc(nbytes)
            n_evict = int(p.evict_slots.size)
            if n_evict:
                if self._d2h_pool is not None:
                    op = SupervisedOp(
                        self._d2h_slice_fn, (entry.evicted_dev, n_evict)
                    )
                    op.future = self._d2h_pool.submit(
                        self._d2h_slice_fn, entry.evicted_dev, n_evict
                    )
                    entry.evicted_host_f = op
                else:
                    entry.evicted_host = self._d2h_slice_fn(
                        entry.evicted_dev, n_evict
                    )  # d2h
                if mc is not None:
                    # the d2h copies the whole padded read back
                    n, nbytes = _link_size(entry.evicted_dev)
                    mc["d2h_rows"].inc(n)
                    mc["d2h_bytes"].inc(nbytes)
            self.pcie.written += p.miss_ids.size * self._row_bytes
            self.pcie.read += p.evict_slots.size * self._row_bytes

    def _stage_insert_host(self, entry: _InFlight):
        """[Insert], host half: write evicted (dirty, trained) rows back."""
        with self._span("insert_host"):
            p = entry.plan
            if p.evict_ids.size:
                if self._host_pool is not None:
                    self._submit_host(
                        self._writeback_fn, p.evict_ids, entry.evicted_host_f
                    )
                else:
                    with self._span("insert.writeback", cat="host"):
                        self.host.scatter(
                            p.evict_ids, self._dequant(entry.evicted_host)
                        )

    def _stage_insert_fill(self, entry: _InFlight):
        """[Insert], device half: fill fetched rows into their slots."""
        with self._span("insert_fill"):
            p = entry.plan
            if p.fill_slots.size:
                self.storage = sp.fill(
                    self.storage,
                    pad_index(p.fill_slots, self.num_slots, self.pad_buckets),
                    entry.fetched_dev,
                    kernel=self.kernel,
                )
            self.hbm.written += p.fill_slots.size * self._row_bytes

    def _stage_train(
        self, entry: _InFlight, fused_entry: Optional[_InFlight] = None
    ) -> StepStats:
        with self._span("train"):
            return self._train_body(entry, fused_entry)

    def _train_body(
        self, entry: _InFlight, fused_entry: Optional[_InFlight]
    ) -> StepStats:
        p = entry.plan
        if fused_entry is not None:
            # one dispatch: the younger batch's [Insert]-fill rides inside
            # this batch's [Train] executable (order — fill, then train — is
            # exactly the split engine's intra-cycle order)
            fp = fused_entry.plan
            self.storage, aux = self.fused_train_fn(
                self.storage,
                pad_index(fp.fill_slots, self.num_slots, self.pad_buckets),
                fused_entry.fetched_dev,
                p.slots,
                entry.batch,
            )
            self.hbm.written += fp.fill_slots.size * self._row_bytes
        else:
            self.storage, aux = self.train_fn(self.storage, p.slots, entry.batch)
        # [Train] HBM traffic: gather reads + coalesced scatter read-mod-write
        self.hbm.read += p.slots.size * self._row_bytes
        self.hbm.read += p.n_unique * self._row_bytes
        self.hbm.written += p.n_unique * self._row_bytes
        by_table = None
        if p.hits_by_table is not None:
            by_table = {"hits": p.hits_by_table, "misses": p.misses_by_table}
        st = StepStats(
            step=p.step,
            n_lookups=int(p.slots.size),
            n_unique=p.n_unique,
            n_hits=p.n_hits,
            n_miss=int(p.miss_ids.size),
            n_evict=int(p.evict_slots.size),
            hit_lookups=int(p.slots.size),  # always-hit at [Train] (§IV)
            by_table=by_table,
            aux=aux,
        )
        self._stats.append(st)
        mc = self._mc
        if mc is not None:
            mc["cycles"].inc()
            mc["lookups"].inc(st.n_lookups)
            mc["unique"].inc(st.n_unique)
            mc["hits"].inc(st.n_hits)
            mc["misses"].inc(st.n_miss)
            mc["evicts"].inc(st.n_evict)
            mc["fills"].inc(int(p.fill_slots.size))
            if by_table is not None and self._tbl_counters is not None:
                for (ch, cm), h, m in zip(
                    self._tbl_counters, by_table["hits"], by_table["misses"]
                ):
                    ch.inc(int(h))
                    cm.inc(int(m))
        return st

    # ------------------------------------------------------------------ #
    # pipeline driver
    # ------------------------------------------------------------------ #
    def run(
        self, stream: Iterator[Tuple[np.ndarray, Any]], lookahead_fn=None
    ) -> List[StepStats]:
        """stream yields (sparse_ids, batch_payload). ``lookahead_fn(k)``
        returns the ids of the next k mini-batches WITHOUT consuming them
        (see repro.data.lookahead). Returns per-step stats (train order)."""
        if not self.pipelined:
            return self._run_sequential(stream, lookahead_fn)
        out: List[StepStats] = []
        it = iter(stream)
        draining = False
        while True:
            with self._span("cycle"):
                if not draining:
                    # Streams exposing ``exhausted`` (LookaheadStream,
                    # TraceReplayStream) are asked directly — a short
                    # look-ahead window near the end already told them, so
                    # the drain decision never rests on a sentinel next()
                    # probe.
                    draining = bool(getattr(stream, "exhausted", False))
                if not draining:
                    # the wait for the data stream, as the program sees it
                    with self._span("input", cat="input"):
                        try:
                            ids, batch = next(it)
                        except StopIteration:
                            draining = True
                        else:
                            la = (
                                lookahead_fn(self.future_window)
                                if lookahead_fn
                                else []
                            )
                    if not draining:
                        entry = _InFlight(np.asarray(ids), batch)
                        self._stage_plan(entry, la)
                        entry.stage = 1
                        self._window.append(entry)
                self._advance_cycle(out)
            if draining and not self._window:
                break
        self._barrier()
        return out

    def _advance_cycle(self, out: List[StepStats]):
        """One pipeline cycle: every in-flight entry advances exactly one
        stage (entries entered on different cycles, so their stage indices
        are all distinct). Execution order inside the cycle is the
        hazard-adversarial one — the newest batch's [Collect] reads host and
        scratchpad state BEFORE the older batches' [Insert] write-back and
        [Train] update run. A missing hold-window rule therefore produces
        stale reads (caught by the property tests) instead of being hidden
        by sequential execution."""
        by_stage = {e.stage: e for e in self._window}
        if 1 in by_stage:
            self._stage_collect(by_stage[1])
        if 2 in by_stage:
            self._stage_exchange(by_stage[2])
        e3 = by_stage.get(3)
        e4 = by_stage.get(4)
        if e3 is not None:
            self._stage_insert_host(e3)
        fuse = (
            self.fused_train_fn is not None
            and e4 is not None
            and e3 is not None
            and e3.plan.fill_slots.size > 0
        )
        if e3 is not None and not fuse:
            self._stage_insert_fill(e3)
        if e4 is not None:
            out.append(self._stage_train(e4, fused_entry=e3 if fuse else None))
            self._window.remove(e4)
        for s in (1, 2, 3):
            if s in by_stage:
                by_stage[s].stage = s + 1
        if e4 is not None:
            # the last references to the retired batch: its ids, plan, host
            # rows and device buffers are released here
            with self._span("retire", cat="host"):
                del by_stage, e4

    # -- incremental driving (lockstep multi-shard execution, §VI-G) ------- #
    def run_one_cycle(self, ids, batch, lookahead_fn=None) -> Optional[StepStats]:
        """Plan one new mini-batch and advance the pipeline one cycle. The
        unpipelined straw-man completes the whole step immediately (the
        EmbeddingCacheRuntime contract) — its zero-width hold windows are
        only sound when stages never interleave across batches."""
        if not self.pipelined:
            return self._step_sequential(np.asarray(ids), batch)
        entry = _InFlight(np.asarray(ids), batch)
        la = lookahead_fn(self.future_window) if lookahead_fn else []
        self._stage_plan(entry, la)
        entry.stage = 1
        self._window.append(entry)
        out: List[StepStats] = []
        self._advance_cycle(out)
        return out[0] if out else None

    def drain_one_cycle(self) -> Optional[StepStats]:
        """Advance one cycle without a new batch (pipeline drain)."""
        out: List[StepStats] = []
        self._advance_cycle(out)
        if not self._window:
            self._barrier()
        return out[0] if out else None

    def _step_sequential(self, ids: np.ndarray, batch) -> StepStats:
        """One full straw-man step: Plan/Collect/Exchange/Insert/Train
        back-to-back. The fused dispatch merges the batch's own
        [Insert]-fill into its [Train] call."""
        entry = _InFlight(ids, batch)
        self._stage_plan(entry, [])
        self._stage_collect(entry)
        self._stage_exchange(entry)
        self._stage_insert_host(entry)
        if self.fused_train_fn is not None and entry.plan.fill_slots.size:
            return self._stage_train(entry, fused_entry=entry)
        self._stage_insert_fill(entry)
        return self._stage_train(entry)

    def _run_sequential(self, stream, lookahead_fn) -> List[StepStats]:
        """Straw-man (§IV-B): dynamic cache, no pipelining — every batch runs
        the five stages back-to-back."""
        out = [
            self._step_sequential(np.asarray(ids), batch)
            for ids, batch in stream
        ]
        self._barrier()
        return out

    # ------------------------------------------------------------------ #
    def flush_to_host(self):
        """Write every cached (dirty) row back to the host table."""
        self._barrier()
        # bind once: the device planner's slot_to_id is a property that
        # performs a full per-table d2h snapshot per access
        slot_to_id = self.planner.slot_to_id
        live = np.flatnonzero(slot_to_id >= 0)
        if live.size:
            vals = sp.read(self.storage, live)
            if isinstance(vals, tuple):
                vals = tuple(np.asarray(v) for v in vals)
            else:
                vals = np.asarray(vals)
            self.host.scatter(slot_to_id[live], self._dequant(vals))

    # -- checkpoint/restart (crash-consistent, ANY cycle) ------------------ #
    def _capture_plan(self, p) -> dict:
        """Materialize a plan (host PlanResult or lazy DevicePlanResult)
        into a plain host dict of `_PLAN_FIELDS`."""
        out: Dict[str, Any] = {}
        for f in _PLAN_FIELDS:
            v = getattr(p, f)
            if f in ("step", "n_unique", "n_hits"):
                out[f] = int(v)
            elif v is None:
                out[f] = None
            else:
                out[f] = np.asarray(v)
        return out

    @staticmethod
    def _np_maybe_tuple(x):
        if x is None:
            return None
        if isinstance(x, tuple):  # int8 staging: (payload, scale)
            return tuple(np.asarray(a) for a in x)
        return np.asarray(x)

    @staticmethod
    def _put_maybe_tuple(x):
        if x is None:
            return None
        if isinstance(x, tuple):
            return tuple(jax.device_put(np.asarray(a)) for a in x)
        return jax.device_put(np.asarray(x))

    def _capture_window(self) -> list:
        """Snapshot every in-flight entry to host structures. Pending ops
        are RESOLVED (not cancelled): after `_barrier()` the host queue is
        drained, and the d2h staging reads settle here. Non-destructive —
        the entries keep their (now settled) ops and the run continues."""
        entries = []
        for e in self._window:
            host_rows = e.host_rows
            if e.host_rows_f is not None:
                host_rows = self._op_result(e.host_rows_f)
            if e.staging is not None and host_rows is not None:
                # the real rows only, out of a block a later [Collect] reuses
                host_rows = np.array(host_rows)
            evicted_host = e.evicted_host
            if e.evicted_host_f is not None:
                evicted_host = self._d2h_value(e.evicted_host_f)
            entries.append({
                "ids": np.asarray(e.ids),
                "stage": int(e.stage),
                "batch": e.batch,  # tree_to_host'd inside pack_blob
                "plan": None if e.plan is None else self._capture_plan(e.plan),
                "host_rows": self._np_maybe_tuple(host_rows),
                "evicted_dev": self._np_maybe_tuple(e.evicted_dev),
                "fetched_dev": self._np_maybe_tuple(e.fetched_dev),
                "evicted_host": self._np_maybe_tuple(evicted_host),
            })
        return entries

    def _restore_entry(self, d: dict) -> _InFlight:
        e = _InFlight(np.asarray(d["ids"]), d["batch"])
        e.stage = int(d["stage"])
        if d["plan"] is not None:
            # always restored as a host PlanResult: the captured fields are
            # exactly what later stages consume, value-identical to what the
            # original (host or device) planner produced
            e.plan = PlanResult(**d["plan"])
        e.host_rows = d["host_rows"]
        e.evicted_dev = self._put_maybe_tuple(d["evicted_dev"])
        e.fetched_dev = self._put_maybe_tuple(d["fetched_dev"])
        ev = d["evicted_host"]
        if ev is not None:
            if self._host_pool is not None:
                # [Insert]-host under overlapped hands the op straight to the
                # write-back task: restore it pre-settled
                e.evicted_host_f = SupervisedOp.completed(
                    lambda *_a, _v=ev: _v, (), ev
                )
            else:
                e.evicted_host = ev
        return e

    def state_arrays(self) -> dict:
        """Crash-consistent host snapshot at ANY cycle: planner state +
        scratchpad contents + host table + traffic counters + the in-flight
        hold window (queued batches, staged rows, resolved d2h futures).
        `_barrier()` first drains the ordered host queue, so the host table
        and every captured staging value are exactly the state the sync
        engine would have at this cycle. Together with the deterministic
        look-ahead stream position (admitted-batch count) a kill-and-resume
        run is elementwise bit-identical to the uninterrupted one
        (tests/test_recovery.py)."""
        self._barrier()
        out = {"host_table": self.host.data}
        if isinstance(self.storage, sp.QuantStorage):
            out["storage"] = np.asarray(self.storage.data)
            out["storage_scale"] = np.asarray(self.storage.scale)
        else:
            out["storage"] = np.asarray(self.storage)
        for k, v in self.planner.state_dict().items():
            out[f"planner_{k}"] = v
        out["traffic"] = np.array(
            [self.pcie.read, self.pcie.written,
             self.hbm.read, self.hbm.written,
             self.host.traffic.read, self.host.traffic.written],
            dtype=np.int64,
        )
        if self._window:
            out["window"] = pack_blob(self._capture_window())
        return out

    def load_state_arrays(self, arrays: dict) -> None:
        self._barrier()
        self._window.clear()
        ht = np.asarray(arrays["host_table"])
        if ht.shape != self.host.data.shape:
            raise ValueError(
                f"checkpoint host table {ht.shape} != {self.host.data.shape}"
            )
        # IN-PLACE: sharded runtimes alias zero-copy slices of one global
        # table — replacing the array would silently detach the shard
        self.host.data[...] = ht
        self.host.reguard()
        if "storage_scale" in arrays:
            self.storage = sp.QuantStorage(
                jax.device_put(np.asarray(arrays["storage"])),
                jax.device_put(np.asarray(arrays["storage_scale"])),
            )
        else:
            self.storage = jax.device_put(np.asarray(arrays["storage"]))
        self.planner.load_state_dict(
            {k[len("planner_"):]: v for k, v in arrays.items()
             if k.startswith("planner_")}
        )
        if "traffic" in arrays:
            t = [int(x) for x in np.asarray(arrays["traffic"])]
            self.pcie.read, self.pcie.written = t[0], t[1]
            self.hbm.read, self.hbm.written = t[2], t[3]
            self.host.traffic.read, self.host.traffic.written = t[4], t[5]
        if "window" in arrays:
            for d in unpack_blob(arrays["window"]):
                self._window.append(self._restore_entry(d))

    @property
    def stats(self) -> List[StepStats]:
        return self._stats

    def traffic(self) -> dict:
        self._barrier()  # host counters settle with the worker queue
        return {"host": self.host.traffic, "pcie": self.pcie, "hbm": self.hbm}


@register_runtime("scratchpipe")
def _make_scratchpipe(host_table, train_fn, *, num_slots, **kw) -> ScratchPipe:
    return ScratchPipe(host_table, num_slots, train_fn, **kw)


@register_runtime("strawman")
def _make_strawman(host_table, train_fn, *, num_slots, **kw) -> ScratchPipe:
    kw.pop("pipelined", None)
    return ScratchPipe(host_table, num_slots, train_fn, pipelined=False, **kw)
