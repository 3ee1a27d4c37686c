"""h2d staging of missed rows (core/pipeline.py: _StagingRing).

  S1  the stale tail of a reused block never lands: poisoning every block's
      rows past the real ones with NaN right after the gather leaves losses,
      scratchpad and host tier bit-identical, per executor x planner, on
      recorded drift and flash_crowd traces.
  S2  a block is rewritten only after the transfer last put from it has
      finished, and ``cache.staging_waits`` counts the reuses that waited.
  S3  ``cache.staging_allocs`` / ``staging_reuses`` account for every cycle
      with misses, with allocations only where the padded length changes.
  S4  ``HostEmbeddingTable.gather(ids, out=)`` equals fancy indexing, checks
      bounds, verifies rows under the guard and counts traffic once.
"""
import jax
import numpy as np
import pytest

from repro import obs
from repro.configs.base import DLRMConfig
from repro.core.dlrm_runtime import DLRMTrainer
from repro.core.host_table import HostEmbeddingTable, RowCorruptionError
from repro.core.pipeline import STAGING_RING, ScratchPipe
from repro.core.plan import pad_len
from repro.core.table_group import TableGroup
from repro.traces import record_trace, scenario_batches
from repro.traces.format import TraceReader
from repro.traces.replay import TraceReplayStream

STEPS = 14
CFG = DLRMConfig(
    name="dlrm-staging-test",
    num_tables=2,
    rows_per_table=300,
    embed_dim=8,
    lookups_per_table=2,
    batch_size=8,
    num_dense_features=4,
    bottom_mlp=(16, 8),
    top_mlp=(16, 1),
)
SLOTS = 256
LABELS = {"runtime": "scratchpipe"}


@pytest.fixture(scope="module")
def traces(tmp_path_factory):
    root = tmp_path_factory.mktemp("staging_traces")
    group = TableGroup.from_config(CFG)
    out = {}
    for scenario in ("drift", "flash_crowd"):
        path = str(root / scenario)
        record_trace(
            path,
            group,
            scenario_batches(
                scenario, group, STEPS, batch_size=CFG.batch_size,
                lookups_per_table=CFG.lookups_per_table,
                num_dense_features=CFG.num_dense_features, seed=5,
            ),
        )
        out[scenario] = TraceReader(path)
    return out


def fresh(executor="sync", planner="host", **kw):
    group = TableGroup.from_config(CFG)
    host = HostEmbeddingTable(group.total_rows, CFG.embed_dim, seed=1)
    tr = DLRMTrainer(CFG, jax.random.key(0), lr=0.05)
    pipe = ScratchPipe(host, SLOTS, tr.train_fn, table_group=group,
                       executor=executor, planner=planner, **kw)
    return host, pipe


def hook_gather(pipe, after):
    """Call ``after(ids, out)`` right after each staged gather."""
    gather = pipe._gather_fn

    def staged(ids, out=None):
        rows = gather(ids, out=out)
        after(ids, out)
        return rows

    pipe._gather_fn = staged


def counter(m, name):
    return m.counter(f"cache.{name}", **LABELS).value


@pytest.mark.parametrize("scenario", ["drift", "flash_crowd"])
@pytest.mark.parametrize("planner", ["host", "device"])
@pytest.mark.parametrize("executor", ["sync", "overlapped"])
def test_poisoned_tail_never_lands(traces, scenario, executor, planner):
    def run(poison):
        host, pipe = fresh(executor, planner)
        tails = []
        if poison:
            def nan_tail(ids, out):
                block = out.base  # the whole padded block
                block[ids.size:] = np.nan
                tails.append(block.shape[0] - ids.size)

            hook_gather(pipe, nan_tail)
        stream = TraceReplayStream(traces[scenario], stop=STEPS)
        stats = pipe.run(stream, lookahead_fn=stream.peek_ids)
        pipe.flush_to_host()
        pipe.close()
        losses = np.array([float(s.aux["loss"]) for s in stats])
        return losses, np.asarray(pipe.storage), host.data.copy(), tails

    loss_a, stor_a, host_a, _ = run(False)
    loss_b, stor_b, host_b, tails = run(True)
    assert len(tails) == STEPS and min(tails) > 0  # every block had a tail
    assert np.isfinite(loss_a).all()
    np.testing.assert_array_equal(loss_b, loss_a)
    np.testing.assert_array_equal(stor_b, stor_a)
    np.testing.assert_array_equal(host_b, host_a)


class PendingPut:
    """A put whose transfer finishes only once something waits for it."""

    def __init__(self):
        self.waited = False

    def is_ready(self):
        return self.waited

    def block_until_ready(self):
        self.waited = True
        return self


@pytest.mark.parametrize("executor", ["sync", "overlapped"])
def test_reuse_waits_for_last_transfer(traces, executor):
    m = obs.MetricsRegistry()
    _, pipe = fresh(executor, metrics=m)
    stream = TraceReplayStream(traces["drift"], stop=STEPS)
    it = iter(stream)
    for _ in range(STAGING_RING + 1):
        pipe.run_one_cycle(*next(it), stream.peek_ids)
    pipe._barrier()
    assert counter(m, "staging_allocs") == STAGING_RING
    ring = pipe._ring
    block = ring._blocks[ring._next]  # the next cycle's [Collect] target
    assert block.last_put is not None  # put by an earlier [Exchange]
    pending = block.last_put = PendingPut()
    waited_before_write = []

    def check(ids, out):
        if out.base is block.buf:
            waited_before_write.append(pending.waited)

    hook_gather(pipe, check)
    waits = counter(m, "staging_waits")
    pipe.run_one_cycle(*next(it), stream.peek_ids)
    pipe._barrier()
    assert waited_before_write == [True]
    assert counter(m, "staging_waits") == waits + 1
    assert block.last_put is None  # consumed by the wait
    pipe.close()


@pytest.mark.parametrize("buckets", [None, (4, 8, 16, 32)])
@pytest.mark.parametrize("executor", ["sync", "overlapped"])
def test_staging_counters(traces, executor, buckets):
    m = obs.MetricsRegistry()
    _, pipe = fresh(executor, metrics=m, pad_buckets=buckets)
    stream = TraceReplayStream(traces["flash_crowd"], stop=STEPS)
    stats = pipe.run(stream, lookahead_fn=stream.peek_ids)
    pipe.close()
    lens = [pad_len(s.n_miss, buckets) for s in stats if s.n_miss]
    changes = sum(1 for i, p in enumerate(lens) if i == 0 or p != lens[i - 1])
    allocs, reuses = counter(m, "staging_allocs"), counter(m, "staging_reuses")
    assert allocs <= STAGING_RING * changes
    assert allocs + reuses == len(lens)
    assert reuses > 0
    if buckets is not None:
        assert changes > 1  # the bucket did change along the trace


def test_quantized_replicas_keep_the_pad_path(traces):
    m = obs.MetricsRegistry()
    group = TableGroup.from_config(CFG).with_precision("int8")
    host = HostEmbeddingTable(group.total_rows, CFG.embed_dim, seed=1)
    tr = DLRMTrainer(CFG, jax.random.key(0), lr=0.05, precision="int8")
    pipe = ScratchPipe(host, SLOTS, tr.train_fn, table_group=group, metrics=m)
    stream = TraceReplayStream(traces["drift"], stop=STEPS)
    pipe.run(stream, lookahead_fn=stream.peek_ids)
    assert pipe._ring is None
    names = {r["name"] for r in m.snapshot()}
    assert not any(n.startswith("cache.staging_") for n in names)


# ---------------------------------------------------------------------------
# S4: HostEmbeddingTable.gather(ids, out=)
# ---------------------------------------------------------------------------
def test_gather_into_out():
    host = HostEmbeddingTable(50, 6, seed=3)
    ids = np.array([7, 0, 49, 7, 12], dtype=np.int32)
    block = np.full((8, 6), -1.0, np.float32)
    got = host.gather(ids, out=block[: ids.size])
    assert got.base is block
    np.testing.assert_array_equal(block[: ids.size], host.data[ids])
    np.testing.assert_array_equal(block[ids.size:], -1.0)  # tail untouched
    assert host.traffic.read == ids.size * host.row_bytes  # counted once
    empty = host.gather(np.zeros(0, np.int64), out=block[:0])
    assert empty.shape == (0, 6)


@pytest.mark.parametrize("bad", [50, 1 << 20, -1])
def test_gather_into_out_checks_bounds(bad):
    host = HostEmbeddingTable(50, 6, seed=3)
    ids = np.array([3, bad, 4], dtype=np.int64)
    with pytest.raises(IndexError):
        host.gather(ids, out=np.empty((3, 6), np.float32))


def test_gather_into_out_verifies_under_guard():
    host = HostEmbeddingTable(50, 6, seed=3, guard=True)
    raw = host.data.view(np.uint8).reshape(-1)
    raw[host.row_bytes * 9 + 2] ^= 0xFF  # one byte of row 9
    out = np.empty((2, 6), np.float32)
    with pytest.raises(RowCorruptionError) as ei:
        host.gather(np.array([9, 1]), out=out)
    assert ei.value.rows == [9]
    host.gather(np.array([1, 2]), out=out)  # intact rows still read
    np.testing.assert_array_equal(out, host.data[[1, 2]])
