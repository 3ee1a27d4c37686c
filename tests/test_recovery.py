"""Crash-consistent recovery: kill-and-resume bit-parity at ANY cycle.

The fault-tolerance contract (DESIGN.md "Fault tolerance & recovery") is
that ``state_arrays()`` / ``load_state_arrays()`` capture the FULL runtime
state — planner, scratchpad, host table, traffic counters, and the
in-flight hold window — so a run killed mid-window and restored into a
fresh process replays elementwise bit-identical to one that never died:
same losses, same miss/evict order, same final tables. These tests prove
that on recorded drift / flash_crowd traces across executor x planner x
replica-precision, for the sharded runtime, and for the serving tier's
mid-queue snapshots, plus the CheckpointManager hardening (background
error propagation, fsync-before-rename) underneath it all.
"""
import os

import jax
import numpy as np
import pytest

import repro.checkpoint.manager as ckpt_manager
from repro.checkpoint import CheckpointManager
from repro.checkpoint.pack import unpack_blob
from repro.configs.base import DLRMConfig
from repro.core.dlrm_runtime import DLRMTrainer
from repro.core.host_table import HostEmbeddingTable
from repro.core.pipeline import ScratchPipe
from repro.core.serving_cache import (
    ReadOnlyCacheServer,
    resident_set_from_state,
)
from repro.core.sharded_pipeline import ShardedScratchPipe
from repro.core.table_group import TableGroup
from repro.runtime import SupervisePolicy
from repro.traces import record_trace, scenario_batches
from repro.traces.format import TraceReader
from repro.traces.replay import TraceReplayStream

SEED = 7
STEPS = 12
KILL_AT = 7  # admitted batches before the "crash" — mid-window by design
DENSE = 4

CFG = DLRMConfig(
    name="dlrm-recovery-test",
    num_tables=2,
    rows_per_table=300,
    embed_dim=8,
    lookups_per_table=2,
    batch_size=8,
    num_dense_features=DENSE,
    bottom_mlp=(16, 8),
    top_mlp=(16, 1),
)
SLOTS = 256


@pytest.fixture(scope="module")
def traces(tmp_path_factory):
    """Recorded drift + flash_crowd training traces (ids + dense + labels)."""
    root = tmp_path_factory.mktemp("recovery_traces")
    group = TableGroup.from_config(CFG)
    out = {}
    for scenario in ("drift", "flash_crowd"):
        path = str(root / scenario)
        record_trace(
            path,
            group,
            scenario_batches(
                scenario,
                group,
                STEPS,
                batch_size=CFG.batch_size,
                lookups_per_table=CFG.lookups_per_table,
                num_dense_features=DENSE,
                seed=SEED,
            ),
        )
        out[scenario] = TraceReader(path)
    return out


def fresh(executor, planner, precision):
    group = TableGroup.from_config(CFG).with_precision(precision)
    host = HostEmbeddingTable(group.total_rows, CFG.embed_dim, seed=1)
    tr = DLRMTrainer(CFG, jax.random.key(0), lr=0.05, precision=precision)
    kw = dict(planner=planner, table_group=group, executor=executor)
    if executor == "overlapped":
        kw["supervise"] = SupervisePolicy(backoff=0.0)
    pipe = ScratchPipe(host, SLOTS, tr.train_fn, **kw)
    return host, tr, pipe


def _losses(stats):
    return np.array([float(s.aux["loss"]) for s in stats], dtype=np.float64)


def _plan_seq(stats):
    return [(s.step, s.n_unique, s.n_hits, s.n_miss, s.n_evict) for s in stats]


def _assert_state_equal(a: dict, b: dict):
    assert sorted(a) == sorted(b)
    for k in a:
        np.testing.assert_array_equal(
            np.asarray(a[k]), np.asarray(b[k]), err_msg=f"state key {k!r}"
        )


@pytest.mark.parametrize(
    "scenario,executor,planner,precision",
    [
        ("drift", "sync", "host", "fp32"),
        ("drift", "overlapped", "host", "fp32"),
        ("drift", "sync", "device", "fp32"),
        ("drift", "overlapped", "device", "fp32"),
        ("drift", "sync", "host", "int8"),
        ("drift", "overlapped", "host", "fp16"),
        ("flash_crowd", "overlapped", "host", "fp32"),
        ("flash_crowd", "sync", "device", "int8"),
    ],
)
def test_midwindow_kill_resume_parity(
    tmp_path, traces, scenario, executor, planner, precision
):
    """Kill at admitted-batch 7 with batches still IN FLIGHT, restore into a
    fresh process, finish the trace: losses, plan decisions, and every final
    state array are bit-identical to the uninterrupted run."""
    reader = traces[scenario]

    # A: uninterrupted reference
    host_a, tr_a, pipe_a = fresh(executor, planner, precision)
    sa = TraceReplayStream(reader, stop=STEPS)
    stats_a = pipe_a.run(sa, lookahead_fn=sa.peek_ids)
    pipe_a.flush_to_host()
    final_a = pipe_a.state_arrays()
    pipe_a.close()
    assert len(stats_a) == STEPS

    # B: admit KILL_AT batches, checkpoint MID-WINDOW, then "crash"
    host_b, tr_b, pipe_b = fresh(executor, planner, precision)
    sb = TraceReplayStream(reader, stop=STEPS)
    it = iter(sb)
    for _ in range(KILL_AT):
        ids, batch = next(it)
        pipe_b.run_one_cycle(ids, batch, sb.peek_ids)
    assert pipe_b._window, "checkpoint must land mid-window, not at a drain"
    cm = CheckpointManager(str(tmp_path / "ck"))
    cm.save(
        KILL_AT,
        {"mlps": tr_b.mlps},
        host_arrays=pipe_b.state_arrays(),
        extra={"trainer_step": int(tr_b._step)},
        blocking=True,
    )
    stats_before_kill = list(pipe_b.stats)
    pipe_b.close()

    # C: fresh process — restore and fast-forward the deterministic stream
    host_c, tr_c, pipe_c = fresh(executor, planner, precision)
    restored, _ = cm.restore({"mlps": jax.eval_shape(lambda: tr_c.mlps)})
    tr_c.mlps = restored["mlps"]
    tr_c._step = int(cm.manifest()["extra"]["trainer_step"])
    pipe_c.load_state_arrays(
        {name: cm.restore_host(name) for name in cm.manifest()["host"]}
    )
    sc = TraceReplayStream(reader, start=KILL_AT, stop=STEPS)
    for ids, batch in iter(sc):
        pipe_c.run_one_cycle(ids, batch, sc.peek_ids)
    while pipe_c._window:
        pipe_c.drain_one_cycle()
    pipe_c.flush_to_host()
    final_c = pipe_c.state_arrays()
    stats_resumed = stats_before_kill + list(pipe_c.stats)
    pipe_c.close()

    np.testing.assert_array_equal(_losses(stats_resumed), _losses(stats_a))
    assert _plan_seq(stats_resumed) == _plan_seq(stats_a)
    np.testing.assert_array_equal(host_c.data, host_a.data)
    _assert_state_equal(final_c, final_a)


def _sharded_train_fn(storages, slots_all, batch):
    out = []
    for storage, slots in zip(storages, slots_all):
        slots = np.asarray(slots)
        if slots.size == 0:
            out.append(storage)
            continue
        u = np.unique(slots.ravel())
        out.append(storage.at[np.asarray(u)].add(1.0))
    return out, {"loss": float(sum(float(s.sum()) for s in out))}


def test_sharded_midwindow_kill_resume_parity(tmp_path):
    """ShardedScratchPipe: shard-indexed state keys round-trip mid-window."""
    rows, dim, shards = 240, 4, 3
    rng = np.random.default_rng(SEED)
    batches = [rng.integers(0, rows, size=14) for _ in range(STEPS)]

    def build():
        host = HostEmbeddingTable(rows, dim, seed=1)
        return host, ShardedScratchPipe(host, 80, shards, _sharded_train_fn)

    host_a, pipe_a = build()
    stats_a = pipe_a.run(iter([(b, {}) for b in batches]))
    pipe_a.flush_to_host()
    final_a = pipe_a.state_arrays()

    host_b, pipe_b = build()
    for b in batches[:KILL_AT]:
        pipe_b.run_one_cycle(b, {})
    assert pipe_b.pipes[-1]._window, "must checkpoint mid-window"
    cm = CheckpointManager(str(tmp_path / "ck"))
    cm.save(KILL_AT, {}, host_arrays=pipe_b.state_arrays(), blocking=True)
    stats_head = list(pipe_b.stats)
    pipe_b.close()

    host_c, pipe_c = build()
    pipe_c.load_state_arrays(
        {name: cm.restore_host(name) for name in cm.manifest()["host"]}
    )
    for b in batches[KILL_AT:]:
        pipe_c.run_one_cycle(b, {})
    while pipe_c.pipes[-1]._window:
        pipe_c.drain_one_cycle()
    pipe_c.flush_to_host()
    stats_resumed = stats_head + list(pipe_c.stats)

    np.testing.assert_array_equal(_losses(stats_resumed), _losses(stats_a))
    np.testing.assert_array_equal(host_c.data, host_a.data)
    _assert_state_equal(pipe_c.state_arrays(), final_a)


@pytest.mark.parametrize("executor", ["sync", "overlapped"])
def test_checkpoint_of_staged_rows_survives_ring_reuse(traces, executor):
    """A checkpoint taken while a batch's rows sit in an h2d staging block
    (after [Collect], before [Exchange]) holds the real rows themselves:
    the run that took it goes on and rewrites every block, and a resume
    from the checkpoint is still bit-identical to the uninterrupted run."""
    reader = traces["drift"]
    host_a, _, pipe_a = fresh(executor, "host", "fp32")
    sa = TraceReplayStream(reader, stop=STEPS)
    stats_a = pipe_a.run(sa, lookahead_fn=sa.peek_ids)
    pipe_a.flush_to_host()
    final_a = pipe_a.state_arrays()
    pipe_a.close()

    host_b, tr_b, pipe_b = fresh(executor, "host", "fp32")
    sb = TraceReplayStream(reader, stop=STEPS)
    it = iter(sb)
    for _ in range(KILL_AT):
        pipe_b.run_one_cycle(*next(it), sb.peek_ids)
    staged = [e for e in pipe_b._window if e.stage == 2]
    assert len(staged) == 1 and staged[0].staging is not None
    n = staged[0].plan.miss_ids.size
    block = staged[0].staging.buf
    head = block[:n].copy()
    # the host table and scratchpad are live arrays: copy them as a save
    # would; the window is left exactly as captured
    saved = {k: (v if k == "window" else np.array(v))
             for k, v in pipe_b.state_arrays().items()}
    (entry,) = [d for d in unpack_blob(saved["window"]) if d["stage"] == 2]
    assert entry["host_rows"].shape == (n, CFG.embed_dim)  # real rows only
    mlps = jax.tree.map(np.array, tr_b.mlps)
    trainer_step = int(tr_b._step)
    stats_before = list(pipe_b.stats)
    for ids, batch in it:  # the crashed run goes on and reuses every block
        pipe_b.run_one_cycle(ids, batch, sb.peek_ids)
    pipe_b.close()
    assert not np.array_equal(block[:n], head), "the block was not rewritten"

    host_c, tr_c, pipe_c = fresh(executor, "host", "fp32")
    tr_c.mlps = jax.tree.map(jax.numpy.asarray, mlps)
    tr_c._step = trainer_step
    pipe_c.load_state_arrays(saved)
    sc = TraceReplayStream(reader, start=KILL_AT, stop=STEPS)
    for ids, batch in iter(sc):
        pipe_c.run_one_cycle(ids, batch, sc.peek_ids)
    while pipe_c._window:
        pipe_c.drain_one_cycle()
    pipe_c.flush_to_host()
    final_c = pipe_c.state_arrays()
    stats_resumed = stats_before + list(pipe_c.stats)
    pipe_c.close()

    np.testing.assert_array_equal(_losses(stats_resumed), _losses(stats_a))
    assert _plan_seq(stats_resumed) == _plan_seq(stats_a)
    np.testing.assert_array_equal(host_c.data, host_a.data)
    _assert_state_equal(final_c, final_a)


# --------------------------------------------------------------------------- #
# serving: mid-queue snapshots
# --------------------------------------------------------------------------- #
SERVE_ROWS, SERVE_DIM, SERVE_SLOTS = 256, 8, 64


def _server(**kw):
    return ReadOnlyCacheServer(
        HostEmbeddingTable(SERVE_ROWS, SERVE_DIM, seed=1),
        SERVE_SLOTS,
        window=2,
        **kw,
    )


def test_serving_midqueue_checkpoint_parity(tmp_path):
    """Checkpoint a server with requests still queued at every pipeline
    stage; restore into a fresh server; every subsequent served bag is
    bit-identical to the uninterrupted server's."""
    rng = np.random.default_rng(0)
    reqs = [rng.integers(0, SERVE_ROWS, size=(2, 1, 4)) for _ in range(12)]

    a = _server()
    b = _server()
    for i, r in enumerate(reqs[:6]):
        a.enqueue(r, tag=i)
        b.enqueue(r, tag=i)
        if a.pending > a.queue_depth:
            a.serve_next()
            b.serve_next()
    assert b._queue and any(e.stage >= 1 for e in b._queue), "not mid-queue"
    cm = CheckpointManager(str(tmp_path / "ck"))
    cm.save(0, {}, host_arrays=b.state_arrays(), blocking=True)

    c = _server()
    c.load_state_arrays(
        {name: cm.restore_host(name) for name in cm.manifest()["host"]}
    )
    assert len(c._queue) == len(b._queue)
    tail_a, tail_c = [], []
    for r in reqs[6:]:
        a.enqueue(r)
        c.enqueue(r)
        tail_a.append(a.serve_next()[0])
        tail_c.append(c.serve_next()[0])
    while a.pending:
        tail_a.append(a.serve_next()[0])
        tail_c.append(c.serve_next()[0])
    assert len(tail_a) == len(tail_c) and len(tail_a) >= 8
    for x, y in zip(tail_a, tail_c):
        np.testing.assert_array_equal(x, y)
    # the restored server's traffic/step counters continued, not reset
    assert c._step == a._step


# --------------------------------------------------------------------------- #
# warm-start serving from a training checkpoint
# --------------------------------------------------------------------------- #
def _null_train_fn(storage, slots, batch):
    return storage, 0.0


def _train_some(pipe, steps=8, seed=0, tables=1):
    """Drive a few cycles of (B, T, L) global-id batches, per-table ranges."""
    rng = np.random.default_rng(seed)
    per = SERVE_ROWS // tables
    for _ in range(steps):
        ids = np.stack(
            [
                rng.integers(t * per, (t + 1) * per, size=(2, 4))
                for t in range(tables)
            ],
            axis=1,
        )
        pipe.run_one_cycle(ids, None)
    return pipe


@pytest.mark.parametrize(
    "planner,precision",
    [("host", "fp32"), ("device", "fp32"), ("host", "int8")],
)
def test_warm_start_from_training_checkpoint(planner, precision):
    """A cold serving replica preloads the trained runtime's resident set:
    every extracted row lands in the scratchpad, and serving them is an
    immediate full hit whose bags equal the host rows exactly."""
    group = TableGroup.uniform(2, SERVE_ROWS // 2, SERVE_DIM).with_precision(
        precision
    )
    kw = dict(planner=planner, table_group=group)
    pipe = ScratchPipe(
        HostEmbeddingTable(SERVE_ROWS, SERVE_DIM, seed=1),
        SERVE_SLOTS,
        _null_train_fn,
        **kw,
    )
    _train_some(pipe, tables=2)
    pipe.flush_to_host()
    arrays = pipe.state_arrays()

    ids_r, rows_r, use_r = resident_set_from_state(arrays)
    assert ids_r.size > 0 and rows_r.shape == (ids_r.size, SERVE_DIM)
    assert rows_r.dtype == np.float32

    srv = _server(table_group=group)
    n = srv.warm_start_from_arrays(arrays)
    assert n == ids_r.size
    slots = srv.planner.hitmap[ids_r]
    assert (slots >= 0).all() and srv._landed[slots].all()

    req = ids_r[: min(8, ids_r.size)].reshape(1, 1, -1)
    srv.enqueue(req)
    bags, st, _ = srv.serve_next()
    ref = (
        srv.host.data[req.ravel()]
        .reshape(1, 1, req.shape[-1], SERVE_DIM)
        .sum(axis=2)
    )
    if precision == "fp32":
        np.testing.assert_array_equal(bags, ref)
    else:
        np.testing.assert_allclose(bags, ref, rtol=0.2, atol=0.5)
    assert st.n_hits == len(np.unique(req))
    assert st.n_miss == 0


def test_warm_start_sharded_layout():
    """resident_set_from_state understands shard{i}_-prefixed checkpoints
    and returns GLOBAL ids with the right rows."""
    host = HostEmbeddingTable(SERVE_ROWS, SERVE_DIM, seed=1)
    pipe = ShardedScratchPipe(host, 32, 2, lambda s, sl, b: (list(s), None))
    _train_some(pipe)
    pipe.flush_to_host()
    arrays = pipe.state_arrays()

    ids_r, rows_r, _use = resident_set_from_state(arrays)
    assert ids_r.size > 0
    np.testing.assert_array_equal(rows_r, host.data[ids_r])

    srv = _server()
    n = srv.warm_start_from_arrays(arrays)
    assert n == min(ids_r.size, SERVE_SLOTS)


def test_warm_start_refuses_nonempty_server():
    pipe = ScratchPipe(
        HostEmbeddingTable(SERVE_ROWS, SERVE_DIM, seed=1),
        SERVE_SLOTS,
        _null_train_fn,
    )
    _train_some(pipe)
    arrays = pipe.state_arrays()
    srv = _server()
    srv.enqueue(np.arange(4).reshape(1, 1, 4))
    with pytest.raises(RuntimeError):
        srv.warm_start_from_arrays(arrays)


# --------------------------------------------------------------------------- #
# CheckpointManager hardening
# --------------------------------------------------------------------------- #
def test_async_save_failure_surfaces_on_next_save(tmp_path, monkeypatch):
    """A background write failure must raise on the NEXT save()/wait(), not
    vanish with the daemon thread."""
    cm = CheckpointManager(str(tmp_path), durable=False)

    def boom(*a, **kw):
        raise OSError("disk full (injected)")

    monkeypatch.setattr(ckpt_manager.np, "savez", boom)
    cm.save(1, {"x": np.zeros(3)}, blocking=False)
    with pytest.raises(RuntimeError, match="async checkpoint write failed"):
        cm.save(2, {"x": np.zeros(3)}, blocking=False)
    monkeypatch.undo()
    # the error is consumed once surfaced; the manager keeps working
    cm.wait()
    cm.save(3, {"x": np.ones(3)}, blocking=True)
    assert cm.latest_step() == 3


def test_durable_save_fsyncs_before_rename(tmp_path, monkeypatch):
    """durable=True fsyncs the tmp tree BEFORE os.replace and the parent
    after — power loss cannot leave a renamed-but-empty checkpoint."""
    events = []
    real_fsync, real_replace = os.fsync, os.replace
    monkeypatch.setattr(
        ckpt_manager.os, "fsync", lambda fd: events.append("fsync")
    )
    monkeypatch.setattr(
        ckpt_manager.os,
        "replace",
        lambda a, b: (events.append("replace"), real_replace(a, b))[1],
    )
    cm = CheckpointManager(str(tmp_path / "durable"), durable=True)
    cm.save(1, {"x": np.zeros(3)}, host_arrays={"t": np.ones(2)}, blocking=True)
    assert "replace" in events
    ri = events.index("replace")
    assert events[:ri].count("fsync") >= 3  # arrays + host + manifest + dirs
    assert "fsync" in events[ri + 1 :]  # parent dir after the rename

    events.clear()
    cm2 = CheckpointManager(str(tmp_path / "fast"), durable=False)
    cm2.save(1, {"x": np.zeros(3)}, blocking=True)
    assert events.count("fsync") == 0
