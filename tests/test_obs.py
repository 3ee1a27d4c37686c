"""Telemetry layer (repro.obs) tests:

  O1  registry: instrument dedup by (kind, name, labels), counter/gauge/
      histogram snapshots, JSONL export validates against obs_metrics/v1.
  O2  tracer: spans recorded on the EXECUTING thread; Chrome trace-event
      export is valid (balanced B/E, monotone per-thread timestamps) with
      >= 3 distinct threads; Tracer.totals() attributes wall-clock to the
      (thread, span) that did the work; dangling spans are balanced.
  O3  opt-in is structural: a ScratchPipe built without tracer/metrics has
      no tracer, no counter cells, and no wrapped pool functions.
  O4  bit parity: executor="overlapped" WITH full tracing+metrics is
      bit-identical to untraced executor="sync" on recorded-style batches.
  O5  counter correctness: cache.* counters equal the StepStats sums on
      drift and flash_crowd scenario traces (incl. per-table cells).
  O6  serving: serve.* counters match replay results (requests, latency
      histogram count, emergency accounting vs StepStats.aux).
  O7  the validators actually reject corrupt artifacts.
  O8  stage spans nest as documented (cycle > stages > sub-spans), and the
      link counters count the padded rows and bytes that cross h2d / d2h.
"""
import importlib.util
import json
import pathlib
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import DLRMConfig
from repro.core.dlrm_runtime import DLRMTrainer
from repro.core.host_table import HostEmbeddingTable
from repro.core.pipeline import ScratchPipe
from repro.core.plan import pad_len
from repro.core.runtime import make_runtime
from repro.core.serving_cache import NoCacheServer, ReadOnlyCacheServer
from repro.core.table_group import TableGroup
from repro.data.lookahead import LookaheadStream
from repro.obs.check import validate_chrome_trace, validate_metrics_jsonl
from repro.serving import replay_serving
from repro.traces.scenarios import scenario_batches

DIM = 8


class CountingTrainer:
    """[Train] = +1 per unique touched slot: integer-exact parity oracle."""

    def train_fn(self, storage, slots, batch):
        uniq = jnp.unique(jnp.asarray(slots).ravel(), size=slots.size,
                          fill_value=-1)
        ok = uniq >= 0
        upd = jnp.where(ok, uniq, 0)
        add = jnp.zeros_like(storage).at[upd].add(
            jnp.where(ok, 1.0, 0.0)[:, None]
        )
        return storage + add, {}


def group_batches(scenario, steps=20, seed=7):
    group = TableGroup.uniform(2, 400, DIM)
    batches = [
        gids
        for gids, _ in scenario_batches(
            scenario, group, steps, batch_size=4, lookups_per_table=3,
            seed=seed,
        )
    ]
    return group, batches


def run_pipe(batches, group, **kw):
    host = HostEmbeddingTable(group.total_rows, DIM, seed=1)
    host.data[:] = 0.0
    pipe = ScratchPipe(
        host, 96, CountingTrainer().train_fn, table_group=group,
        past_window=3, future_window=2, **kw
    )
    stream = LookaheadStream(iter([(b, {}) for b in batches]))
    stats = pipe.run(stream, lookahead_fn=stream.peek_ids)
    pipe.close()
    pipe.flush_to_host()
    return host.data.copy(), stats, pipe


# ---------------------------------------------------------------------------
# O1: metrics registry
# ---------------------------------------------------------------------------
def test_registry_dedup_and_counter():
    m = obs.MetricsRegistry()
    a = m.counter("cache.hits", runtime="x")
    b = m.counter("cache.hits", runtime="x")
    c = m.counter("cache.hits", runtime="y")
    assert a is b and a is not c
    a.inc()
    a.inc(4)
    assert a.value == 5 and c.value == 0
    assert len(m) == 2


def test_gauge_probe_and_histogram():
    m = obs.MetricsRegistry()
    box = {"v": 0}
    m.gauge("probe", fn=lambda: box["v"])
    h = m.histogram("lat", unit="us")
    for v in (1, 2, 4, 100, 1000):
        h.observe(v)
    box["v"] = 42
    snap = {r["name"]: r for r in m.snapshot()}
    assert snap["probe"]["value"] == 42  # evaluated at snapshot time
    assert snap["lat"]["count"] == 5
    assert snap["lat"]["min"] == 1 and snap["lat"]["max"] == 1000
    assert snap["lat"]["p50"] <= snap["lat"]["p99"]
    # a probe that raises must not break the snapshot
    m.gauge("bad", fn=lambda: 1 / 0)
    bad = {r["name"]: r for r in m.snapshot()}["bad"]
    assert bad["value"] is None and "error" in bad


def test_metrics_jsonl_schema(tmp_path):
    m = obs.MetricsRegistry()
    m.counter("c").inc(3)
    m.gauge("g").set(1.5)
    m.histogram("h").observe(10)
    path = str(tmp_path / "m.jsonl")
    m.write_jsonl(path, provenance={"mode": "test"})
    assert validate_metrics_jsonl(path) == []
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["schema"] == "obs_metrics/v1"
    assert lines[0]["kind"] == "meta"
    assert lines[0]["provenance"] == {"mode": "test"}
    assert lines[0]["num_metrics"] == 3 == len(lines) - 1


# ---------------------------------------------------------------------------
# O2: tracer
# ---------------------------------------------------------------------------
def test_chrome_trace_multithread(tmp_path):
    tr = obs.Tracer()

    def worker(name):
        with tr.span(name, cat="host"):
            pass

    with tr.span("main_stage"):
        t1 = threading.Thread(target=worker, args=("w1",), name="worker-1")
        t2 = threading.Thread(target=worker, args=("w2",), name="worker-2")
        t1.start(), t2.start()
        t1.join(), t2.join()
    tr.instant("marker")
    path = str(tmp_path / "t.json")
    n = tr.export_chrome(path)
    assert n > 0
    assert validate_chrome_trace(path, min_threads=3) == []
    doc = json.load(open(path))
    names = {
        e["args"]["name"]
        for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert {"worker-1", "worker-2"} <= names
    totals = tr.totals()
    assert ("worker-1", "w1") in totals and ("worker-2", "w2") in totals


def test_dangling_span_balanced(tmp_path):
    tr = obs.Tracer()
    s = tr.span("never_closed")
    s.__enter__()  # simulate a thread that died mid-span
    path = str(tmp_path / "d.json")
    tr.export_chrome(path)
    assert validate_chrome_trace(path) == []


def test_wrap_attributes_to_executing_thread():
    tr = obs.Tracer()
    fn = tr.wrap("work", lambda x: x + 1, cat="host")
    out = {}
    t = threading.Thread(target=lambda: out.update(r=fn(1)), name="exec-thread")
    t.start()
    t.join()
    assert out["r"] == 2
    assert ("exec-thread", "work") in tr.totals()


# ---------------------------------------------------------------------------
# O3: opt-out is structural
# ---------------------------------------------------------------------------
def test_metrics_off_default_structure():
    group, batches = group_batches("drift", steps=4)
    _, _, pipe = run_pipe(batches, group)
    assert pipe._tracer is None
    assert pipe._mc is None


def test_install_resolve_precedence():
    g = obs.MetricsRegistry()
    local = obs.MetricsRegistry()
    obs.install(None, g)
    try:
        assert obs.resolve(None, None) == (None, g)
        assert obs.resolve(None, local) == (None, local)  # explicit wins
    finally:
        obs.install(None, None)
    assert obs.resolve(None, None) == (None, None)


# ---------------------------------------------------------------------------
# O4: bit parity under tracing
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("executor", ["overlapped", "sync"])
@pytest.mark.parametrize("scenario", ["drift", "flash_crowd"])
def test_traced_overlapped_parity(scenario, executor):
    group, batches = group_batches(scenario)
    ref, ref_stats, _ = run_pipe(batches, group, executor="sync")
    tr, m = obs.Tracer(), obs.MetricsRegistry()
    got, got_stats, _ = run_pipe(
        batches, group, executor=executor, tracer=tr, metrics=m
    )
    np.testing.assert_array_equal(ref, got)
    assert [s.n_hits for s in ref_stats] == [s.n_hits for s in got_stats]
    assert [s.n_evict for s in ref_stats] == [s.n_evict for s in got_stats]
    # the traced run actually traced: host worker spans present
    assert any(name == "collect.gather" for _, name in tr.totals())


# ---------------------------------------------------------------------------
# O5: counter correctness vs StepStats
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scenario", ["drift", "flash_crowd"])
def test_counters_match_stepstats(scenario):
    group, batches = group_batches(scenario)
    m = obs.MetricsRegistry()
    _, stats, _ = run_pipe(batches, group, metrics=m)
    lbl = {"runtime": "scratchpipe"}
    assert m.counter("cache.cycles", **lbl).value == len(stats)
    assert m.counter("cache.lookups", **lbl).value == sum(
        s.n_lookups for s in stats
    )
    assert m.counter("cache.unique", **lbl).value == sum(
        s.n_unique for s in stats
    )
    assert m.counter("cache.hits", **lbl).value == sum(s.n_hits for s in stats)
    assert m.counter("cache.misses", **lbl).value == sum(
        s.n_miss for s in stats
    )
    assert m.counter("cache.evicts", **lbl).value == sum(
        s.n_evict for s in stats
    )
    for i, t in enumerate(group.tables):
        assert m.counter("cache.hits", table=t.name, **lbl).value == sum(
            int(s.by_table["hits"][i]) for s in stats
        )
        assert m.counter("cache.misses", table=t.name, **lbl).value == sum(
            int(s.by_table["misses"][i]) for s in stats
        )
    # byte gauges read the unconditional traffic counters
    snap = {
        (r["name"], r["labels"].get("runtime")): r for r in m.snapshot()
    }
    assert snap[("traffic.host.read_bytes", "scratchpipe")]["value"] > 0


# ---------------------------------------------------------------------------
# O6: serving counters
# ---------------------------------------------------------------------------
def test_serving_counters_and_latency(tmp_path):
    group, batches = group_batches("flash_crowd", steps=16)
    host = HostEmbeddingTable(group.total_rows, DIM, seed=2)
    m, tr = obs.MetricsRegistry(), obs.Tracer()
    srv = ReadOnlyCacheServer(
        host, 96, window=2, table_group=group, tracer=tr, metrics=m
    )
    res = replay_serving(srv, batches, depth=1)
    lbl = {"runtime": "scratchpipe-serve"}
    assert m.counter("serve.requests", **lbl).value == res["served"] == len(
        batches
    )
    snap = {r["name"]: r for r in m.snapshot() if r["kind"] == "histogram"}
    assert snap["serve.latency_us"]["count"] == res["served"]
    # oracle emergency accounting from an untelemetried replay
    srv2 = ReadOnlyCacheServer(host, 96, window=2, table_group=group)
    emergencies = []
    for b in batches:
        srv2.enqueue(b)
        _, st, _ = srv2.serve_next()
        emergencies.append(
            st.aux.get("emergency", 0) if isinstance(st.aux, dict) else 0
        )
    assert m.counter("serve.emergency_rows", **lbl).value == sum(emergencies)
    assert m.counter("serve.emergency_serves", **lbl).value == sum(
        1 for e in emergencies if e
    )
    assert any(name == "serve" for _, name in tr.totals())


def test_serving_parity_with_telemetry(tmp_path):
    group, batches = group_batches("drift", steps=12)
    oracle = replay_serving(
        NoCacheServer(HostEmbeddingTable(group.total_rows, DIM, seed=2)),
        batches, depth=0, collect_bags=True,
    )["bags"]
    m, tr = obs.MetricsRegistry(), obs.Tracer()
    srv = ReadOnlyCacheServer(
        HostEmbeddingTable(group.total_rows, DIM, seed=2), 128, window=2,
        table_group=group, tracer=tr, metrics=m,
    )
    bags = replay_serving(srv, batches, depth=2, collect_bags=True)["bags"]
    for i, (a, b) in enumerate(zip(bags, oracle)):
        np.testing.assert_array_equal(a, b, err_msg=f"batch {i}")


# ---------------------------------------------------------------------------
# O7: validators reject corruption
# ---------------------------------------------------------------------------
def test_validators_reject_bad_artifacts(tmp_path):
    bad_trace = tmp_path / "bad.json"
    bad_trace.write_text("{not json")
    assert validate_chrome_trace(str(bad_trace)) != []
    # unbalanced + non-monotone events
    evil = {
        "traceEvents": [
            {"ph": "B", "name": "a", "pid": 1, "tid": 1, "ts": 10.0},
            {"ph": "E", "pid": 1, "tid": 1, "ts": 5.0},
            {"ph": "E", "pid": 1, "tid": 1, "ts": 6.0},
        ]
    }
    evil_path = tmp_path / "evil.json"
    evil_path.write_text(json.dumps(evil))
    assert validate_chrome_trace(str(evil_path)) != []
    bad_metrics = tmp_path / "bad.jsonl"
    bad_metrics.write_text('{"kind": "counter", "name": "x"}\n')
    assert validate_metrics_jsonl(str(bad_metrics)) != []


# ---------------------------------------------------------------------------
# O8: stage span nesting and link counters
# ---------------------------------------------------------------------------
def span_parents(tracer):
    """(thread name, span name, enclosing span name or None) of every span,
    from the exported events."""
    events = tracer.events()
    names = {e["tid"]: e["args"]["name"] for e in events if e["ph"] == "M"}
    out, stacks = [], {}
    for e in events:
        st = stacks.setdefault(e["tid"], [])
        if e["ph"] == "B":
            out.append((names[e["tid"]], e["name"], st[-1] if st else None))
            st.append(e["name"])
        elif e["ph"] == "E":
            st.pop()
    return out


STAGES = ("input", "plan", "collect", "exchange", "insert_host",
          "insert_fill", "train", "retire")


@pytest.mark.parametrize("executor", ["sync", "overlapped"])
def test_stage_span_nesting(executor):
    group, batches = group_batches("flash_crowd")
    tr = obs.Tracer()
    _, stats, _ = run_pipe(batches, group, executor=executor, tracer=tr)
    assert sum(s.n_evict for s in stats) > 0  # the eviction path ran
    seen = {}
    for thread, name, parent in span_parents(tr):
        seen.setdefault(name, set()).add((thread, parent))
    main = "MainThread"
    assert seen["cycle"] == {(main, None)}
    for name in STAGES:
        assert seen[name] == {(main, "cycle")}, name
    assert seen["exchange.pad"] == {(main, "exchange")}
    assert seen["exchange.h2d"] == {(main, "exchange")}
    assert seen["plan.evict"] == {(main, "plan")}
    # a digest is built at [Plan] or, for a future batch, inside eviction
    assert {p for _, p in seen["plan.unique"]} <= {"plan", "plan.evict"}
    assert {t for t, _ in seen["plan.unique"]} == {main}
    if executor == "sync":
        assert seen["insert.writeback"] == {(main, "insert_host")}
        assert seen["collect.gather"] == {(main, "collect")}
        assert seen["exchange.d2h"] == {(main, "exchange")}
    else:  # pool work lands on the worker that ran it, at its top level
        assert seen["insert.writeback"] == {("scratchpipe-host_0", None)}
        assert seen["collect.gather"] == {("scratchpipe-host_0", None)}
        assert seen["exchange.d2h"] == {("scratchpipe-d2h_0", None)}


@pytest.mark.parametrize("executor", ["sync", "overlapped"])
def test_link_counters_count_padded_rows(executor):
    group, batches = group_batches("drift")
    m = obs.MetricsRegistry()
    _, stats, _ = run_pipe(batches, group, executor=executor, metrics=m)
    lbl = {"runtime": "scratchpipe"}
    h2d = sum(pad_len(s.n_miss) for s in stats if s.n_miss)
    d2h = sum(pad_len(s.n_evict) for s in stats if s.n_evict)
    assert d2h > 0
    row = DIM * 4  # fp32 rows
    got = {k: m.counter(f"cache.{k}", **lbl).value
           for k in ("h2d_rows", "h2d_bytes", "d2h_rows", "d2h_bytes")}
    assert got == {"h2d_rows": h2d, "h2d_bytes": h2d * row,
                   "d2h_rows": d2h, "d2h_bytes": d2h * row}
    # the benchmark's padding share, read from these counters, agrees with
    # the share computed here from the step stats
    real = sum(s.n_miss + s.n_evict for s in stats)
    path = (pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
            / "chip" / "metrics" / "pad_share_pct.py")
    spec = importlib.util.spec_from_file_location("pad_share_pct", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    class Ctx:
        counters = {r["name"]: r["value"] for r in m.snapshot()
                    if r["kind"] == "counter" and "table" not in r["labels"]}

    assert reader.read(Ctx) == pytest.approx(100 * (1 - real / (h2d + d2h)))


def test_traced_int8_parity_and_link_bytes():
    """Tracing keeps the quantizing gather (a traced int8 run equals the
    untraced one), and the link counters add up both parts of an int8
    row block: the payload and its scale column."""
    group, batches = group_batches("drift", steps=12)
    cfg = DLRMConfig(
        name="dlrm-obs-int8", table_rows=(400, 400), embed_dim=DIM,
        lookups_per_table=3, batch_size=4, bottom_mlp=(16, DIM),
        top_mlp=(16, 1), precision="int8", rounding="nearest",
    )
    rng = np.random.default_rng(3)
    items = [(b, {"dense": rng.standard_normal((4, 13)).astype(np.float32),
                  "label": rng.integers(0, 2, 4).astype(np.float32)})
             for b in batches]

    def run(**kw):
        host = HostEmbeddingTable(group.total_rows, DIM, seed=1)
        trainer = DLRMTrainer(cfg, jax.random.key(0), lr=0.05)
        pipe = make_runtime("scratchpipe", host, trainer.train_fn,
                            num_slots=24, precision="int8", **kw)
        stream = LookaheadStream(iter(items))
        stats = pipe.run(stream, lookahead_fn=stream.peek_ids)
        pipe.flush_to_host()
        return host.data.copy(), [float(s.aux["loss"]) for s in stats], stats

    ref, ref_loss, _ = run()
    m = obs.MetricsRegistry()
    got, got_loss, stats = run(tracer=obs.Tracer(), metrics=m)
    np.testing.assert_array_equal(ref, got)
    assert ref_loss == got_loss
    lbl = {"runtime": "scratchpipe"}
    h2d = sum(pad_len(s.n_miss) for s in stats if s.n_miss)
    d2h = sum(pad_len(s.n_evict) for s in stats if s.n_evict)
    assert d2h > 0
    row = DIM + 4  # int8 payload + one fp32 scale
    assert m.counter("cache.h2d_bytes", **lbl).value == h2d * row
    assert m.counter("cache.d2h_bytes", **lbl).value == d2h * row
