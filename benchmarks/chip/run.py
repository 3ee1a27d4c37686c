#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name:
``BENCHMARK.json`` names the cell's configuration file and traffic mix,
``traffic/<mix>.json`` holds the mix, and ``metrics/<metric>.py`` reads each
metric the cell reports. The configuration names the module that builds the
system under test (``driver``) and its plain reference (``reference``).

One run, in one process:

1. set-up: a producer process (spawned before JAX is imported; numpy only)
   starts drawing batches from the seed; the host tier's rows are drawn in
   parallel chunks; the runtime is built through the program's own entry
   points and driven through ``warmup_steps`` steps, until the scratchpad
   is full and evicting and every operand shape has compiled, then through
   ``check.FIRST`` more. The states that ``check.points`` names (the first
   steps from the seed and the steps after warm-up) are read on the way.
2. window: the same ``pipe.run`` goes on for ``--seconds``; a watcher thread
   stamps each step's loss as it becomes ready, so the main loop is never
   made to wait. With ``--trace 1`` the window is traced instead (at most
   ``TRACE_MAX_S`` seconds) and the per-layer metrics are read.
3. check: the device memory peak is read, the program is flushed to the
   host tier and freed, and the plain reference trains every set-up step
   from the seed; the numbers in ``check.py`` are compared with the
   configuration's limits.

The last line of stdout is the result as one JSON object. Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import check  # noqa: E402
import host_tier  # noqa: E402
import trace_reduce  # noqa: E402
import traffic_gen  # noqa: E402

#: the longest traced window; a trace of more steps only costs reading time
TRACE_MAX_S = 6.0


# ---------------------------------------------------------------------- #
# finding a cell's files by name
# ---------------------------------------------------------------------- #
def load_cell(name: str, root: pathlib.Path = ROOT, here: pathlib.Path = HERE):
    """(workload, configuration, traffic mix, end-to-end metrics, per-layer
    metrics) of the cell ``name``, from ``BENCHMARK.json`` and the files it
    names."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    cfg = json.loads((root / entry["file"]).read_text())
    cfg["name"] = entry["name"]
    for key in ("table_rows", "pooling"):  # one entry per table
        if key in cfg:
            n = len(cfg[key])
            if cfg.setdefault("num_tables", n) != n:
                raise SystemExit(f"{entry['file']}: {key} lists {n} tables, "
                                 f"num_tables is {cfg['num_tables']}")
    mix = json.loads((here / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(m):
        return name in m.get("workloads", [name])

    return (w, cfg, mix, [m for m in bench["end_to_end"] if mine(m)],
            [m for m in bench["per_layer"] if mine(m)])


def load_reader(metric: str, here: pathlib.Path = HERE):
    """The reader module ``metrics/<metric>.py``."""
    path = here / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(metrics, ctx, here: pathlib.Path = HERE) -> dict:
    """{name: {"value", "unit"}} for each metric whose reader finds
    something to read; a reader that returns None is left out."""
    out = {}
    for m in metrics:
        v = load_reader(m["name"], here).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def peaks_for(kind: str, here: pathlib.Path = HERE) -> dict:
    table = json.loads((here / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


# ---------------------------------------------------------------------- #
# helpers that run beside the program
# ---------------------------------------------------------------------- #
class CompileLog:
    """Persistent-cache hits and misses, and the host time at which each
    backend compile ended, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        self.compile_ends = []
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_ends.append(time.perf_counter())
            self.compile_s += secs

    def between(self, a: float, b: float) -> int:
        return sum(a < t <= b for t in self.compile_ends)


class Watcher(threading.Thread):
    """Stamps each step's output, in step order, when it becomes ready on
    the device. It only waits; it never makes the main loop wait."""

    def __init__(self, stats: list, value_of):
        super().__init__(name="bench-watcher", daemon=True)
        self._stats, self._value_of = stats, value_of
        self._done = threading.Event()
        self.stamps: list = []

    def run(self):
        import jax

        i = 0
        while True:
            if i < len(self._stats):
                jax.block_until_ready(self._value_of(self._stats[i]))
                self.stamps.append(time.perf_counter())
                i += 1
            elif self._done.is_set() and i >= len(self._stats):
                return
            else:
                time.sleep(2e-4)

    def finish(self):
        self._done.set()
        self.join()


def span_self_seconds(events, begin: str, end: str) -> dict:
    """Self time (own duration less nested spans on the same thread) of
    each program span, clipped to the instants ``begin``..``end``."""
    t = {e["name"]: e["ts"] for e in events if e.get("ph") == "I"}
    lo, hi = t[begin], t[end]
    out, stacks = {}, {}
    for e in events:
        ph = e.get("ph")
        st = stacks.setdefault(e["tid"], [])
        if ph == "B":
            st.append([e["name"], e["ts"], 0.0])
        elif ph == "E" and st:
            name, a, child = st.pop()
            dur = max(0.0, min(e["ts"], hi) - max(a, lo))
            out[name] = out.get(name, 0.0) + max(0.0, dur - child) / 1e6
            if st:
                st[-1][2] += dur
    return out


def host_rss_peak_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def host_rss_gb() -> float:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") / 2**30


class Ctx:
    """What a metric reader may read. End-to-end readers use the timed
    window (``step_s``, ``timed_steps``, ``timed_s``, ``setup_s``); per-layer
    readers the traced window (``steps``, ``window_s``, ``spans``,
    ``counters``, ``trace``...)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def span_ms_per_step(self, *names):
        if not self.steps:
            return None
        return sum(self.spans.get(n, 0.0) for n in names) / self.steps * 1e3


# ---------------------------------------------------------------------- #
# one run
# ---------------------------------------------------------------------- #
def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root: pathlib.Path = ROOT, here: pathlib.Path = HERE,
         require_tpu: bool = True) -> int:
    args = parse(argv)
    w, cfg, mix, e2e, per_layer = load_cell(args.workload, root, here)
    producer = traffic_gen.Producer(traffic_gen.stream_kwargs(cfg, mix, args.seed))
    try:
        return _run(args, w, cfg, mix, e2e, per_layer, producer, root, here,
                    require_tpu)
    finally:
        producer.close()


def _run(args, w, cfg, mix, e2e, per_layer, producer, root, here, require_tpu):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < w["chips"]):
        print(f"run.py: needs {w['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s). No CPU fallback.",
              file=sys.stderr)
        return 3
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clog = CompileLog()
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    driver = importlib.import_module(cfg["driver"])
    ref = importlib.import_module(cfg["reference"])
    dev = devs[0]
    log = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}; host RSS {host_rss_gb():.1f} GB")

    # -- set-up ---------------------------------------------------------- #
    offsets = traffic_gen.row_offsets(cfg)
    rows = int(offsets[-1])
    B = cfg["batch_size"]
    W = int(mix["warmup_steps"])
    pts = check.points(W)
    C = pts["last"]  # steps in set-up: warm-up, then the checked steady steps
    seed32 = int(np.random.default_rng(host_tier.seed_key(args.seed))
                 .integers(2**31))
    key = jax.random.key(seed32)
    t = time.perf_counter()
    data = host_tier.make_rows(args.seed, rows, cfg["embed_dim"])
    host_s = time.perf_counter() - t
    log(f"host RSS after the host tier: {host_rss_gb():.1f} GB")
    pre = [producer.get() for _ in range(C)]
    ids_of = check.row_ids(pts, [ids for ids, _ in pre])
    want = check.reads(pts)

    tracer = metrics = None
    if args.trace:
        from repro import obs

        tracer, metrics = obs.Tracer(jax_annotations=True), obs.MetricsRegistry()
        obs.install(tracer, metrics)
    host, trainer, pipe = driver.build(cfg, key, data)
    mlps0 = trainer.mlps
    stats = pipe.stats
    watcher = Watcher(stats, driver.step_value)
    watcher.start()

    snaps = {0: ({}, mlps0)}  # steps done -> ({name: rows}, MLPs)
    all_ids = []
    win = {}
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None

    def snapshot(n_done):
        if len(stats) != n_done:
            raise RuntimeError(f"snapshot after {len(stats)} steps, "
                               f"wanted {n_done}")
        snaps[n_done] = ({nm: driver.rows_now(pipe, ids_of[nm])
                          for nm in sorted(want[n_done])}, trainer.mlps)

    def marks():
        c = driver.counters(metrics) if metrics is not None else {}
        tr = host.traffic
        return dict(t=time.perf_counter(), n=len(stats), counters=c,
                    host_bytes=tr.read + tr.written, wait=producer.wait_s)

    def begin_window():
        # the last snapshot has waited for step C: the window starts after it
        if args.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            win["ann"] = jax.profiler.TraceAnnotation("bench.window")
            win["ann"].__enter__()
            tracer.instant("bench.window.begin")
            win["begin"] = marks()
        win["start"] = time.perf_counter()
        win["deadline"] = win["start"] + (
            min(args.seconds, TRACE_MAX_S) if args.trace else args.seconds)

    def end_window():
        if args.trace:
            win["end"] = marks()
            tracer.instant("bench.window.end")
            win["ann"].__exit__(None, None, None)

    def feed():
        k = 0
        while True:
            n = len(stats)
            if n in want and n not in snaps:
                snapshot(n)
            if "start" not in win and n == C:
                begin_window()
            if k < C:
                item = pre[k]
            else:
                if "deadline" in win and time.perf_counter() >= win["deadline"]:
                    end_window()
                    return
                item = producer.get()
            all_ids.append(item[0])
            k += 1
            yield item

    try:
        driver.run(pipe, feed())
    finally:
        watcher.finish()
    if args.trace:
        jax.profiler.stop_trace()
    N = len(stats)
    used = devs[: w["chips"]]
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)
    step_losses = [float(x) for x in driver.losses(stats)]
    stamps = watcher.stamps
    t_start, t_end = win["start"], stamps[N - 1]
    in_window = clog.between(t_start, t_end)
    log(f"compiles inside the window: {in_window}")
    log(f"host RSS at the end of the window: {host_rss_gb():.1f} GB")
    log(f"compile cache: hits={clog.hits} misses={clog.misses} "
        f"backend compile {clog.compile_s:.1f} s")
    log(f"set-up: host tier {host_s:.1f} s; window from {t_start - T0:.1f} s; "
        f"steps: warm-up {W}, set-up {C}, all {N}")

    # rows touched in set-up and never again: their host copy after the
    # final flush went through eviction, d2h and write-back, some of them
    # more than once
    pipe.flush_to_host()
    u_all = np.unique(np.concatenate([x.ravel() for x in all_ids[:C]]))
    later = np.zeros(rows, bool)
    for ids in all_ids[C:]:
        later[ids.ravel()] = True
    ids_of["writeback"] = u_all[~later[u_all]]
    rows_wb = host.data[ids_of["writeback"]]
    host_bytes_total = host.traffic.read + host.traffic.written

    # -- per-layer readings (traced run) --------------------------------- #
    ctx = Ctx(cfg=cfg, mix=mix, batch=B, ref=ref, peaks=peaks_for(dev.device_kind, here),
              memory_peak_bytes=mem_peak, setup_s=t_start - T0,
              timed_steps=N - C, timed_s=t_end - t_start,
              step_s=list(np.diff([t_start] + stamps[C:N])), trace=None, steps=0)
    red = None
    if args.trace:
        m0, m1 = win["begin"], win["end"]
        xs = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)
        events = tracer.events()
        red = trace_reduce.reduce_trace(
            xs[0], span_names={ev["name"] for ev in events if ev.get("ph") == "B"})
        shutil.rmtree(trace_dir, ignore_errors=True)
        steps = m1["n"] - m0["n"]
        uniq = [np.unique(all_ids[i]).size for i in range(m0["n"], m1["n"])]
        ctx.__dict__.update(
            steps=steps, window_s=m1["t"] - m0["t"], trace=red,
            spans=span_self_seconds(events, "bench.window.begin",
                                    "bench.window.end"),
            counters={k: m1["counters"].get(k, 0) - m0["counters"].get(k, 0)
                      for k in m1["counters"]},
            host_bytes=m1["host_bytes"] - m0["host_bytes"],
            input_wait_s=m1["wait"] - m0["wait"],
            n_unique_mean=float(np.mean(uniq)) if uniq else None,
        )
        log("program span self time, ms per traced step: " + ", ".join(
            f"{k}={v / max(steps, 1) * 1e3:.2f}" for k, v in sorted(ctx.spans.items())))
        log("device idle by innermost open span, s: " + ", ".join(
            f"{k}={v:.4f}" for k, v in sorted(red["idle_by_span"].items())))
        from repro import obs

        obs.install(None, None)

    # -- free the program, then the reference ---------------------------- #
    p = {"loss": {k: step_losses[k - 1] for k in pts["losses"]},
         "mlps": {n: jax.tree.map(lambda a: np.asarray(a, np.float32), m)
                  for n, (_, m) in snaps.items()},
         "rows": {(n, nm): v for n, (got, _) in snaps.items()
                  for nm, v in got.items()}}
    p["rows"][("end", "writeback")] = rows_wb
    del host, trainer, pipe, stats, snaps, data, mlps0, rows_wb
    gc.collect()
    t = time.perf_counter()
    rows0 = host_tier.rows_of(args.seed, u_all, cfg["embed_dim"])
    pos = {nm: np.searchsorted(u_all, ids) for nm, ids in ids_of.items()}
    keep = {n: {nm: pos[nm] for nm in names} for n, names in want.items()}
    keep.setdefault(C, {})["writeback"] = pos["writeback"]
    batches = [(ids, pl["dense"], pl["label"]) for ids, pl in pre]
    r = ref.train(cfg, key, u_all, rows0, batches, cfg["lr"], keep)
    ref_s = time.perf_counter() - t
    r["loss"] = dict(enumerate(r["loss"], 1))
    r["rows"][("end", "writeback")] = r["rows"].pop((C, "writeback"))
    for nm, ids in ids_of.items():
        p["rows"][(0, nm)] = r["rows"][(0, nm)] = rows0[pos[nm]]
    tbl = {nm: traffic_gen.table_of(offsets, ids) for nm, ids in ids_of.items()}
    nums = check.numbers(cfg, cfg["lr"], pts, p, r, tbl)
    ok, rows_cmp = check.verdict(nums, cfg["limits"])
    log(f"reference: {C} steps over {u_all.size} rows in {ref_s:.1f} s; rows "
        f"read: " + ", ".join(f"{k} {v.size}" for k, v in ids_of.items()))
    log(f"readings: loss {nums['loss_gap']!r}; grad {nums['grad_at']} "
        f"worst at {nums['grad_where']}; change {nums['change_at']} worst at "
        f"{nums['change_where']}; write-back worst at {nums['writeback_where']}")
    log(f"host: peak RSS {host_rss_peak_gb():.1f} GB; host tier bytes moved "
        f"{host_bytes_total / 1e9:.1f} GB")

    # -- result ---------------------------------------------------------- #
    ctx.failed = sum(not math.isfinite(x) for x in step_losses[C:])
    result = {
        "correct": bool(ok),
        "attempted": N - C if not args.trace else ctx.steps,
        "failed": ctx.failed,
        "metrics": read_metrics(per_layer if args.trace else e2e, ctx, here),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs), "memory_peak_bytes": int(mem_peak)},
    }
    if red is not None:
        result["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = trace_reduce.breakdown(red)
    result["checks"] = {  # last: each compared number beside its limit
        n: {"value": v if v is not None and math.isfinite(v) else None,
            "limit": lim} for n, v, lim in rows_cmp}
    for n, v, lim in rows_cmp:
        log(f"check {n}: {v!r} (limit {lim})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
