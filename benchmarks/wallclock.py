"""Measured wall-clock benchmark: real steps/s per cache design (no model).

Every other benchmark in this directory reports *model-derived* latency (the
calibrated two-tier bandwidth model of ``benchmarks/common.py`` — this
container cannot exhibit a 900 GB/s HBM). This module is the other column of
the methodology: it measures what actually runs, end to end, on this
container — steps/s through the full runtime hot loop (planner, host
gathers/scatters, device dispatches, train), the per-stage ms breakdown from
``StepStats.stage_times``, and the [Plan] controller cost in µs/batch.
Model-derived ms and measured steps/s are different columns and are never
mixed.

The bench config is sized so the *cache runtime* — not the 2-core container's
GEMM throughput — dominates: 8 tables x 50k rows, dim 32, small MLPs, batch
64 x 20 lookups/table (same id-stream shape as the paper config, high-
locality steady state is high-hit-rate).

``--baseline before.json`` merges a previous run in as the "before"
column. Every measurement runs in its OWN subprocess: cells must not share
the in-process XLA compile cache, or a cell's number would depend on which
cells ran before it. The parent process never imports JAX — on an
accelerator, a parent holding the device would lock its children out — so
the backend in ``machine`` is the one the children report.

Measured modes: ``sync`` (sync executor, split dispatch — the fast-path
planner/padding/empty-skip still apply), ``fast`` (overlapped executor +
fused insert+train, host planner), ``device`` (fast + the device-resident
planner: PlanState on-accelerator, raw ids h2d instead of translated slots)
and ``pallas`` (fast + ``kernel="pallas"``: the fused fill+gather /
coalesce+scatter cycle kernels — interpret-mode on this container, so its
wall-clock measures the dispatch path, not TPU kernel speed; the
``launches`` section carries the launch-count delta that IS the claim).
On this 2-core container the overlapped worker threads contend with XLA's
spinning pool, so the modes land close; on real two-tier hardware
``device`` is the intended production mode (DESIGN.md). The planner section
carries the [Plan] controller µs/batch per placement (host naive/memoized,
device per-step, device lax.scan window).

The checked-in json also stores a gate-sized ``smoke`` section
(``--with-smoke``); CI replays that sizing and fails on regressions beyond
a generous noise threshold (``--gate BENCH_wallclock.json``).

    PYTHONPATH=src python -m benchmarks.wallclock [--tiny] [--check]
        [--out BENCH_wallclock.json] [--baseline before.json]
        [--with-smoke] [--gate BENCH_wallclock.json]
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import subprocess
import sys
import time
from typing import List, Optional

import numpy as np

# ---- bench config ----------------------------------------------------------
TABLES = 8
ROWS_PER_TABLE = 50_000
EMBED_DIM = 32
BATCH = 64
LOOKUPS = 20
CACHE_FRAC = 0.25
LOCALITY = "high"
SEED = 0

DESIGNS = ("scratchpipe", "strawman", "sharded", "static", "nocache")
SCENARIOS = ("synthetic", "drift", "flash_crowd")

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_wallclock.json")


def bench_cfg():
    from repro.configs.base import DLRMConfig

    return DLRMConfig(
        name="dlrm-wallclock",
        num_tables=TABLES,
        rows_per_table=ROWS_PER_TABLE,
        embed_dim=EMBED_DIM,
        lookups_per_table=LOOKUPS,
        batch_size=BATCH,
        bottom_mlp=(64, EMBED_DIM),
        top_mlp=(128, 64, 1),
    )


def _modes_for(design: str) -> tuple:
    """Measured mode axis per design. ``device`` = overlapped executor +
    fused dispatch + planner="device" — the all-in fast path. ``pallas`` =
    fast + ``kernel="pallas"`` — scratchpipe only (one design covers the
    kernel axis)."""
    if design == "scratchpipe":
        return ("sync", "fast", "device", "pallas")
    if design in ("strawman", "sharded"):
        return ("fast", "device")
    return ("fast",)


def _mode_kernel(mode: str) -> str:
    return "pallas" if mode == "pallas" else "xla"


# ---- workloads -------------------------------------------------------------
def make_batches(scenario: str, group, steps: int) -> list:
    """Pre-materialized (ids, batch) list — generation cost stays OUT of the
    measured window (we measure the runtime, not the generator)."""
    from repro.data.synthetic import TraceConfig, dlrm_batches

    if scenario == "synthetic":
        tc = TraceConfig(
            num_tables=TABLES,
            rows_per_table=ROWS_PER_TABLE,
            lookups_per_table=LOOKUPS,
            batch_size=BATCH,
            locality=LOCALITY,
            seed=SEED,
        )
        return list(dlrm_batches(tc, steps))
    from repro.traces import scenario_batches

    return list(
        scenario_batches(
            scenario,
            group,
            steps,
            batch_size=BATCH,
            lookups_per_table=LOOKUPS,
            locality=LOCALITY,
            seed=SEED,
        )
    )


# ---- runtime construction --------------------------------------------------
def _sharded_train_fn(num_tables: int):
    """Fixed-shape per-shard device update (one shard per table => every
    shard sees exactly B*L slots; one jit executable total). The DLRM proper
    cannot run through the sharded runtime (bucketing drops bag positions),
    so this cell measures the cache-runtime + dispatch cost around a
    representative embedding update."""
    import jax

    @functools.partial(jax.jit, donate_argnums=0)
    def _add(storage, slots):
        return storage.at[slots.ravel()].add(1.0)

    def fn(storages, slots_all, batch):
        return [
            _add(s, np.asarray(sl)) if np.asarray(sl).size else s
            for s, sl in zip(storages, slots_all)
        ], None

    return fn


def build_runtime(design: str, mode: str, group, host, trainer,
                  batches_for_profile) -> object:
    from repro.core.runtime import make_runtime
    from repro.data.synthetic import TraceConfig, hot_ids_global

    rows = group.total_rows
    slots = max(1024, int(rows * CACHE_FRAC))
    executor = "sync" if mode == "sync" else "overlapped"
    planner = "device" if mode == "device" else "host"
    if design in ("scratchpipe", "strawman"):
        kw = {"num_slots": slots, "executor": executor, "planner": planner,
              "record_stage_times": True,
              "kernel": _mode_kernel(mode)}  # runtime-side [Insert] fills
        if mode in ("fast", "device", "pallas"):
            kw["fused_train_fn"] = trainer.fused_train_fn
        return make_runtime(design, host, trainer.train_fn, **kw)
    if design == "sharded":
        kw = {"num_slots": slots, "table_group": group, "executor": executor,
              "record_stage_times": True, "planner": planner}
        return make_runtime(
            design, host, _sharded_train_fn(group.num_tables), **kw
        )
    if design == "static":
        from repro.traces import profile_hot_ids

        hot = profile_hot_ids(
            iter(batches_for_profile), group, CACHE_FRAC
        ) if batches_for_profile else hot_ids_global(
            TraceConfig(
                num_tables=TABLES,
                rows_per_table=ROWS_PER_TABLE,
                lookups_per_table=LOOKUPS,
                batch_size=BATCH,
                locality=LOCALITY,
                seed=SEED,
            ),
            CACHE_FRAC,
            steps=10,
        )
        return make_runtime("static", host, trainer.train_fn, hot_ids=hot)
    return make_runtime("nocache", host, trainer.train_fn)


def _sync(runtime, trainer):
    """Quiesce everything the run may have left in flight before a timer
    edge — one shared implementation with run_design's timer fix."""
    from benchmarks.common import sync_runtime

    sync_runtime(runtime, trainer)


# ---- one measured cell -----------------------------------------------------
def measure_cell(design: str, scenario: str, mode: str, warmup: int,
                 steps: int) -> dict:
    import jax

    from repro.core.dlrm_runtime import DLRMTrainer
    from repro.core.host_table import HostEmbeddingTable
    from repro.core.table_group import TableGroup
    from repro.data.lookahead import LookaheadStream

    cfg = bench_cfg()
    group = TableGroup.from_config(cfg)
    items = make_batches(scenario, group, warmup + steps)
    profile = items[: max(1, warmup // 2)] if scenario != "synthetic" else None
    host = HostEmbeddingTable(group.total_rows, cfg.embed_dim, seed=1)
    kernel = _mode_kernel(mode)
    trainer = DLRMTrainer(cfg, jax.random.key(0), lr=0.05, kernel=kernel)
    runtime = build_runtime(design, mode, group, host, trainer, profile)

    stream = LookaheadStream(iter(items))
    it = iter(stream)
    for _ in range(warmup):
        ids, batch = next(it)
        runtime.run_one_cycle(ids, batch, stream.peek_ids)
    _sync(runtime, trainer)

    n_before = len(runtime.stats)
    t0 = time.perf_counter()
    for _ in range(steps):
        ids, batch = next(it)
        runtime.run_one_cycle(ids, batch, stream.peek_ids)
    if hasattr(runtime, "drain_one_cycle"):
        while getattr(runtime, "_window", None):
            runtime.drain_one_cycle()
    elif hasattr(runtime, "pipes"):  # lockstep sharded: drain every shard
        while any(p._window for p in runtime.pipes):
            for p in runtime.pipes:
                if p._window:
                    p.drain_one_cycle()
    _sync(runtime, trainer)
    elapsed = time.perf_counter() - t0

    stats = runtime.stats[n_before:]
    n_trained = len(stats)
    stage_ms = None
    # the first (past+1+future) retired entries ran their early stages
    # BEFORE the timer edge (they were in flight at the warmup boundary) —
    # excluding them keeps mean stage sums comparable to ms_per_step
    whole = stats[6:] if len(stats) > 9 else stats
    timed = [s for s in whole if getattr(s, "stage_times", None)]
    if timed:
        keys = sorted({k for s in timed for k in s.stage_times})
        stage_ms = {
            k: round(
                1e3 * float(np.mean([s.stage_times.get(k, 0.0) for s in timed])),
                4,
            )
            for k in keys
        }
    hit = float(np.mean([s.hit_rate for s in stats])) if stats else 0.0
    close = getattr(runtime, "close", None)
    if close is not None:
        close()  # release overlapped-executor worker threads
    return {
        "design": design,
        "scenario": scenario,
        "mode": mode,
        "kernel": kernel,
        "backend": jax.default_backend(),
        "steps": n_trained,
        "steps_per_s": round(n_trained / elapsed, 3) if elapsed > 0 else 0.0,
        "ms_per_step": round(elapsed / max(n_trained, 1) * 1e3, 4),
        "hit_rate": round(hit, 4),
        "stage_ms": stage_ms,
    }


# ---- planner microbench ----------------------------------------------------
def measure_planner(scenario: str, steps: int, memoize: bool) -> dict:
    from repro.core.plan import Planner
    from repro.core.table_group import TableGroup

    cfg = bench_cfg()
    group = TableGroup.from_config(cfg)
    items = make_batches(scenario, group, steps + 2)
    ids_list = [np.asarray(ids) for ids, _ in items]
    rows = group.total_rows
    slots = max(1024, int(rows * CACHE_FRAC))
    planner = Planner(rows, slots, past_window=3, future_window=2,
                      memoize=memoize)
    t0 = time.perf_counter()
    for i in range(steps):
        planner.plan(ids_list[i], [ids_list[i + 1], ids_list[i + 2]])
    elapsed = time.perf_counter() - t0
    return {
        "scenario": scenario,
        "placement": "host",
        "memoize": memoize,
        "steps": steps,
        "us_per_batch": round(elapsed / steps * 1e6, 1),
    }


def measure_planner_device(scenario: str, steps: int, scan: bool) -> dict:
    """Device-resident [Plan] µs/batch. ``scan=False`` drives DevicePlanner
    exactly like the pipeline does — one plan() per cycle including the
    host-facing miss/evict sync. ``scan=True`` plans the whole window in ONE
    ``plan_window`` (lax.scan) dispatch — the amortized cost when the
    controller batches the look-ahead window on-device. Steady-state cost:
    the first (compiling) pass runs outside the timed window."""
    import jax as _jax
    import jax.numpy as jnp

    from repro.core.plan_jax import DevicePlanner, init_state, plan_window
    from repro.core.table_group import TableGroup

    cfg = bench_cfg()
    group = TableGroup.from_config(cfg)
    items = make_batches(scenario, group, steps + 2)
    ids_list = [np.asarray(ids) for ids, _ in items]
    rows = group.total_rows
    slots = max(1024, int(rows * CACHE_FRAC))
    if scan:
        flat = np.stack(
            [ids_list[i].ravel().astype(np.int32) for i in range(steps)]
        )
        fut = np.stack(
            [
                np.concatenate(
                    [ids_list[i + 1].ravel(), ids_list[i + 2].ravel()]
                ).astype(np.int32)
                for i in range(steps)
            ]
        )
        def run_once():
            st, outs = plan_window(
                init_state(rows, slots), jnp.asarray(flat), jnp.asarray(fut),
                past_window=3,
            )
            _jax.block_until_ready(outs["miss_ids"])
        run_once()  # compile
        t0 = time.perf_counter()
        run_once()
        elapsed = time.perf_counter() - t0
    else:
        def run_once():
            planner = DevicePlanner(rows, slots, past_window=3, future_window=2)
            for i in range(steps):
                r = planner.plan(ids_list[i], [ids_list[i + 1], ids_list[i + 2]])
                r.miss_ids  # the host-facing sync the pipeline pays
        run_once()  # compile
        t0 = time.perf_counter()
        run_once()
        elapsed = time.perf_counter() - t0
    return {
        "scenario": scenario,
        "placement": "device",
        "mode": "scan" if scan else "step",
        "steps": steps,
        "us_per_batch": round(elapsed / steps * 1e6, 1),
    }


# ---- launch accounting -----------------------------------------------------
def measure_launches() -> List[dict]:
    """Per-cycle dispatch counts for one fused [Insert]+[Train] cycle at the
    bench shapes, per kernel mode — traced (jax.make_jaxpr), not executed,
    so the numbers are backend-independent. This is the evidence for the
    "<= 2 pallas_call launches per cycle per pad bucket" claim: the whole
    embedding fwd+bwd collapses into 1 fused fill+gather call and 1
    coalesce+scatter call."""
    import jax
    import jax.numpy as jnp

    from repro.core.dlrm_runtime import DLRMTrainer, dlrm_fill_train_step
    from repro.launch.hlo_stats import jaxpr_primitive_counts

    cfg = bench_cfg()
    n_slots = max(1024, int(TABLES * ROWS_PER_TABLE * CACHE_FRAC))
    F = 256  # one pad bucket's worth of fills
    slots = jnp.zeros((BATCH, TABLES, LOOKUPS), jnp.int32)
    dense = jnp.zeros((BATCH, cfg.num_dense_features), jnp.float32)
    label = jnp.zeros((BATCH,), jnp.float32)
    fill_slots = jnp.zeros((F,), jnp.int32)
    fill_rows = jnp.zeros((F, EMBED_DIM), jnp.float32)
    storage = jnp.zeros((n_slots, EMBED_DIM), jnp.float32)
    trainer = DLRMTrainer(cfg, jax.random.key(0), lr=0.05)
    out = []
    for kernel in ("xla", "pallas"):
        counts = jaxpr_primitive_counts(
            lambda st, m: dlrm_fill_train_step(
                st, m, fill_slots, fill_rows, slots, dense, label, 0.05,
                kernel=kernel,  # noqa: B023 (called before kernel rebinds)
            ),
            storage, trainer.mlps,
        )
        out.append({
            "kernel": kernel,
            "pallas_calls_per_cycle": counts.get("pallas_call", 0),
            "scatter_ops_per_cycle": sum(
                v for k, v in counts.items() if k.startswith("scatter")
            ),
            "gather_ops_per_cycle": counts.get("gather", 0),
        })
    return out


def machine_info(backend: Optional[str] = None) -> dict:
    """Provenance for checked-in numbers: the gate compares across machines,
    so every recorded run says what class of machine produced it. The
    parent never touches JAX, so ``backend`` is what the children reported
    (None when no child has run)."""
    import platform
    from importlib import metadata

    return {
        "platform": platform.platform(),
        "machine": platform.machine(),
        "cpus": os.cpu_count(),
        "python": platform.python_version(),
        "jax": metadata.version("jax"),
        "backend": backend,
    }


def measure_aux(scenarios, planner_steps: int) -> dict:
    """The [Plan] microbenches and the launch counts (one child process)."""
    import jax

    planner = []
    for scenario in scenarios:
        for memoize in (False, True):
            planner.append(measure_planner(scenario, planner_steps, memoize))
        for scan in (False, True):
            planner.append(
                measure_planner_device(scenario, planner_steps, scan)
            )
    return {"planner": planner, "launches": measure_launches(),
            "backend": jax.default_backend()}


# ---- driver ----------------------------------------------------------------
def _run_isolated(child_args: List[str], what: str) -> dict:
    """Run one measurement in a fresh process and return its CELL_RESULT.
    Cells share nothing — in particular not the in-process XLA compile
    cache, which would otherwise make a cell's number depend on which cells
    ran before it."""
    cmd = [sys.executable, "-m", "benchmarks.wallclock"] + child_args
    out = subprocess.run(cmd, capture_output=True, text=True)
    for line in out.stdout.splitlines():
        if line.startswith("CELL_RESULT "):
            return json.loads(line[len("CELL_RESULT "):])
    raise RuntimeError(
        f"{what} produced no result:\n"
        f"{out.stdout[-2000:]}\n{out.stderr[-2000:]}"
    )


def _measure_cell_isolated(design: str, scenario: str, mode: str,
                           warmup: int, steps: int) -> dict:
    return _run_isolated(
        ["--cell", design, scenario, mode,
         "--warmup", str(warmup), "--steps", str(steps)],
        f"cell {design}/{scenario}/{mode}",
    )


def run_suite(warmup: int, steps: int, planner_steps: int) -> dict:
    runs: List[dict] = []
    for scenario in SCENARIOS:
        for design in DESIGNS:
            for mode in _modes_for(design):
                cell = _measure_cell_isolated(design, scenario, mode, warmup, steps)
                runs.append(cell)
                print(
                    f"{design:<12} {scenario:<12} {mode:<6} "
                    f"{cell['steps_per_s']:>8.2f} steps/s  "
                    f"{cell['ms_per_step']:>8.2f} ms/step  "
                    f"hit={cell['hit_rate']:.3f}",
                    flush=True,
                )
    aux = _run_isolated(
        ["--aux", "--planner-steps", str(planner_steps)], "planner/launches"
    )
    planner, launches = aux["planner"], aux["launches"]
    for cell in planner:
        if cell["placement"] == "host":
            print(
                f"planner      {cell['scenario']:<12} host  memoize="
                f"{str(cell['memoize']):<5} "
                f"{cell['us_per_batch']:>8.1f} us/batch",
                flush=True,
            )
        else:
            print(
                f"planner      {cell['scenario']:<12} device {cell['mode']:<5} "
                f"{cell['us_per_batch']:>8.1f} us/batch",
                flush=True,
            )
    for rec in launches:
        print(
            f"launches     kernel={rec['kernel']:<7} "
            f"pallas_call={rec['pallas_calls_per_cycle']} "
            f"scatter={rec['scatter_ops_per_cycle']} "
            f"gather={rec['gather_ops_per_cycle']}  (per fused cycle)",
            flush=True,
        )
    return {
        "schema": "bench_wallclock/v1",
        "machine": machine_info(aux["backend"]),
        "config": {
            "tables": TABLES,
            "rows_per_table": ROWS_PER_TABLE,
            "embed_dim": EMBED_DIM,
            "batch": BATCH,
            "lookups_per_table": LOOKUPS,
            "cache_frac": CACHE_FRAC,
            "locality": LOCALITY,
            "warmup": warmup,
            "steps": steps,
        },
        "runs": runs,
        "planner": planner,
        "launches": launches,
    }


def _cell_key(c: dict) -> tuple:
    return (c["design"], c["scenario"], c["mode"])


def attach_baseline(result: dict, baseline: dict) -> dict:
    """Merge a previous run (same harness, older code) and compute the
    headline speedups the acceptance criteria track."""
    result["baseline"] = {
        "features": baseline.get("features"),
        "runs": baseline.get("runs"),
        "planner": baseline.get("planner"),
    }
    before = {_cell_key(c): c for c in baseline.get("runs", [])}
    speedups = {}
    for c in result["runs"]:
        b = before.get(_cell_key(c))
        if b and b["steps_per_s"] > 0:
            speedups["/".join(_cell_key(c))] = round(
                c["steps_per_s"] / b["steps_per_s"], 3
            )
    planner_speed = {}
    b_planner = {
        p["scenario"]: p
        for p in baseline.get("planner", [])
        if not p.get("memoize", False) and p.get("placement", "host") == "host"
    }
    for p in result["planner"]:
        b = b_planner.get(p["scenario"])
        if b is None or p["us_per_batch"] <= 0:
            continue
        if p.get("placement", "host") == "host" and p.get("memoize"):
            planner_speed[p["scenario"]] = round(
                b["us_per_batch"] / p["us_per_batch"], 3
            )
        elif p.get("placement") == "device":
            planner_speed[f"{p['scenario']}/device_{p['mode']}"] = round(
                b["us_per_batch"] / p["us_per_batch"], 3
            )
    result["speedup_steps_per_s"] = speedups
    result["speedup_planner"] = planner_speed
    return result


# ---- CI perf-regression gate ------------------------------------------------
# The checked-in BENCH_wallclock.json carries a "smoke" section recorded at
# the gate sizing below; CI re-runs the same sizing and fails on collapses
# beyond the noise band. The gate only arms when the baseline's machine-class
# provenance matches the runner (see gate_skip_reason) — on a different
# machine class it skips loudly instead of stretching the threshold until it
# can mask real regressions. Within a class the threshold is still generous:
# it catches order-of-magnitude collapses (a new per-cycle sync, a per-step
# recompile), not single-% noise.
GATE_WARMUP, GATE_STEPS, GATE_PLANNER_STEPS = 8, 10, 20


def _planner_key(p: dict) -> tuple:
    return (
        p["scenario"],
        p.get("placement", "host"),
        p.get("mode", "memoize" if p.get("memoize") else "naive"),
    )


# What makes two runners comparable for a perf ratio: architecture, core
# count, and accelerator backend. Software versions (python/jax) and the
# kernel build in the platform string move between images without changing
# the machine class, so they deliberately do NOT gate.
MACHINE_CLASS_KEYS = ("machine", "cpus", "backend")


def machine_class(info: Optional[dict]) -> Optional[tuple]:
    if not info:
        return None
    return tuple(info.get(k) for k in MACHINE_CLASS_KEYS)


def gate_skip_reason(
    baseline: dict, current: Optional[dict] = None
) -> Optional[str]:
    """The gate's ratios only mean anything against a baseline recorded on
    the same machine class — a loose cross-machine threshold silently
    absorbs real regressions (a 0.35 floor vs a 2x-faster recording box
    hides a 2.8x collapse). Returns the human-readable skip reason when the
    baseline must not be used, None when the gate may run."""
    base_cls = machine_class(baseline.get("machine"))
    cur_cls = machine_class(current if current is not None else machine_info())
    if base_cls is None:
        return (
            "baseline carries no machine provenance — cannot verify it was "
            "recorded on this machine class; re-record with --with-smoke"
        )
    if base_cls != cur_cls:
        diff = ", ".join(
            f"{k}: baseline={b!r} vs runner={c!r}"
            for k, b, c in zip(MACHINE_CLASS_KEYS, base_cls, cur_cls)
            if b != c
        )
        return f"baseline machine class does not match this runner ({diff})"
    return None


def regression_gate(
    result: dict, baseline: dict, min_ratio: float, planner_ratio: float = 3.0
) -> List[str]:
    """Compare a fresh gate-sized run against the baseline's smoke section.
    Returns a list of regression descriptions (empty = pass)."""
    problems: List[str] = []
    smoke = baseline.get("smoke")
    if not smoke:
        return [
            "baseline has no 'smoke' section — regenerate BENCH_wallclock.json "
            "with --with-smoke"
        ]
    fresh = result
    cfg = result.get("config", {})
    if (cfg.get("warmup"), cfg.get("steps")) != (GATE_WARMUP, GATE_STEPS):
        fresh = result.get("smoke")
        if not fresh:
            return ["gate needs a run at gate sizing (--tiny or --with-smoke)"]
    before = {_cell_key(c): c for c in smoke.get("runs", [])}
    for c in fresh.get("runs", []):
        b = before.get(_cell_key(c))
        if not b or b["steps_per_s"] <= 0:
            continue
        ratio = c["steps_per_s"] / b["steps_per_s"]
        if ratio < min_ratio:
            problems.append(
                f"{'/'.join(_cell_key(c))}: {c['steps_per_s']:.2f} steps/s vs "
                f"baseline {b['steps_per_s']:.2f} (x{ratio:.2f} < {min_ratio})"
            )
    b_planner = {_planner_key(p): p for p in smoke.get("planner", [])}
    for p in fresh.get("planner", []):
        b = b_planner.get(_planner_key(p))
        if not b or b["us_per_batch"] <= 0:
            continue
        ratio = p["us_per_batch"] / b["us_per_batch"]
        if ratio > planner_ratio:
            problems.append(
                f"planner {'/'.join(str(x) for x in _planner_key(p))}: "
                f"{p['us_per_batch']:.1f} us/batch vs baseline "
                f"{b['us_per_batch']:.1f} (x{ratio:.2f} > {planner_ratio})"
            )
    return problems


def smoke_section(result: dict) -> Optional[dict]:
    """The gate-sized slice of a run: the run itself when it was recorded at
    gate sizing, else its ``--with-smoke`` section, else None."""
    cfg = result.get("config", {})
    if (cfg.get("warmup"), cfg.get("steps")) == (GATE_WARMUP, GATE_STEPS):
        return {k: result[k] for k in ("config", "runs", "planner")}
    return result.get("smoke")


def rolling_baseline(result: dict) -> Optional[dict]:
    """A standalone ``--gate-fallback`` baseline from this run: its smoke
    section plus the machine provenance the gate needs to verify class.
    CI caches this per runner class, so the gate arms from the second run
    on a class onward even when the checked-in baseline was recorded on a
    different machine."""
    smoke = smoke_section(result)
    if smoke is None:
        return None
    return {
        "schema": "bench_wallclock_smoke/v1",
        "machine": result.get("machine") or machine_info(),
        "smoke": smoke,
    }


def resolve_gate_baseline(
    primary: dict, fallback: Optional[dict], current: Optional[dict] = None
) -> tuple:
    """Pick the first gate baseline recorded on THIS machine class: the
    checked-in one, else the rolling fallback. Returns
    ``(baseline_or_None, skip_reason_or_None, notes)`` — notes say which
    baselines were rejected and why (printed loudly, never silent)."""
    notes: List[str] = []
    skip = gate_skip_reason(primary, current=current)
    if skip is None:
        return primary, None, notes
    notes.append(f"checked-in baseline rejected: {skip}")
    if fallback is not None:
        fb_skip = gate_skip_reason(fallback, current=current)
        if fb_skip is None:
            notes.append("arming gate from the rolling baseline instead")
            return fallback, None, notes
        notes.append(f"rolling baseline rejected: {fb_skip}")
    return None, skip, notes


def check(result: dict) -> List[str]:
    """Sanity assertions for the CI perf-smoke job."""
    problems = []
    seen = {c["design"] for c in result["runs"]}
    for d in DESIGNS:
        if d not in seen:
            problems.append(f"design {d} missing from runs")
    for c in result["runs"]:
        if c["steps_per_s"] <= 0:
            problems.append(f"{_cell_key(c)}: steps_per_s <= 0")
        if c["stage_ms"] and c["mode"] == "sync":
            # sanity that the instrumentation works, not a precision claim:
            # at --tiny sizing a single in-window XLA compile legitimately
            # skews the per-stage means, so the band is generous — it still
            # catches missing stages or wildly wrong accounting
            total = sum(c["stage_ms"].values())
            if not (0.4 * c["ms_per_step"] <= total <= 2.0 * c["ms_per_step"]):
                problems.append(
                    f"{_cell_key(c)}: stage times sum {total:.2f} ms "
                    f"vs cycle {c['ms_per_step']:.2f} ms (sync executor "
                    "should account for the whole cycle)"
                )
    if not result["planner"]:
        problems.append("planner section empty")
    kernels = {c.get("kernel", "xla") for c in result["runs"]}
    if "pallas" not in kernels:
        problems.append("no kernel=pallas cell in runs (dispatch rot)")
    for rec in result.get("launches", []):
        if rec["kernel"] == "pallas" and rec["pallas_calls_per_cycle"] > 2:
            problems.append(
                f"pallas cycle dispatches {rec['pallas_calls_per_cycle']} "
                "pallas_call launches (> 2 per pad bucket)"
            )
    if not result.get("launches"):
        problems.append("launches section empty")
    return problems


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="CI smoke sizing")
    ap.add_argument(
        "--cell",
        nargs=3,
        metavar=("DESIGN", "SCENARIO", "MODE"),
        default=None,
        help="internal: measure one cell and print CELL_RESULT json",
    )
    ap.add_argument(
        "--aux",
        action="store_true",
        help="internal: measure the planner microbenches and launch counts "
        "and print CELL_RESULT json",
    )
    ap.add_argument("--warmup", type=int, default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--planner-steps", type=int, default=None)
    ap.add_argument("--out", default=os.path.normpath(OUT_PATH))
    ap.add_argument(
        "--baseline",
        default=None,
        help="previous BENCH_wallclock.json to merge as the 'before' column",
    )
    ap.add_argument("--check", action="store_true")
    ap.add_argument(
        "--with-smoke",
        action="store_true",
        help="also run the gate-sized smoke suite and store it under "
        "'smoke' (the section --gate compares CI runs against)",
    )
    ap.add_argument(
        "--gate",
        default=None,
        metavar="BASELINE.json",
        help="CI perf-regression gate: compare this run (at gate sizing) "
        "against the baseline's 'smoke' section and fail on regressions "
        "beyond the noise threshold",
    )
    ap.add_argument(
        "--gate-ratio",
        type=float,
        default=0.35,
        help="minimum fresh/baseline steps_per_s ratio before the gate "
        "fails (loose: CI machines differ from the recording machine)",
    )
    ap.add_argument(
        "--gate-fallback",
        default=None,
        metavar="SMOKE.json",
        help="rolling baseline to arm the gate with when the --gate "
        "baseline's machine class does not match this runner (CI caches a "
        "--save-smoke file per runner class, so the gate arms from the "
        "second run on the same class onward)",
    )
    ap.add_argument(
        "--save-smoke",
        default=None,
        metavar="SMOKE.json",
        help="write this run's gate-sized section (+ machine provenance) "
        "as a standalone rolling-baseline file for --gate-fallback",
    )
    args = ap.parse_args()
    warmup = args.warmup if args.warmup is not None else (
        GATE_WARMUP if args.tiny else 40
    )
    steps = args.steps if args.steps is not None else (
        GATE_STEPS if args.tiny else 80
    )
    planner_steps = args.planner_steps if args.planner_steps is not None else (
        GATE_PLANNER_STEPS if args.tiny else 200
    )
    if args.cell is not None or args.aux:
        from repro.launch.compile_cache import setup_compile_cache

        setup_compile_cache()
        if args.aux:
            cell = measure_aux(SCENARIOS, planner_steps)
        else:
            cell = measure_cell(*args.cell, warmup, steps)
        print("CELL_RESULT " + json.dumps(cell))
        return
    result = run_suite(warmup, steps, planner_steps)
    if args.with_smoke:
        if (warmup, steps) == (GATE_WARMUP, GATE_STEPS):
            # already at gate sizing: the run IS the smoke section
            result["smoke"] = {
                k: result[k] for k in ("config", "runs", "planner")
            }
        else:
            print("--- smoke section (gate sizing) ---", flush=True)
            result["smoke"] = run_suite(
                GATE_WARMUP, GATE_STEPS, GATE_PLANNER_STEPS
            )
    if args.baseline:
        with open(args.baseline) as f:
            result = attach_baseline(result, json.load(f))
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wallclock,{args.out},{len(result['runs'])} cells")
    failures = []
    if args.check:
        problems = check(result)
        for p in problems:
            print(f"  [FAIL] {p}")
        failures += problems
        if not problems:
            print("  [PASS] wallclock sanity")
    if args.gate:
        with open(args.gate) as f:
            gate_baseline = json.load(f)
        fallback = None
        if args.gate_fallback and os.path.exists(args.gate_fallback):
            with open(args.gate_fallback) as f:
                fallback = json.load(f)
        baseline, skip, notes = resolve_gate_baseline(
            gate_baseline, fallback, current=result["machine"]
        )
        for n in notes:
            print(f"  [GATE] {n}")
        if baseline is None:
            # loudly NOT a pass: a cross-machine ratio would need a
            # threshold loose enough to mask real regressions. With
            # --gate-fallback + --save-smoke wired (CI), the gate arms
            # itself from the second run on this machine class onward.
            print(
                "  [SKIP][gate] perf gate not applied — no baseline from "
                "this machine class yet (--with-smoke re-record, or let "
                "the --save-smoke rolling baseline arm it next run)"
            )
        else:
            problems = regression_gate(result, baseline, args.gate_ratio)
            for p in problems:
                print(f"  [FAIL][gate] {p}")
            failures += problems
            if not problems:
                which = (
                    args.gate if baseline is gate_baseline
                    else args.gate_fallback
                )
                print(f"  [PASS] perf gate vs {which}")
    if args.save_smoke:
        roll = rolling_baseline(result)
        if roll is None:
            print(
                "  [WARN] --save-smoke ignored: run carries no gate-sized "
                "section (use --tiny or --with-smoke)"
            )
        else:
            d = os.path.dirname(args.save_smoke)
            if d:
                os.makedirs(d, exist_ok=True)
            with open(args.save_smoke, "w") as f:
                json.dump(roll, f, indent=1)
            print(f"smoke,{args.save_smoke}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
