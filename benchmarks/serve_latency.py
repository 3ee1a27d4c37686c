"""Measured serving benchmark: request latency per read-only cache design.

The serving analogue of ``benchmarks/wallclock.py`` — and like it, this
measures what actually runs on this container (host gathers, planner,
device dispatches) rather than the calibrated bandwidth model. The workload
is a RECORDED serving trace (``inference_mix`` by default, through the
traces subsystem's serving mode), replayed through each registered serving
design at a pinned queue depth:

    nocache-serve      every request gathers from the host tier (oracle)
    static-serve       profiled top-N pinned rows + transient-tail misses
    scratchpipe-serve  the read-only plan-ahead cache; the queue is the
                       look-ahead window

Reported per design: p50/p99/mean request latency (serve critical path,
bags materialized host-side) and lookups/s. For ``scratchpipe-serve`` the
benchmark additionally sweeps queue depth — hit-rate vs depth is THE
serving claim: at depth >= the look-ahead window every request's rows were
planned, fetched, and inserted before the request reached the head, so the
hit-rate saturates at 100% and the latency distribution collapses onto the
pure-lookup cost. Results carry the same machine-class provenance as
``BENCH_wallclock.json`` so cross-machine numbers are never compared.

    PYTHONPATH=src python -m benchmarks.serve_latency [--tiny] [--check]
        [--out BENCH_serve.json] [--scenario inference_mix]
        [--depths 0,1,2,4,8]
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
from typing import Dict, List, Optional

import numpy as np

from benchmarks.wallclock import machine_info
from repro.core.host_table import HostEmbeddingTable
from repro.core.runtime import make_runtime
from repro.core.table_group import TableGroup
from repro.serving import replay_serving
from repro.traces.format import TraceReader
from repro.traces.profiling import hot_ids_from_trace
from repro.traces.recorder import record_serving_trace
from repro.traces.scenarios import scenario_batches

# ---- bench config ----------------------------------------------------------
TABLES = 4
ROWS_PER_TABLE = 20_000
EMBED_DIM = 32
BATCH = 64  # requests per micro-batch (R)
LOOKUPS = 8
STEPS = 60
CACHE_FRAC = 0.25
WINDOW = 2
SEED = 0

DESIGNS = ("nocache-serve", "static-serve", "scratchpipe-serve")
DEFAULT_DEPTHS = (0, 1, 2, 4, 8)

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")


def _sizing(tiny: bool) -> Dict[str, int]:
    if tiny:
        return dict(
            tables=2, rows=2_000, dim=16, batch=8, lookups=4, steps=24
        )
    return dict(
        tables=TABLES,
        rows=ROWS_PER_TABLE,
        dim=EMBED_DIM,
        batch=BATCH,
        lookups=LOOKUPS,
        steps=STEPS,
    )


def _record_trace(path: str, scenario: str, sz: Dict[str, int]) -> TableGroup:
    group = TableGroup.uniform(sz["tables"], sz["rows"], sz["dim"])
    stream = scenario_batches(
        scenario,
        group,
        sz["steps"],
        batch_size=sz["batch"],
        lookups_per_table=sz["lookups"],
        seed=SEED,
    )
    record_serving_trace(
        path,
        group,
        stream,
        steps=sz["steps"],
        provenance={"scenario": scenario, "seed": SEED},
    )
    return group


def _trace_batches(path: str) -> List[np.ndarray]:
    reader = TraceReader(path)
    return [reader.batch(i)[0] for i in range(reader.num_batches)]


def _make_backend(design: str, group: TableGroup, trace_path: str, sz, *, kernel):
    host = HostEmbeddingTable(group.total_rows, sz["dim"], seed=SEED + 1)
    if design == "nocache-serve":
        return make_runtime(design, host, None, kernel=kernel)
    if design == "static-serve":
        hot = hot_ids_from_trace(
            trace_path, CACHE_FRAC, profile_batches=max(2, sz["steps"] // 4)
        )
        return make_runtime(design, host, None, hot_ids=hot, kernel=kernel)
    num_slots = int(group.total_rows * CACHE_FRAC)
    return make_runtime(
        design,
        host,
        None,
        num_slots=num_slots,
        window=WINDOW,
        table_group=group,
        kernel=kernel,
    )


def _design_row(design: str, res: dict) -> dict:
    return {
        "design": design,
        "depth": res["depth"],
        "served": res["served"],
        "latency": res["latency"],
        "lookups_per_s": res["lookups_per_s"],
        "hit_rate": res["hit_rate"],
        "hit_lookup_rate": res["hit_lookup_rate"],
        "emergency_rate": res["emergency_rate"],
    }


def run_suite(
    scenario: str, depths, sz: Dict[str, int], *, kernel: str = "xla"
) -> dict:
    tmp = tempfile.mkdtemp(prefix="serve_trace_")
    trace_path = os.path.join(tmp, scenario)
    group = _record_trace(trace_path, scenario, sz)
    batches = _trace_batches(trace_path)

    designs = []
    parity_bags: Dict[str, list] = {}
    for design in DESIGNS:
        depth = WINDOW if design == "scratchpipe-serve" else 0
        backend = _make_backend(design, group, trace_path, sz, kernel=kernel)
        res = replay_serving(
            backend, batches, depth=depth, collect_bags=True
        )
        parity_bags[design] = res.pop("bags")
        designs.append(_design_row(design, res))
        lat = res["latency"]
        print(
            f"{design:<18} depth={depth} p50={lat['p50_ms']:.2f}ms "
            f"p99={lat['p99_ms']:.2f}ms {res['lookups_per_s']:,.0f} lookups/s "
            f"hit={res['hit_rate']:.3f}",
            flush=True,
        )

    # bit-parity: read-only caching must not change a single lookup result
    oracle = parity_bags["nocache-serve"]
    parity = {
        d: all(
            np.array_equal(a, b) for a, b in zip(parity_bags[d], oracle)
        )
        for d in DESIGNS
        if d != "nocache-serve"
    }

    curve = []
    for depth in depths:
        backend = _make_backend(
            "scratchpipe-serve", group, trace_path, sz, kernel=kernel
        )
        res = replay_serving(backend, batches, depth=depth)
        curve.append(_design_row("scratchpipe-serve", res))
        print(
            f"curve depth={depth} hit={res['hit_rate']:.3f} "
            f"emergency={res['emergency_rate']:.3f} "
            f"p99={res['latency']['p99_ms']:.2f}ms",
            flush=True,
        )

    return {
        "schema": "bench_serve/v1",
        "machine": machine_info(jax.default_backend()),
        "config": {**sz, "cache_frac": CACHE_FRAC, "window": WINDOW,
                   "kernel": kernel, "scenario": scenario},
        "designs": designs,
        "hit_rate_vs_depth": curve,
        "parity_vs_nocache": parity,
    }


def check(result: dict) -> List[str]:
    """Sanity assertions for the CI serving-smoke job."""
    problems = []
    seen = {d["design"] for d in result["designs"]}
    for d in DESIGNS:
        if d not in seen:
            problems.append(f"design {d} missing from results")
    for d in result["designs"]:
        lat = d["latency"]
        if not (0 < lat["p50_ms"] <= lat["p99_ms"]):
            problems.append(
                f"{d['design']}: insane latency fields p50={lat['p50_ms']} "
                f"p99={lat['p99_ms']}"
            )
        if d["lookups_per_s"] <= 0:
            problems.append(f"{d['design']}: lookups_per_s <= 0")
    for design, ok in result["parity_vs_nocache"].items():
        if not ok:
            problems.append(f"{design}: lookup results differ from nocache oracle")
    window = result["config"]["window"]
    deep = [c for c in result["hit_rate_vs_depth"] if c["depth"] >= window]
    if not deep:
        problems.append(f"no curve point at depth >= window ({window})")
    for c in deep:
        if c["hit_rate"] < 1.0:
            problems.append(
                f"depth {c['depth']} >= window {window} but hit_rate "
                f"{c['hit_rate']:.4f} < 1.0 — the always-hit guarantee broke"
            )
    return problems


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="CI smoke sizing")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--scenario", default="inference_mix")
    ap.add_argument("--kernel", default="xla", choices=("xla", "pallas"))
    ap.add_argument(
        "--depths",
        default=",".join(str(d) for d in DEFAULT_DEPTHS),
        help="comma-separated queue depths for the hit-rate curve",
    )
    ap.add_argument("--out", default=os.path.normpath(OUT_PATH))
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    depths = tuple(int(d) for d in args.depths.split(",") if d != "")
    result = run_suite(args.scenario, depths, _sizing(args.tiny),
                       kernel=args.kernel)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"serve_latency,{args.out},{len(result['designs'])} designs")
    if args.check:
        problems = check(result)
        for p in problems:
            print(f"  [FAIL] {p}")
        if problems:
            raise SystemExit(1)
        print("  [PASS] serve_latency sanity")


if __name__ == "__main__":
    main()
