"""Benchmark driver: one function per paper table/figure.

``python -m benchmarks.run [--steps N] [--skip-roofline]``

Prints ``name,us_per_call,derived`` CSV rows (plus per-benchmark detail CSVs)
and the paper-claim validation checklist for each figure. Roofline rows are
read from benchmarks/results/roofline/ (produced by ``python -m
benchmarks.roofline``, a separate process because it forces 512 host
devices).

Every cache-design run executed during the suite is also drained into
``benchmarks/results/BENCH_summary.json`` — one machine-readable record per
(design, locality) with hit_rate and iter_ms_paper, so the perf trajectory
is tracked across PRs instead of living in scrollback.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import time


def _emit(rows):
    if not rows:
        return
    keys = sorted({k for r in rows for k in r})
    print(",".join(keys))
    for r in rows:
        print(",".join(str(r.get(k, "")) for k in keys))


def _checks(name, checks):
    ok = True
    for desc, passed in checks:
        print(f"  [{'PASS' if passed else 'FAIL'}] {name}: {desc}")
        ok &= bool(passed)
    return ok


def _csv_line(name, t0, derived):
    us = (time.time() - t0) * 1e6
    print(f"{name},{us:.0f},{derived}")


SUMMARY_PATH = os.path.join(
    os.path.dirname(__file__), "results", "BENCH_summary.json"
)


def write_summary(all_ok: bool, total_seconds: float, path: str = SUMMARY_PATH):
    """Drain the run_design results log into a machine-readable summary."""
    from benchmarks.common import drain_results_log

    designs = [
        {
            "design": r.design,
            "locality": r.locality,
            "source": r.source,
            # [Plan] placement the run executed with (host | device)
            "planner": r.planner,
            "cache_frac": r.cache_frac,
            "steps": r.steps,
            "hit_rate": round(r.hit_rate, 4),
            "iter_ms": round(r.iter_ms, 3),
            "iter_ms_paper": round(r.iter_ms_paper, 3),
            # measured wall-clock on THIS container — a different column
            # from the model-derived iter_ms, never mixed (see
            # benchmarks/wallclock.py for the dedicated measured bench)
            "wall_ms": round(r.wall_ms, 3),
            "wall_steps_per_s": round(1e3 / r.wall_ms, 3) if r.wall_ms > 0 else None,
            "error": r.error,
        }
        for r in drain_results_log()
    ]
    # same machine-class provenance block as BENCH_wallclock.json and
    # BENCH_serve.json — all three bench artifacts share one schema for it
    import jax

    from benchmarks.wallclock import machine_info

    summary = {
        "schema": "bench_summary/v1",
        "all_claims_ok": bool(all_ok),
        "total_bench_seconds": round(total_seconds, 1),
        "machine": machine_info(jax.default_backend()),
        "designs": designs,
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(summary, f, indent=1)
    print(f"bench_summary,{path},{len(designs)} design rows")
    return summary


def run_figures(steps: int, num_tables: int = 8):
    from benchmarks import (
        fig6_hitrate,
        fig12_breakdown,
        fig13_speedup,
        fig14_energy,
        fig15_sensitivity,
        overhead,
        table1_cost,
    )

    all_ok = True
    for mod, name in (
        (fig6_hitrate, "fig6_hitrate"),
        (fig12_breakdown, "fig12_breakdown"),
        (fig13_speedup, "fig13_speedup"),
        (fig14_energy, "fig14_energy"),
        (fig15_sensitivity, "fig15_sensitivity"),
        (table1_cost, "table1_cost"),
        (overhead, "overhead"),
    ):
        t0 = time.time()
        varnames = mod.run.__code__.co_varnames
        kwargs = {}
        if "steps" in varnames:
            kwargs["steps"] = steps
        if "num_tables" in varnames:
            kwargs["num_tables"] = num_tables
        rows = mod.run(**kwargs)
        print(f"\n=== {name} ===", flush=True)
        _emit(rows)
        checks = mod.validate(rows)
        all_ok &= _checks(name, checks)
        derived = ";".join(f"{d}={'OK' if p else 'FAIL'}" for d, p in checks)
        _csv_line(name, t0, derived)
        # drop jit executables + device buffers between modules (the full
        # suite otherwise accumulates several GB of XLA state on one host)
        import gc

        import jax

        jax.clear_caches()
        gc.collect()
    return all_ok


def run_roofline_summary():
    here = os.path.join(os.path.dirname(__file__), "results", "roofline")
    files = sorted(glob.glob(os.path.join(here, "*.json")))
    rows = []
    for f in files:
        r = json.load(open(f))
        if not r.get("ok"):
            rows.append({"arch": r["arch"], "shape": r["shape"], "error": r.get("error", "")})
            continue
        rows.append(
            {
                "arch": r["arch"],
                "shape": r["shape"],
                "compute_ms": round(r["compute_s"] * 1e3, 2),
                "memory_ms": round(r["memory_s"] * 1e3, 2),
                "collective_ms": round(r["collective_s"] * 1e3, 2),
                "dominant": r["dominant"],
                "useful_flops_ratio": round(r["useful_flops_ratio"], 3),
                "roofline_fraction": round(r["roofline_fraction"], 4),
            }
        )
    print("\n=== roofline (per arch x shape, single-pod 16x16) ===")
    _emit(rows)
    return rows


def run_dryrun_summary():
    here = os.path.join(os.path.dirname(__file__), "results", "dryrun")
    files = sorted(glob.glob(os.path.join(here, "*.json")))
    rows = []
    for f in files:
        r = json.load(open(f))
        rec = {
            "arch": r["arch"],
            "shape": r["shape"],
            "mesh": r["mesh"],
            "ok": r.get("ok", False),
        }
        if r.get("ok"):
            mem = r.get("memory", {})
            rec["peak_GB_per_dev"] = round(
                mem.get("peak_memory_in_bytes", 0) / 1e9, 2
            )
            rec["collectives"] = r["collectives"]["total"]["count"]
        else:
            rec["error"] = r.get("error", "")[:60]
        rows.append(rec)
    print("\n=== dry-run (lower+compile) summary ===")
    _emit(rows)
    n_ok = sum(1 for r in rows if r["ok"])
    print(f"dryrun_cells,{len(rows)},ok={n_ok}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument(
        "--tables",
        type=int,
        default=8,
        help="embedding tables in the DLRM cache benchmarks (1 = the "
        "single-table scenario; 8 = the paper's config)",
    )
    ap.add_argument("--skip-roofline", action="store_true")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    if args.tables < 1:
        ap.error("--tables must be >= 1")
    t0 = time.time()
    ok = run_figures(args.steps, args.tables)
    run_dryrun_summary()
    if not args.skip_roofline:
        run_roofline_summary()
    write_summary(ok, time.time() - t0)
    print(f"\ntotal_bench_seconds,{time.time() - t0:.1f},all_claims={'OK' if ok else 'CHECK'}")


if __name__ == "__main__":
    main()
