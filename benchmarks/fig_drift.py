"""Hit-rate-vs-time under non-stationary workloads (beyond the paper).

The paper evaluates stationary Zipf traces; production popularity drifts.
This figure records a non-stationary scenario (default: gradual hot-set
rotation) into the binary trace format, then replays the SAME trace through
every design in the EmbeddingCacheRuntime registry — nocache / static /
strawman / scratchpipe / sharded — and reports the train-time hit rate per
time window:

* the static top-N cache is provisioned by profiling the trace's own
  prefix (how a deployed static cache is built) and its hit rate decays as
  the hot set rotates away from the frozen profile;
* the look-ahead designs (strawman / scratchpipe / sharded) stay at 100%
  train-time hits by construction — the paper's always-hit guarantee holds
  under harder-than-paper conditions, because the guarantee comes from the
  dataset recording the future, not from the distribution standing still.

All designs run the identical recorded workload (bit-identical replay is
asserted as a validation check), with a no-op [Train] stage: this figure
measures cache dynamics, not the bandwidth-model latency.

``python -m benchmarks.fig_drift --scenario drift [--steps N] [--check]``
"""
from __future__ import annotations

import argparse
import json
import shutil
import tempfile
from typing import List, Optional

import numpy as np

from repro.core.host_table import HostEmbeddingTable
from repro.core.runtime import available_runtimes, make_runtime
from repro.core.table_group import TableGroup, TableSpec
from repro.traces import (
    TraceReplayStream,
    hot_ids_from_trace,
    record_trace,
    scenario_batches,
)

DESIGNS = ("nocache", "static", "strawman", "scratchpipe", "sharded")

# container-scale shapes: small enough for CI, large enough that the hot
# set dwarfs the batch working set (otherwise nothing meaningful decays)
ROWS = (32_768, 16_384, 8_192, 4_096)
EMBED_DIM = 16
BATCH = 64
LOOKUPS = 4
CACHE_FRAC = 0.10
PROFILE_FRAC = 6  # static profiles the first steps//PROFILE_FRAC batches


def _noop_train(storage, slots, batch):
    return storage, None


def _noop_train_sharded(storages, slots_all, batch):
    return list(storages), None


def _make_group(num_tables: int) -> TableGroup:
    rows = ROWS[:num_tables] if num_tables <= len(ROWS) else tuple(
        max(4_096, ROWS[0] >> t) for t in range(num_tables)
    )
    return TableGroup(
        [TableSpec(f"table{t}", r, EMBED_DIM) for t, r in enumerate(rows)]
    )


def _scenario_kw(scenario: str, steps: int) -> dict:
    if scenario == "drift":
        # hot set fully displaced ~2/3 into the run: early windows match
        # the profile, late windows have rotated completely past it
        return {"drift_rate": 0.25 / max(steps, 1)}
    if scenario == "flash_crowd":
        return {"period": max(8, steps // 3), "burst_len": max(4, steps // 6)}
    if scenario == "diurnal":
        return {"period": max(8, steps // 2)}
    if scenario == "cold_start":
        return {"growth_per_step": 0.5 / max(steps, 1)}
    return {}


def _run_one(design, trace_dir, group, steps, seed):
    stream = TraceReplayStream(trace_dir)
    host = HostEmbeddingTable(group.total_rows, group.dim, seed=seed)
    slots = max(1024, int(group.total_rows * CACHE_FRAC))
    floor = group.window_floor(BATCH * LOOKUPS)
    slots = max(slots, sum(min(floor, r) for r in group.rows))
    budgets = group.slot_budgets(slots, min_per_table=floor)
    if design == "nocache":
        runner = make_runtime("nocache", host, _noop_train)
    elif design == "static":
        hot = hot_ids_from_trace(
            trace_dir, CACHE_FRAC, profile_batches=max(1, steps // PROFILE_FRAC)
        )
        runner = make_runtime("static", host, _noop_train, hot_ids=hot)
    elif design == "sharded":
        runner = make_runtime(
            "sharded",
            host,
            _noop_train_sharded,
            num_slots=slots,
            table_group=group,
            slot_budgets=budgets,
        )
    else:
        runner = make_runtime(
            design,
            host,
            _noop_train,
            num_slots=slots,
            table_group=group,
            slot_budgets=budgets,
        )
    stats = runner.run(stream, lookahead_fn=stream.peek_ids)
    stream.close()
    train_hit = [s.hit_lookups / max(s.n_lookups, 1) for s in stats]
    plan_hit = [s.hit_rate for s in stats]
    return train_hit, plan_hit


def _windows(series: List[float], n: int) -> List[float]:
    edges = np.linspace(0, len(series), n + 1).astype(int)
    return [
        float(np.mean(series[lo:hi])) if hi > lo else float("nan")
        for lo, hi in zip(edges[:-1], edges[1:])
    ]


def run(
    steps: int = 72,
    num_tables: int = 4,
    scenario: str = "drift",
    windows: int = 6,
    seed: int = 0,
    trace_dir: Optional[str] = None,
) -> list:
    group = _make_group(num_tables)
    kw = _scenario_kw(scenario, steps)

    def gen():
        return scenario_batches(
            scenario,
            group,
            steps,
            batch_size=BATCH,
            lookups_per_table=LOOKUPS,
            locality="medium",
            seed=seed,
            **kw,
        )

    tmp = trace_dir or tempfile.mkdtemp(prefix=f"fig_drift_{scenario}_")
    record_trace(
        tmp,
        group,
        gen(),
        provenance={"generator": f"scenario:{scenario}", "seed": seed, **kw},
    )

    # validation check: the recorded trace replays bit-identically to its
    # source generator (ids AND payload, and the SAME batch count — a
    # truncated recording must fail, not pass on a matching prefix)
    replay = TraceReplayStream(tmp)
    identical = replay.num_batches == steps
    for (g_ref, p_ref), (g_got, p_got) in zip(gen(), replay):
        identical &= bool(np.array_equal(g_ref, g_got))
        identical &= bool(np.array_equal(p_ref["dense"], p_got["dense"]))
        identical &= bool(np.array_equal(p_ref["label"], p_got["label"]))
    identical &= replay.exhausted
    replay.close()

    rows = [
        {
            "bench": "fig_drift",
            "scenario": scenario,
            "design": "replay_check",
            "window": -1,
            "train_hit": float(identical),
            "plan_hit": float(identical),
        }
    ]
    missing = sorted(set(DESIGNS) - set(available_runtimes()))
    assert not missing, f"registry lost designs: {missing}"
    for design in DESIGNS:
        train_hit, plan_hit = _run_one(design, tmp, group, steps, seed)
        th, ph = _windows(train_hit, windows), _windows(plan_hit, windows)
        for w in range(windows):
            rows.append(
                {
                    "bench": "fig_drift",
                    "scenario": scenario,
                    "design": design,
                    "window": w,
                    "train_hit": round(th[w], 4),
                    "plan_hit": round(ph[w], 4),
                }
            )
    if trace_dir is None:
        shutil.rmtree(tmp, ignore_errors=True)
    return rows


def validate(rows) -> list:
    by = {
        (r["design"], r["window"]): r
        for r in rows
        if r["bench"] == "fig_drift"
    }
    wins = sorted({w for (_, w) in by if w >= 0})
    first, last = wins[0], wins[-1]

    def series(design, key="train_hit"):
        return [by[(design, w)][key] for w in wins]

    always_hit = ("strawman", "scratchpipe", "sharded")
    static_drop = by[("static", first)]["train_hit"] - by[("static", last)][
        "train_hit"
    ]
    checks = [
        (
            "trace replays bit-identically to its source generator",
            by[("replay_check", -1)]["train_hit"] == 1.0,
        ),
        (
            "scratchpipe train-time hit rate = 100% in every window",
            all(h == 1.0 for h in series("scratchpipe")),
        ),
        (
            "all look-ahead designs always-hit under drift",
            all(h == 1.0 for d in always_hit for h in series(d)),
        ),
        (
            "static hit rate measurably decays over the drift window",
            static_drop >= 0.10,
        ),
        (
            "static decay is monotone-ish (each window <= first + 5%)",
            all(
                h <= by[("static", first)]["train_hit"] + 0.05
                for h in series("static")
            ),
        ),
        ("nocache never hits", all(h == 0.0 for h in series("nocache"))),
    ]
    return checks


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="drift")
    ap.add_argument("--steps", type=int, default=72)
    ap.add_argument("--tables", type=int, default=4)
    ap.add_argument("--windows", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the recorded trace here (default: temp dir)")
    ap.add_argument("--out", default=None, help="write rows as JSON")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero if any validation check fails")
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    rows = run(
        steps=args.steps,
        num_tables=args.tables,
        scenario=args.scenario,
        windows=args.windows,
        seed=args.seed,
        trace_dir=args.trace_dir,
    )
    keys = ["bench", "scenario", "design", "window", "train_hit", "plan_hit"]
    print(",".join(keys))
    for r in rows:
        print(",".join(str(r[k]) for k in keys))
    checks = validate(rows)
    ok = True
    for desc, passed in checks:
        print(f"  [{'PASS' if passed else 'FAIL'}] fig_drift: {desc}")
        ok &= bool(passed)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "ok": ok}, f, indent=1)
    if args.check and not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
