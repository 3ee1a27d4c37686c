"""Mixed-precision capacity benchmark: rows resident and hit rate per format.

The mixed-precision scratchpad (core/quantize.py, DESIGN.md "Mixed-precision
cache") holds fp16/int8 replica rows against fp32 host masters, so the SAME
device byte budget holds 2x/4x the rows. This benchmark makes that claim
measurable and gateable:

  * every cell runs the SAME drift workload through a real ScratchPipe at the
    SAME nominal byte budget (``num_slots`` is denominated in fp32-row
    payload bytes; the runtime applies the per-precision capacity
    multiplier), so the only axis that moves is the replica format;
  * a drifting hot set sized past the fp32 cache makes capacity the binding
    resource — the extra fp16/int8 rows convert directly into a higher
    post-warmup hit rate;
  * per-precision xla-vs-pallas parity cells re-run a short trace under both
    kernel axes and compare final storage, scale column, host table and loss
    trajectory BITWISE (the scale-snap exact-product discipline of
    core/quantize.py is what makes this possible; see kernels/ref.py).

Results land in ``BENCH_capacity.json`` with machine provenance.  ``--check``
asserts the acceptance ordering — at equal byte budget:

    rows_resident:  fp16 == 2x fp32,  int8 == 4x fp32  (payload bytes equal)
    hit rate:       int8 >= fp16 > fp32  (post-warmup)
    parity:         xla == pallas bitwise, per precision

    PYTHONPATH=src python -m benchmarks.capacity [--tiny] [--check]
        [--out BENCH_capacity.json]
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, List

import jax
import numpy as np

from benchmarks.wallclock import machine_info
from repro.configs.base import DLRMConfig
from repro.core import scratchpad as sp
from repro.core.dlrm_runtime import DLRMTrainer
from repro.core.host_table import HostEmbeddingTable
from repro.core.quantize import SLOT_MULTIPLIER
from repro.core.runtime import make_runtime
from repro.core.table_group import TableGroup
from repro.data.lookahead import LookaheadStream
from repro.traces import scenario_batches

PRECISIONS = ("fp32", "fp16", "int8")
OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_capacity.json")

# full sizing: hot head of the medium-Zipf drift workload comfortably
# exceeds the fp32 slot budget, so capacity binds and the fp16/int8
# multipliers are visible in the hit rate (not just in the byte counters)
FULL = dict(tables=4, rows=100_000, dim=32, batch=64, lookups=4,
            slots=8_192, steps=120, warmup=12, drift_rate=0.01)
# CI smoke sizing: same shape, ~seconds per cell
TINY = dict(tables=2, rows=30_000, dim=16, batch=32, lookups=4,
            slots=2_048, steps=40, warmup=8, drift_rate=0.02)


def _cfg(p: dict, precision: str) -> DLRMConfig:
    return DLRMConfig(
        name="dlrm-capacity",
        num_tables=p["tables"],
        rows_per_table=p["rows"],
        embed_dim=p["dim"],
        lookups_per_table=p["lookups"],
        batch_size=p["batch"],
        bottom_mlp=(64, p["dim"]),
        top_mlp=(64, 1),
        precision=precision,
    )


def _batches(p: dict, group: TableGroup, steps: int) -> list:
    return list(
        scenario_batches(
            "drift",
            group,
            steps,
            batch_size=p["batch"],
            lookups_per_table=p["lookups"],
            locality="medium",
            seed=0,
            drift_rate=p["drift_rate"],
        )
    )


def _run_pipe(p: dict, precision: str, kernel: str, steps: int):
    """One ScratchPipe run at the shared nominal byte budget; returns
    (pipe, trainer, per-step stats) after draining and quiescing."""
    cfg = _cfg(p, precision)
    group = TableGroup.from_config(cfg)
    host = HostEmbeddingTable(group.total_rows, cfg.embed_dim, seed=1)
    trainer = DLRMTrainer(cfg, jax.random.key(0), lr=0.05, kernel=kernel)
    pipe = make_runtime(
        "scratchpipe",
        host,
        trainer.train_fn,
        num_slots=p["slots"],
        precision=precision,
        kernel=kernel,
        fused_train_fn=trainer.fused_train_fn,
    )
    stream = LookaheadStream(iter(_batches(p, group, steps)))
    stats = pipe.run(stream, lookahead_fn=stream.peek_ids)
    jax.block_until_ready(pipe.storage)
    return pipe, trainer, stats


def measure_cell(p: dict, precision: str) -> dict:
    """Hit rate and residency for one replica format at the shared budget."""
    pipe, trainer, stats = _run_pipe(p, precision, "xla", p["steps"])
    warm = stats[p["warmup"]:]
    losses = [float(s.aux["loss"]) for s in stats if s.aux]
    tr = pipe.traffic()
    # payload only (the slot-budget denomination); the int8 scale column is
    # metadata ON TOP of the budget, visible in cache_bytes (storage_bytes)
    payload = pipe.num_slots * p["dim"] * (4 // SLOT_MULTIPLIER[precision])
    return {
        "precision": precision,
        "nominal_slots": pipe.nominal_slots,
        "rows_resident": pipe.num_slots,
        "payload_bytes": payload,
        "cache_bytes": int(sp.storage_bytes(pipe.storage)),
        "hit_rate_warm": round(
            float(np.mean([s.hit_rate for s in warm])), 4
        ),
        "hit_rate_all": round(
            float(np.mean([s.hit_rate for s in stats])), 4
        ),
        "pcie_bytes_per_step": int(tr["pcie"].total / max(len(stats), 1)),
        "hbm_bytes_per_step": int(tr["hbm"].total / max(len(stats), 1)),
        "loss_final": round(float(np.mean(losses[-5:])), 6) if losses else None,
        "steps": len(stats),
    }


def parity_cell(p: dict, precision: str, steps: int = 10) -> dict:
    """Bitwise xla-vs-pallas comparison of a short end-to-end run."""
    outs = {}
    for kernel in ("xla", "pallas"):
        pipe, trainer, stats = _run_pipe(p, precision, kernel, steps)
        pipe.flush_to_host()
        st = pipe.storage
        outs[kernel] = {
            "storage": [np.asarray(a) for a in (st if isinstance(st, tuple) else (st,))],
            "host": np.asarray(pipe.host.data).copy(),
            "losses": [float(s.aux["loss"]) for s in stats if s.aux],
        }
    a, b = outs["xla"], outs["pallas"]
    same = (
        len(a["storage"]) == len(b["storage"])
        and all(
            np.array_equal(x, y, equal_nan=True)
            for x, y in zip(a["storage"], b["storage"])
        )
        and np.array_equal(a["host"], b["host"], equal_nan=True)
        and a["losses"] == b["losses"]
    )
    return {
        "precision": precision,
        "steps": steps,
        "bit_identical": bool(same),
        "loss_final": a["losses"][-1] if a["losses"] else None,
    }


def run_suite(p: dict) -> dict:
    runs: List[dict] = []
    for prec in PRECISIONS:
        cell = measure_cell(p, prec)
        runs.append(cell)
        print(
            f"{prec:<5} rows={cell['rows_resident']:>6} "
            f"payload={cell['payload_bytes']:>9}B "
            f"hit_warm={cell['hit_rate_warm']:.4f} "
            f"pcie/step={cell['pcie_bytes_per_step']}B "
            f"loss={cell['loss_final']}",
            flush=True,
        )
    parity = []
    for prec in PRECISIONS:
        cell = parity_cell(p, prec)
        parity.append(cell)
        print(
            f"parity {prec:<5} xla==pallas bitwise: {cell['bit_identical']}",
            flush=True,
        )
    return {
        "schema": "bench_capacity/v1",
        "machine": machine_info(jax.default_backend()),
        "config": p,
        "runs": runs,
        "parity": parity,
    }


def check(result: dict) -> List[str]:
    """The acceptance ordering (see module docstring)."""
    problems: List[str] = []
    by_prec: Dict[str, dict] = {c["precision"]: c for c in result["runs"]}
    for prec in PRECISIONS:
        if prec not in by_prec:
            problems.append(f"precision {prec} missing from runs")
    if problems:
        return problems
    fp32 = by_prec["fp32"]
    for prec in ("fp16", "int8"):
        c = by_prec[prec]
        mult = SLOT_MULTIPLIER[prec]
        if c["rows_resident"] != mult * fp32["rows_resident"]:
            problems.append(
                f"{prec}: rows_resident {c['rows_resident']} != "
                f"{mult}x fp32 ({mult * fp32['rows_resident']})"
            )
        if c["payload_bytes"] != fp32["payload_bytes"]:
            problems.append(
                f"{prec}: payload bytes {c['payload_bytes']} != fp32 "
                f"{fp32['payload_bytes']} (budgets not equal-byte)"
            )
        if not c["hit_rate_warm"] > fp32["hit_rate_warm"]:
            problems.append(
                f"{prec}: post-warmup hit rate {c['hit_rate_warm']} not "
                f"strictly above fp32 {fp32['hit_rate_warm']} — the extra "
                "capacity did not bind"
            )
    if by_prec["int8"]["hit_rate_warm"] < by_prec["fp16"]["hit_rate_warm"]:
        problems.append(
            f"int8 hit rate {by_prec['int8']['hit_rate_warm']} below fp16 "
            f"{by_prec['fp16']['hit_rate_warm']} (capacity ordering broken)"
        )
    for cell in result["parity"]:
        if not cell["bit_identical"]:
            problems.append(
                f"{cell['precision']}: xla vs pallas NOT bit-identical"
            )
    return problems


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="CI smoke sizing")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--out", default=os.path.normpath(OUT_PATH))
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    p = TINY if args.tiny else FULL
    result = run_suite(dict(p))
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"capacity,{args.out},{len(result['runs'])} cells")
    if args.check:
        problems = check(result)
        for prob in problems:
            print(f"  [FAIL] {prob}")
        if problems:
            raise SystemExit(1)
        print("  [PASS] capacity ordering + parity")


if __name__ == "__main__":
    main()
