"""Recovery benchmark: checkpoint overhead, restore latency, and MTTR.

The fault-tolerance stack (DESIGN.md "Fault tolerance & recovery") claims
crash-consistent checkpoints at ANY pipeline cycle, restore+fast-forward
that reproduces the uninterrupted run bit-for-bit, and bounded recovery
time. This benchmark prices those claims on a real ScratchPipe + DLRM
stack over a drifting workload:

  * baseline      — supervised overlapped pipeline, no checkpointing.
  * checkpoint    — the same run saving a full crash-consistent snapshot
                    (planner + scratchpad + host table + in-flight window)
                    every ``ckpt_every`` admitted batches, blocking saves
                    so the measured overhead is the worst case (production
                    saves run on the background writer thread).
  * restore       — cold-start a fresh runtime from the latest snapshot.
  * mttr          — inject host-row corruption mid-run (repro.chaos); the
                    checksum guard detects it, EmbeddingTrainSupervisor
                    rebuilds + restores + fast-forwards; MTTR = detect ->
                    parity-restored wall-clock. The run's losses and final
                    host table must be IDENTICAL to the never-failed
                    baseline — recovery that changes the model is not
                    recovery.

    PYTHONPATH=src python -m benchmarks.recovery [--tiny] [--check]
        [--out BENCH_recovery.json]
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import List

import jax
import numpy as np

from benchmarks.wallclock import machine_info
from repro.chaos import ChaosInjector, ChaosPlan
from repro.checkpoint import CheckpointManager
from repro.configs.base import DLRMConfig
from repro.core.dlrm_runtime import DLRMTrainer
from repro.core.host_table import HostEmbeddingTable
from repro.core.runtime import make_runtime
from repro.core.table_group import TableGroup
from repro.data.lookahead import LookaheadStream
from repro.runtime import EmbeddingTrainSupervisor, SupervisePolicy
from repro.traces import scenario_batches

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_recovery.json")

FULL = dict(tables=4, rows=100_000, dim=32, batch=64, lookups=4,
            slots=8_192, steps=120, ckpt_every=20, fail_at=50)
TINY = dict(tables=2, rows=20_000, dim=16, batch=32, lookups=4,
            slots=2_048, steps=30, ckpt_every=8, fail_at=18)


def _cfg(p: dict) -> DLRMConfig:
    return DLRMConfig(
        name="dlrm-recovery",
        num_tables=p["tables"],
        rows_per_table=p["rows"],
        embed_dim=p["dim"],
        lookups_per_table=p["lookups"],
        batch_size=p["batch"],
        num_dense_features=4,
        bottom_mlp=(64, p["dim"]),
        top_mlp=(64, 1),
    )


def _batches(p: dict, group: TableGroup) -> list:
    return list(
        scenario_batches(
            "drift",
            group,
            p["steps"],
            batch_size=p["batch"],
            lookups_per_table=p["lookups"],
            num_dense_features=4,
            seed=7,
        )
    )


def _build(p: dict):
    cfg = _cfg(p)
    host = HostEmbeddingTable(
        TableGroup.from_config(cfg).total_rows, cfg.embed_dim, seed=1
    )
    trainer = DLRMTrainer(cfg, jax.random.key(1), lr=0.05)
    pipe = make_runtime(
        "scratchpipe",
        host,
        trainer.train_fn,
        num_slots=p["slots"],
        executor="overlapped",
        supervise=SupervisePolicy(backoff=0.0),
    )
    return pipe, trainer


def _losses(stats) -> List[float]:
    return [float(s.aux["loss"]) for s in stats if s.aux]


def _drive(pipe, batches) -> list:
    stream = LookaheadStream(iter(batches))
    return pipe.run(stream, lookahead_fn=stream.peek_ids)


def _dir_bytes(path: str) -> int:
    total = 0
    for dirpath, _dirs, files in os.walk(path):
        for f in files:
            total += os.path.getsize(os.path.join(dirpath, f))
    return total


def run_suite(p: dict, workdir: str) -> dict:
    group = TableGroup.from_config(_cfg(p))
    batches = _batches(p, group)

    # warmup pass: populate the jit compile cache so the baseline and the
    # checkpointed run compare steady-state costs, not compile time
    pipe, _ = _build(p)
    _drive(pipe, batches[: min(8, len(batches))])
    pipe.close()

    # -- baseline: no checkpointing ------------------------------------- #
    pipe, trainer = _build(p)
    t0 = time.perf_counter()
    stats = _drive(pipe, batches)
    base_s = time.perf_counter() - t0
    base_losses = _losses(stats)
    pipe.flush_to_host()
    base_host = pipe.host.data.copy()
    pipe.close()
    baseline = {
        "steps": len(stats),
        "total_s": round(base_s, 3),
        "ms_per_step": round(base_s / max(len(stats), 1) * 1e3, 3),
    }
    print(f"baseline        {baseline['ms_per_step']:>8.2f} ms/step", flush=True)

    # -- checkpoint overhead (blocking saves = worst case) --------------- #
    ck_dir = os.path.join(workdir, "ck_overhead")
    ckpt = CheckpointManager(ck_dir, keep=2)
    pipe, trainer = _build(p)
    save_ms: List[float] = []
    t0 = time.perf_counter()
    admitted = 0
    for ids, batch in batches:
        pipe.run_one_cycle(ids, batch)
        admitted += 1
        if admitted % p["ckpt_every"] == 0:
            t1 = time.perf_counter()
            ckpt.save(
                admitted,
                {"mlps": trainer.mlps},
                host_arrays=pipe.state_arrays(),
                extra={"admitted": admitted, "trained": len(pipe.stats)},
                blocking=True,
            )
            save_ms.append((time.perf_counter() - t1) * 1e3)
    while pipe._window:
        pipe.drain_one_cycle()
    ck_s = time.perf_counter() - t0
    pipe.close()
    ck_bytes = _dir_bytes(os.path.join(ck_dir, f"step_{admitted - admitted % p['ckpt_every']}")) \
        if save_ms else 0
    checkpoint = {
        "every": p["ckpt_every"],
        "saves": len(save_ms),
        "save_ms_mean": round(float(np.mean(save_ms)), 3) if save_ms else 0.0,
        "save_ms_max": round(float(np.max(save_ms)), 3) if save_ms else 0.0,
        "snapshot_bytes": ck_bytes,
        "overhead_pct": round((ck_s - base_s) / base_s * 100.0, 2),
    }
    print(
        f"checkpoint      save={checkpoint['save_ms_mean']:>7.2f} ms mean "
        f"({checkpoint['saves']} saves, {ck_bytes / 1e6:.2f} MB each), "
        f"overhead {checkpoint['overhead_pct']:+.1f}%",
        flush=True,
    )

    # -- restore latency (cold start from the latest snapshot) ----------- #
    pipe, trainer = _build(p)
    t0 = time.perf_counter()
    man = ckpt.manifest()
    arrays = {name: ckpt.restore_host(name) for name in man["host"]}
    pipe.load_state_arrays(arrays)
    state, _ = ckpt.restore({"mlps": trainer.mlps})
    trainer.mlps = state["mlps"]
    restore_ms = (time.perf_counter() - t0) * 1e3
    pipe.close()
    restore = {"restore_ms": round(restore_ms, 2)}
    print(f"restore         {restore_ms:>8.2f} ms", flush=True)

    # -- MTTR: injected corruption -> detect -> restore -> parity -------- #
    mttr_dir = os.path.join(workdir, "ck_mttr")
    ckpt2 = CheckpointManager(mttr_dir, keep=2)
    spec = f"corrupt-row@{p['fail_at']}:8"
    first = [True]

    def runtime_factory():
        pipe, trainer = _build(p)
        if first[0]:
            first[0] = False
            ChaosInjector(ChaosPlan.parse(spec), seed=3).attach(pipe)
        return pipe, trainer

    def stream_factory(skip):
        return LookaheadStream(iter(batches[skip:]))

    sup = EmbeddingTrainSupervisor(
        ckpt2,
        runtime_factory,
        stream_factory,
        ckpt_every=p["ckpt_every"],
        verify_every=1,
        blocking_saves=True,
    )
    t0 = time.perf_counter()
    stats2, report = sup.run(p["steps"])
    mttr_s = time.perf_counter() - t0
    sup.runtime.flush_to_host()
    parity = _losses(stats2) == base_losses and np.array_equal(
        sup.runtime.host.data, base_host
    )
    sup.runtime.close()
    last_ck = p["fail_at"] - p["fail_at"] % p["ckpt_every"]
    mttr = {
        "inject": spec,
        "restarts": report.restarts,
        "restore_ms": [round(m, 2) for m in report.restore_ms],
        "steps_replayed": p["fail_at"] - last_ck,
        "run_s": round(mttr_s, 3),
        "parity": bool(parity),
    }
    print(
        f"mttr            restarts={report.restarts} "
        f"restore={mttr['restore_ms']} ms, "
        f"{mttr['steps_replayed']} steps replayed, parity={parity}",
        flush=True,
    )

    return {
        "schema": "bench_recovery/v1",
        "machine": machine_info(jax.default_backend()),
        "config": p,
        "baseline": baseline,
        "checkpoint": checkpoint,
        "restore": restore,
        "mttr": mttr,
    }


def check(result: dict) -> List[str]:
    problems: List[str] = []
    if result["checkpoint"]["saves"] < 1:
        problems.append("no checkpoints were written")
    if result["mttr"]["restarts"] < 1:
        problems.append("injected corruption did not trigger a restart")
    if not result["mttr"]["parity"]:
        problems.append(
            "recovered run is NOT bit-identical to the never-failed "
            "baseline (losses or final host table diverge)"
        )
    return problems


def main():
    import tempfile

    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="CI smoke sizing")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--out", default=os.path.normpath(OUT_PATH))
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    p = TINY if args.tiny else FULL
    with tempfile.TemporaryDirectory(prefix="bench_recovery_") as workdir:
        result = run_suite(dict(p), workdir)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"recovery,{args.out}")
    if args.check:
        problems = check(result)
        for prob in problems:
            print(f"  [FAIL] {prob}")
        if problems:
            raise SystemExit(1)
        print("  [PASS] recovery parity + restart + checkpoints")


if __name__ == "__main__":
    main()
