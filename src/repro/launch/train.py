"""Training launcher: ``python -m repro.launch.train --arch <id> [--smoke]``.

LM archs run the pjit train step (AdamW + ZeRO-1) over a synthetic token
stream under the TrainSupervisor (checkpoint/restart, NaN quarantine).
``--arch dlrm-scratchpipe`` runs the paper's system: host-resident tables +
ScratchPipe pipeline + the DLRM [Train] stage.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import obs
from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.launch import steps as S
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import api
from repro.runtime import TrainSupervisor


def obs_setup(trace_out, metrics_out, jax_annotations=False):
    """Build and globally install the opt-in telemetry pair (either side
    may be None). Every runtime/stream constructed afterwards picks them
    up via ``repro.obs.resolve`` — one call covers all threads."""
    tracer = obs.Tracer(jax_annotations=jax_annotations) if trace_out else None
    metrics = obs.MetricsRegistry() if metrics_out else None
    if tracer is not None or metrics is not None:
        obs.install(tracer, metrics)
    return tracer, metrics


def obs_export(trace_out, metrics_out, tracer, metrics, provenance):
    """Write the artifacts and clear the global install (also on error
    paths — callers wrap the run in try/finally)."""
    try:
        if metrics is not None:
            metrics.write_jsonl(metrics_out, provenance=provenance)
            print(f"metrics snapshot -> {metrics_out}")
        if tracer is not None:
            n = tracer.export_chrome(trace_out)
            print(f"chrome trace -> {trace_out} ({n} events)")
    finally:
        obs.install(None, None)


def synth_lm_stream(cfg, shape, steps, seed=0):
    from repro.configs.base import ShapeSpec

    for i in range(steps):
        yield api.synth_batch(cfg, shape, seed=seed + i)


def train_lm(args):
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh() if args.smoke else make_production_mesh()
    from repro.configs.base import ShapeSpec

    shape = (
        ShapeSpec("smoke", args.seq_len, args.batch or 8, "train")
        if args.smoke
        else ShapeSpec("train_4k", 4096, 256, "train")
    )
    with jax.set_mesh(mesh):
        train_step, specs, opt = S.make_train_step(cfg, mesh, lr=args.lr)
        from repro.parallel.sharding import mesh_axes

        params = api.init(cfg, jax.random.key(args.seed), mesh_axes(mesh))
        opt_state = opt.init(params)
        step_jit = jax.jit(train_step, donate_argnums=(0, 1))

        ckpt = CheckpointManager(args.ckpt_dir, keep=2)

        def step_fn(state, batch):
            params, opt_state = state
            params, opt_state, metrics = step_jit(params, opt_state, batch)
            return (params, opt_state), {
                "loss": float(metrics["loss"]),
                "grad_norm": float(metrics["grad_norm"]),
            }

        def stream_factory(skip):
            it = synth_lm_stream(cfg, shape, args.steps, seed=args.seed)
            for _ in range(skip):
                next(it)
            return it

        sup = TrainSupervisor(
            ckpt, step_fn, stream_factory, ckpt_every=args.ckpt_every
        )
        t0 = time.time()
        state, report = sup.run((params, opt_state), args.steps)
        dt = time.time() - t0
        print(
            f"done: steps={report.steps_run} restarts={report.restarts} "
            f"time={dt:.1f}s ({dt / max(report.steps_run, 1):.3f}s/step)"
        )


def _state_digest(pipe, trainer, stats) -> str:
    """SHA-256 over the final host tables, dense params, and the loss
    trajectory — one line two runs can diff to prove bit-parity (the CI
    chaos-smoke job compares an injected run against a clean twin)."""
    import hashlib

    h = hashlib.sha256()
    pipes = getattr(pipe, "pipes", None)
    hosts = [p.host for p in pipes] if pipes else [pipe.host]
    for host in hosts:
        h.update(np.ascontiguousarray(host.data).tobytes())
    if trainer is not None:
        for leaf in jax.tree_util.tree_leaves(trainer.mlps):
            h.update(np.asarray(leaf).tobytes())
    for s in stats:
        loss = s.aux.get("loss") if isinstance(s.aux, dict) else s.aux
        if loss is not None:
            h.update(np.float64(loss).tobytes())
    return h.hexdigest()


def _train_dlrm_supervised(args, build, batches, reader):
    """Run DLRM training under EmbeddingTrainSupervisor: periodic
    crash-consistent checkpoints, restore+fast-forward on faults, and
    (with --chaos) deterministic fault injection on the FIRST runtime
    incarnation only — the rebuilt runtime after a restart is clean, like
    a replaced node."""
    from repro.checkpoint import CheckpointManager
    from repro.data.lookahead import LookaheadStream
    from repro.runtime import EmbeddingTrainSupervisor

    plan = None
    injectors = []
    if args.chaos:
        from repro.chaos import ChaosInjector, ChaosPlan

        plan = ChaosPlan.parse(args.chaos)
        print(f"chaos plan: {plan.spec} (seed {args.chaos_seed})")
    first = [True]

    def runtime_factory():
        _host, trainer, pipe = build(supervised=True)
        if plan is not None and first[0]:
            first[0] = False
            injectors.append(
                ChaosInjector(plan, seed=args.chaos_seed).attach(pipe)
            )
        return pipe, trainer

    def stream_factory(skip):
        if reader is not None:
            from repro.traces import TraceReplayStream

            return TraceReplayStream(reader, start=skip, stop=args.steps)
        it = iter(batches(args.steps))
        for _ in range(skip):
            next(it)
        return LookaheadStream(it)

    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    sup = EmbeddingTrainSupervisor(
        ckpt,
        runtime_factory,
        stream_factory,
        ckpt_every=args.ckpt_every,
        verify_every=args.verify_every,
    )
    t0 = time.time()
    stats, report = sup.run(args.steps)
    dt = time.time() - t0
    fired = [e.spec for inj in injectors for e in inj.fired]
    print(
        f"supervised: restarts={report.restarts} "
        f"checkpoints={report.checkpoints} "
        f"nan_skipped={report.nan_steps_skipped} "
        f"restore_ms={[round(m, 1) for m in report.restore_ms]} "
        f"chaos_fired={fired}"
    )
    return sup.runtime, sup.trainer, stats, report, dt


def train_dlrm(args):
    import dataclasses
    import itertools

    from repro.configs.dlrm_scratchpipe import (
        multi_table_config,
        multi_table_smoke_config,
    )
    from repro.core.dlrm_runtime import DLRMTrainer
    from repro.core.host_table import HostEmbeddingTable
    from repro.core.runtime import make_runtime
    from repro.core.table_group import TableGroup
    from repro.data.lookahead import LookaheadStream
    from repro.data.synthetic import (
        TraceConfig,
        dlrm_batches,
        dlrm_batches_group,
        hot_ids_for_group,
    )
    from repro.traces import (
        TraceReader,
        TraceRecorder,
        TraceReplayStream,
        derive_pad_buckets,
        hot_ids_from_trace,
        profile_hot_ids,
        scenario_batches,
    )

    reader = None
    if args.trace:  # replay a recorded workload trace
        reader = TraceReader(args.trace)
        if reader.num_batches < 1:
            raise SystemExit(
                f"--trace {args.trace}: empty trace (0 recorded batches)"
            )
        if reader.num_dense_features < 1:
            raise SystemExit(
                f"--trace {args.trace}: no dense features (not a DLRM trace)"
            )
        base = (
            get_smoke_config("dlrm-scratchpipe")
            if args.smoke
            else get_config("dlrm-scratchpipe")
        )
        group = reader.group
        # the trace manifest defines the workload shape; the MLP stack
        # follows (bottom-MLP output must match the trace's embed dim)
        cfg = dataclasses.replace(
            base,
            name="dlrm-trace",
            table_rows=tuple(group.rows),
            embed_dim=group.dim,
            lookups_per_table=reader.lookups_per_table,
            num_dense_features=reader.num_dense_features,
            batch_size=reader.batch_size,
            bottom_mlp=tuple(base.bottom_mlp[:-1]) + (group.dim,),
        )
        batch = reader.batch_size
        args.steps = min(args.steps, reader.num_batches)
    else:
        if args.tables:  # heterogeneous multi-table scenario
            cfg = (
                multi_table_smoke_config(args.tables)
                if args.smoke
                else multi_table_config(args.tables)
            )
        else:
            cfg = (
                get_smoke_config("dlrm-scratchpipe")
                if args.smoke
                else get_config("dlrm-scratchpipe")
            )
        if args.rows:  # host-RAM cut of the uniform config's table height
            cfg = dataclasses.replace(cfg, rows_per_table=args.rows)
        group = TableGroup.from_config(cfg)
        batch = args.batch or cfg.batch_size
    if args.precision != "fp32":
        # scratchpad replica precision: fp32 masters stay on host; the
        # trainer reads it from the config (so do the TableGroup specs)
        cfg = dataclasses.replace(
            cfg, precision=args.precision, rounding=args.rounding
        )
        group = (
            group.with_precision(args.precision)
            if reader is not None
            else TableGroup.from_config(cfg)
        )
    rows = group.total_rows
    slots = max(2048, int(rows * cfg.cache_fraction))

    def batches(steps):
        if reader is not None:
            return TraceReplayStream(reader, stop=steps)
        if args.scenario:  # non-stationary generator (repro.traces)
            return scenario_batches(
                args.scenario,
                group,
                steps,
                batch_size=batch,
                lookups_per_table=cfg.lookups_per_table,
                locality=args.locality,
                num_dense_features=cfg.num_dense_features,
                seed=args.seed,
            )
        if args.tables:
            return dlrm_batches_group(
                group,
                steps,
                batch_size=batch,
                lookups_per_table=cfg.lookups_per_table,
                locality=args.locality,
                num_dense_features=cfg.num_dense_features,
                seed=args.seed,
            )
        tc = TraceConfig(
            num_tables=cfg.num_tables,
            rows_per_table=cfg.rows_per_table,
            lookups_per_table=cfg.lookups_per_table,
            batch_size=batch,
            locality=args.locality,
            seed=args.seed,
        )
        return dlrm_batches(tc, steps)

    hetero_rows_present = len(set(group.rows)) > 1
    if args.tables or (reader is not None and hetero_rows_present):
        # heterogeneous scenario: per-table budgets with the §VI-D window
        # floor (worst-case 6-batch window working set per table)
        floor = group.window_floor(batch * cfg.lookups_per_table)
        slots = max(slots, sum(min(floor, r) for r in group.rows))
        # byte-budget slot math: per-table budgets in ROWS of each table's
        # replica precision (== the plain budgets at fp32)
        budgets = group.precision_slot_budgets(slots, min_per_table=floor)
        kw = {"num_slots": slots, "table_group": group, "slot_budgets": budgets}
    else:
        # uniform paper config: one global slot pool, at least the §VI-D
        # worst-case window working set (6 batches of B*T*L lookups)
        floor = group.window_floor(
            batch * cfg.lookups_per_table * group.num_tables
        )
        slots = max(slots, min(rows, floor))
        kw = {"num_slots": slots}
    if args.runtime == "scratchpipe":
        kw.update(past_window=cfg.past_window, future_window=cfg.future_window)
    if args.runtime in ("scratchpipe", "strawman", "sharded"):
        kw["executor"] = args.executor
        kw["planner"] = args.planner
        kw["kernel"] = args.kernel  # runtime-side [Insert] fills
        kw["precision"] = args.precision
        if args.adaptive_pad:
            # trace-derived fill/evict pad buckets (vs the pow-2/256 default)
            pw, fw = (
                (cfg.past_window, cfg.future_window)
                if args.runtime == "scratchpipe"
                else (0, 0)
            )
            kw["pad_buckets"] = derive_pad_buckets(
                reader, slots, past_window=pw, future_window=fw,
                profile_batches=min(args.steps, 512),
            )
            print(f"adaptive pad buckets: {kw['pad_buckets']}")
    if args.runtime == "static":
        if reader is not None:
            hot = hot_ids_from_trace(
                reader,
                cfg.cache_fraction,
                profile_batches=max(1, args.steps // 5),
            )
        elif args.scenario:
            # offline profiling pass over the workload's own prefix
            hot = profile_hot_ids(
                itertools.islice(batches(args.steps), max(1, args.steps // 5)),
                group,
                cfg.cache_fraction,
            )
        else:
            hot = hot_ids_for_group(
                group, cfg.cache_fraction, locality=args.locality
            )
        kw = {"hot_ids": hot, "precision": args.precision}
    elif args.runtime == "nocache":
        if args.precision != "fp32":
            raise SystemExit(
                "--precision applies to the device-resident caches; "
                "the nocache baseline holds no rows to quantize"
            )
        kw = {}
    def build(supervised: bool = False):
        """One full runtime stack — host table, trainer, cache runtime —
        rebuilt from scratch per (re)start: restart-from-checkpoint models
        a clean process image, so nothing survives a restart but the
        checkpoint and the deterministic stream position."""
        host = HostEmbeddingTable(rows, cfg.embed_dim, seed=args.seed)
        trainer = DLRMTrainer(
            cfg, jax.random.key(args.seed), lr=args.lr, kernel=args.kernel
        )
        kw2 = dict(kw)
        if args.runtime in ("scratchpipe", "strawman") and args.fused:
            kw2["fused_train_fn"] = trainer.fused_train_fn
        if supervised and args.runtime in ("scratchpipe", "strawman"):
            from repro.runtime import SupervisePolicy

            kw2["supervise"] = SupervisePolicy()
        pipe = make_runtime(args.runtime, host, trainer.train_fn, **kw2)
        return host, trainer, pipe

    if args.chaos:
        args.supervise = True
    if args.supervise:
        pipe, trainer, stats, report, dt = _train_dlrm_supervised(
            args, build, batches, reader
        )
    else:
        host, trainer, pipe = build()
        src = batches(args.steps)
        if args.record_trace:
            prov = {
                "generator": args.scenario or "synthetic",
                "locality": args.locality,
                "seed": args.seed,
            }
            src = TraceRecorder(
                args.record_trace, group, provenance=prov
            ).tee(src)
        # a replay stream already is a look-ahead source
        stream = src if hasattr(src, "peek_ids") else LookaheadStream(src)
        t0 = time.time()
        stats = pipe.run(stream, lookahead_fn=stream.peek_ids)
        dt = time.time() - t0
    losses = [float(s.aux["loss"]) for s in stats if s.aux]
    hit = float(np.mean([s.hit_rate for s in stats[6:]])) if len(stats) > 6 else 0
    source = (
        f"trace:{args.trace}"
        if args.trace
        else f"scenario:{args.scenario}"
        if args.scenario
        else "synthetic"
    )
    print(
        f"runtime={args.runtime} source={source} kernel={args.kernel} "
        f"precision={args.precision} "
        f"tables={group.num_tables} rows={list(group.rows)}"
    )
    if args.record_trace:
        print(f"recorded trace -> {args.record_trace}")
    print(
        f"done: steps={len(stats)} loss {losses[0]:.4f}->{losses[-1]:.4f} "
        f"plan_hit={hit:.3f} {dt / max(len(stats), 1) * 1e3:.1f}ms/step"
    )
    if args.supervise:
        # settle every cached row so the digest covers the full model state
        pipe.flush_to_host()
        print(f"state_digest={_state_digest(pipe, trainer, stats)}")
    tr = pipe.traffic()
    print(
        f"traffic: host {tr['host'].total / 1e6:.1f}MB "
        f"pcie {tr['pcie'].total / 1e6:.1f}MB hbm {tr['hbm'].total / 1e6:.1f}MB"
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument(
        "--batch",
        type=int,
        default=None,
        help="mini-batch size (default: the config's; 8 for LM smoke runs)",
    )
    ap.add_argument(
        "--rows",
        type=int,
        default=None,
        help="rows per table of the uniform DLRM config (default: the "
        "config's; the paper's 10M x 8 tables is a 40 GB host tier)",
    )
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--locality", default="medium")
    ap.add_argument(
        "--executor",
        choices=("sync", "overlapped"),
        default="sync",
        help="pipeline executor: 'overlapped' moves host gathers/write-backs "
        "and the victim d2h off the critical path (bit-identical to sync)",
    )
    ap.add_argument(
        "--fused",
        action="store_true",
        help="fuse [Insert]-fill into the [Train] dispatch (one jitted call "
        "per cycle; bit-identical to the split path)",
    )
    ap.add_argument(
        "--planner",
        choices=("host", "device"),
        default="host",
        help="[Plan] placement: 'device' keeps PlanState on-accelerator and "
        "ships raw ids instead of pre-translated slots (bit-identical)",
    )
    ap.add_argument(
        "--kernel",
        choices=("xla", "pallas"),
        default="xla",
        help="embedding-primitive implementation: 'pallas' runs the fused "
        "fill+gather+reduce forward and coalesce+scatter backward cycle "
        "kernels (native on TPU, interpreted on the CPU backend)",
    )
    ap.add_argument(
        "--precision",
        choices=("fp32", "fp16", "int8"),
        default="fp32",
        help="scratchpad replica precision: fp32 host masters stay exact; "
        "fp16/int8 rows hold 2x/4x resident rows at the same byte budget "
        "(int8: per-row scale, in-kernel dequant; see core/quantize.py)",
    )
    ap.add_argument(
        "--rounding",
        choices=("nearest", "stochastic"),
        default="stochastic",
        help="re-quantization rounding for in-cache updates (reduced "
        "precision only); 'stochastic' keeps repeated small updates unbiased",
    )
    ap.add_argument(
        "--adaptive-pad",
        action="store_true",
        help="derive the fill/evict pad-bucket set from the --trace's "
        "miss-count distribution instead of the pow-2/256 default",
    )
    ap.add_argument(
        "--runtime",
        default="scratchpipe",
        choices=("scratchpipe", "strawman", "nocache", "static"),
        help="embedding-cache runtime (EmbeddingCacheRuntime registry)",
    )
    ap.add_argument(
        "--tables",
        type=int,
        default=0,
        help="N>0: heterogeneous N-table DLRM scenario (TableGroup); "
        "0: the paper's uniform 8-table config",
    )
    ap.add_argument(
        "--trace",
        default=None,
        help="replay a recorded workload trace directory "
        "(repro.traces format; overrides the synthetic generator)",
    )
    ap.add_argument(
        "--scenario",
        default=None,
        help="non-stationary workload generator by name "
        "(drift, flash_crowd, diurnal, cold_start)",
    )
    ap.add_argument(
        "--record-trace",
        default=None,
        help="snapshot the training workload into this trace directory "
        "while training (repro.traces.TraceRecorder.tee)",
    )
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument(
        "--supervise",
        action="store_true",
        help="run DLRM training under EmbeddingTrainSupervisor: periodic "
        "crash-consistent checkpoints (any cycle, mid-window), "
        "restore+fast-forward on faults, watchdogged overlapped executor; "
        "prints a state_digest= line for bit-parity diffs",
    )
    ap.add_argument(
        "--chaos",
        default=None,
        help="fault-injection spec armed on the first runtime incarnation "
        "(implies --supervise), e.g. "
        "'kill-gather@3;stall-d2h@12:0.2;corrupt-row@13:5;nan-loss@9' "
        "(see repro.chaos)",
    )
    ap.add_argument(
        "--chaos-seed",
        type=int,
        default=0,
        help="RNG seed for chaos victim selection (corrupt-row targets)",
    )
    ap.add_argument(
        "--verify-every",
        type=int,
        default=0,
        help="audit host-table row checksums every N cycles (0 = off; "
        "corruption triggers checkpoint restore)",
    )
    ap.add_argument(
        "--metrics-out",
        default=None,
        help="write an obs_metrics/v1 JSONL snapshot here at exit "
        "(opt-in telemetry; see repro.obs)",
    )
    ap.add_argument(
        "--trace-out",
        default=None,
        help="write a Chrome trace-event JSON here at exit (load in "
        "Perfetto / chrome://tracing; spans cover all pipeline threads)",
    )
    ap.add_argument(
        "--jax-annotations",
        action="store_true",
        help="additionally wrap spans in jax.profiler.TraceAnnotation "
        "(for correlating stage names with a jax-profiler capture)",
    )
    args = ap.parse_args()
    if args.tables < 0:
        ap.error("--tables must be >= 0 (0 = uniform paper config)")
    if args.trace and args.scenario:
        ap.error("--trace and --scenario are mutually exclusive")
    if args.adaptive_pad and not args.trace:
        ap.error("--adaptive-pad derives buckets from a recorded trace; "
                 "pass --trace")
    if (args.supervise or args.chaos) and args.record_trace:
        ap.error("--record-trace cannot ride a supervised run: a restart "
                 "would re-record already-captured batches")
    if (args.supervise or args.chaos) and args.runtime not in (
        "scratchpipe", "strawman"
    ):
        ap.error("--supervise/--chaos cover the scratchpipe-family runtimes")
    if args.rows and (args.tables or args.trace):
        ap.error("--rows sizes the uniform config; --tables/--trace set "
                 "their own table heights")
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    tracer, metrics = obs_setup(
        args.trace_out, args.metrics_out, jax_annotations=args.jax_annotations
    )
    try:
        if args.arch == "dlrm-scratchpipe":
            train_dlrm(args)
        else:
            train_lm(args)
    finally:
        obs_export(
            args.trace_out,
            args.metrics_out,
            tracer,
            metrics,
            provenance={
                "mode": "train",
                "arch": args.arch,
                "runtime": args.runtime,
                "executor": args.executor,
                "planner": args.planner,
                "kernel": args.kernel,
                "precision": args.precision,
                "steps": args.steps,
                "smoke": bool(args.smoke),
            },
        )


if __name__ == "__main__":
    main()
