"""Serving launcher: LM prefill+decode, or the embedding lookup tier.

LM archs (batched prefill + greedy decode against the KV/SSM cache):

    python -m repro.launch.serve --arch <id> --smoke --batch 4 \
        --prompt-len 32 --gen 16

Embedding serving (the DLRM lookup tier through a read-only cache runtime —
the queue-as-lookahead pipeline, driven either from a recorded serving
trace or a synthetic scenario):

    python -m repro.launch.serve --embedding --design scratchpipe-serve \
        --scenario inference_mix --steps 64 --depth 2
    python -m repro.launch.serve --embedding --trace /path/to/trace --depth 2
"""
from __future__ import annotations

import argparse
import time


def _serve_lm(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, get_smoke_config
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_host_mesh, make_production_mesh
    from repro.models import api

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family == "encoder":
        raise SystemExit("encoder-only arch has no decode step")
    mesh = make_host_mesh() if args.smoke else make_production_mesh()
    shape = ShapeSpec("serve", args.prompt_len, args.batch, "prefill")

    with jax.set_mesh(mesh):
        from repro.parallel.sharding import mesh_axes

        params = api.init(cfg, jax.random.key(args.seed), mesh_axes(mesh))
        batch = api.synth_batch(cfg, shape, seed=args.seed)
        prefill = jax.jit(api.make_prefill_fn(cfg, mesh))
        decode = jax.jit(api.make_decode_fn(cfg, mesh), donate_argnums=(1,))

        t0 = time.time()
        logits, cache = prefill(params, batch)
        # grow KV caches to the full generation length (dense/hybrid archs)
        if isinstance(cache, dict) and "k" in cache and cfg.family != "ssm":
            pad = args.gen + (1 if cfg.family == "hybrid" else 0)
            if cfg.sliding_window is None:
                cache["k"] = jnp.pad(
                    cache["k"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
                )
                cache["v"] = jnp.pad(
                    cache["v"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
                )
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        print(f"prefill: {time.time() - t0:.2f}s")
        outs = [np.asarray(tok)]
        t1 = time.time()
        for i in range(args.gen - 1):
            tok, cache = decode(params, cache, tok, jnp.int32(args.prompt_len + i))
            outs.append(np.asarray(tok))
        dt = time.time() - t1
        gen = np.concatenate(outs, axis=1)
        print(f"decode: {args.gen - 1} steps in {dt:.2f}s "
              f"({dt / max(args.gen - 1, 1) * 1e3:.1f} ms/step/batch)")
        for b in range(min(args.batch, 2)):
            print(f"  sample[{b}]: {gen[b].tolist()}")


def _serve_embedding(args) -> None:
    import numpy as np

    from repro.core.host_table import HostEmbeddingTable
    from repro.core.runtime import make_runtime
    from repro.core.table_group import TableGroup
    from repro.serving import replay_serving, summarize_latencies

    if args.trace:
        from repro.traces.format import TraceReader

        reader = TraceReader(args.trace)
        group = reader.group
        steps = reader.num_batches if args.steps is None else min(
            args.steps, reader.num_batches
        )
        batches = [reader.batch(i)[0] for i in range(steps)]
        src = f"trace {args.trace} ({steps} batches)"
    else:
        from repro.traces.scenarios import scenario_batches

        group = TableGroup.uniform(args.tables, args.rows, args.dim)
        steps = args.steps if args.steps is not None else 64
        batches = [
            gids
            for gids, _ in scenario_batches(
                args.scenario,
                group,
                steps,
                batch_size=args.batch,
                lookups_per_table=args.lookups,
                seed=args.seed,
            )
        ]
        src = f"scenario {args.scenario} ({steps} batches)"

    host = HostEmbeddingTable(group.total_rows, group.dim, seed=args.seed + 1)
    kwargs = dict(kernel=args.kernel)
    if args.design == "scratchpipe-serve":
        num_slots = max(
            int(group.total_rows * args.cache_frac),
            sum(
                min(s.rows, group.window_floor(args.batch * args.lookups,
                                               window=args.depth + 2))
                for s in group.tables
            ),
        )
        kwargs.update(num_slots=num_slots, window=args.depth,
                      table_group=group)
    elif args.design == "static-serve":
        from repro.traces.profiling import profile_hot_ids

        kwargs.update(
            hot_ids=profile_hot_ids(batches[: max(2, len(batches) // 4)],
                                    group, args.cache_frac)
        )
    backend = make_runtime(args.design, host, None, **kwargs)

    if args.warm_start:
        if args.design != "scratchpipe-serve":
            raise SystemExit(
                "--warm-start preloads the plan-ahead scratchpad; it "
                "requires --design scratchpipe-serve"
            )
        from repro.checkpoint import CheckpointManager

        ckpt = CheckpointManager(args.warm_start)
        if ckpt.latest_step() is None:
            raise SystemExit(
                f"--warm-start: no checkpoints under {args.warm_start} "
                "(train with --supervise/--ckpt-every to produce them)"
            )
        man = ckpt.manifest()
        arrays = {name: ckpt.restore_host(name) for name in man["host"]}
        n = backend.warm_start_from_arrays(arrays)
        print(
            f"warm start: {n} rows preloaded from {args.warm_start} "
            f"(training step {man['step']})"
        )

    print(f"serving {src} through {args.design} at queue depth {args.depth}")
    res = replay_serving(backend, batches, depth=args.depth)
    lat = res["latency"]
    print(
        f"served {res['served']} micro-batches: "
        f"p50={lat['p50_ms']:.2f}ms p99={lat['p99_ms']:.2f}ms "
        f"{res['lookups_per_s']:,.0f} lookups/s"
    )
    print(
        f"hit_rate={res['hit_rate']:.3f} "
        f"hit_lookup_rate={res['hit_lookup_rate']:.3f} "
        f"emergency_rate={res['emergency_rate']:.3f} "
        f"(post-warmup, warmup={res['warmup']})"
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="LM arch id (LM serving)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    emb = ap.add_argument_group("embedding serving")
    emb.add_argument(
        "--embedding", action="store_true",
        help="serve the DLRM embedding lookup tier instead of an LM arch",
    )
    emb.add_argument("--design", default="scratchpipe-serve")
    emb.add_argument("--trace", default=None, help="recorded serving trace dir")
    emb.add_argument("--scenario", default="inference_mix")
    emb.add_argument("--steps", type=int, default=None)
    emb.add_argument("--depth", type=int, default=2,
                     help="queue depth = look-ahead window")
    emb.add_argument("--tables", type=int, default=4)
    emb.add_argument("--rows", type=int, default=20_000)
    emb.add_argument("--dim", type=int, default=32)
    emb.add_argument("--lookups", type=int, default=8)
    emb.add_argument("--cache-frac", type=float, default=0.25)
    emb.add_argument("--kernel", default="xla", choices=("xla", "pallas"))
    emb.add_argument(
        "--warm-start",
        default=None,
        help="training checkpoint dir (CheckpointManager layout): preload "
        "the serving scratchpad with the trained runtime's resident set "
        "and host table, so the replica starts warm instead of cold",
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
        "Perfetto / chrome://tracing)",
    )
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache
    from repro.launch.train import obs_export, obs_setup

    setup_compile_cache()

    tracer, metrics = obs_setup(args.trace_out, args.metrics_out)
    try:
        if args.embedding:
            _serve_embedding(args)
        elif args.arch is not None:
            _serve_lm(args)
        else:
            ap.error(
                "pick a serving mode: --arch <id> (LM) or --embedding (DLRM)"
            )
    finally:
        obs_export(
            args.trace_out,
            args.metrics_out,
            tracer,
            metrics,
            provenance={
                "mode": "serve",
                "design": args.design if args.embedding else args.arch,
                "depth": args.depth,
                "kernel": args.kernel,
                "scenario": None if args.trace else args.scenario,
            },
        )


if __name__ == "__main__":
    main()
