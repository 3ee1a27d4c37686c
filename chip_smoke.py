#!/usr/bin/env python3
"""Run the paper's DLRM training path once on one TPU chip and check it.

    python chip_smoke.py

A smoke, not a benchmark: it shows that the main path compiles, runs and
gives the right answers on the chip. Everything runs in this one process,
because a chip belongs to one process at a time.

1. device  -- require a TPU. There is no CPU fallback.
2. kernels -- every cycle kernel of ``repro.kernels.ops``, compiled for the
   chip, against ``kernels/ref.py`` run on the host's CPU backend (whose
   scatter applies duplicates in the pinned flat bag-major order), compared
   bit for bit at D = 128 and (2048*8, 20) lookups, with duplicate slots
   within and across bags and sentinel fill slots.
3. train   -- the paper's DLRM (``configs/dlrm_scratchpipe.py: config()``)
   at its published widths, with rows per table cut from 10M to 1M, through
   ``make_runtime`` + ``DLRMTrainer`` + ``pipe.run`` as
   ``launch/train.py: train_dlrm`` drives it:
   (a) nocache, (b) scratchpipe xla, (c) scratchpipe pallas,
   (d) pallas + device planner + overlapped executor + fused dispatch.
   (b) must equal (a) and (d) must equal (c) bit for bit; (c) must stay
   within 1e-5 relative of (b) at every step.

Nothing is caught: the first failure ends the run with a non-zero exit.
The last line of stdout, printed only when every phase passed, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import resource
import sys
import time
from importlib import metadata

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the kernel references run on the host's CPU backend beside the chip
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# kernel phase: slots, width, bags x lookups, fill rows
KN, KD, KNB, KL, KF = 1 << 20, 128, 2048 * 8, 20, 1 << 16
# train phase
ROWS_PER_TABLE = 1_000_000  # the paper's 10M x 8 tables is a 40 GB host tier
STEPS, WARM = 18, 4  # evictions start after ~11 steps at this size
LR = 0.05
REL_TOL = 1e-5


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def bits(x) -> np.ndarray:
    """The raw bits of an array, so equality is exact (-0.0 != 0.0)."""
    x = np.asarray(x)
    return x.view(f"u{x.dtype.itemsize}")


def same(a, b) -> bool:
    return np.array_equal(bits(a), bits(b))


class CompileLog:
    """Counts persistent-cache hits/misses and sums backend compile time,
    from JAX's own monitoring events."""

    def __init__(self):
        self.hits = self.misses = self.compiles = 0
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
            self.compiles += 1
            self.compile_s += secs

    def snapshot(self):
        return self.compiles, self.compile_s

    def __str__(self):
        return (f"compiles={self.compiles} ({self.compile_s:.1f} s) "
                f"persistent-cache hits={self.hits} misses={self.misses}")


def host_rss() -> str:
    with open("/proc/self/status") as f:
        kv = dict(line.split(":", 1) for line in f if ":" in line)
    peak_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
    return (f"rss={kv.get('VmRSS', 'n/a').strip()} "
            f"peak={peak_mb:.0f} MB")


# --------------------------------------------------------------------- #
# 1. device
# --------------------------------------------------------------------- #
def device_phase(cache_dir: str):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX's first device is {dev.platform!r}; "
                 "this smoke runs only on a TPU and has no CPU fallback")
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    print(f"device: {dev.device_kind} x{len(jax.devices())} "
          f"jax={jax.__version__} jaxlib={metadata.version('jaxlib')} "
          f"libtpu={libtpu}")
    print(f"compile cache: {cache_dir}")
    return dev


# --------------------------------------------------------------------- #
# 2. kernels
# --------------------------------------------------------------------- #
def kernel_inputs(rng):
    ids = rng.integers(0, KN, (KNB, KL), dtype=np.int32)
    ids[:, 1] = ids[:, 0]  # a duplicate inside every bag
    ids[::5, 2] = 7  # one row shared by non-adjacent bags
    ids[::3, 3] = KN - 1  # another, on a different stride
    fill_slots = rng.permutation(KN)[:KF].astype(np.int32)
    fill_slots[::10] = KN  # bucket-padding sentinels: dropped
    live = fill_slots[fill_slots < KN]
    ids[:, 4] = live[rng.integers(0, live.size, KNB)]  # read this cycle's fills
    table = (rng.standard_normal((KN, KD), dtype=np.float32)
             / np.float32(np.sqrt(KD)))
    rows = rng.standard_normal((KF, KD), dtype=np.float32)
    bag_grads = rng.standard_normal((KNB, KD), dtype=np.float32)
    return ids, fill_slots, table, rows, bag_grads


def kernel_phase():
    from repro.core import quantize as qz
    from repro.kernels import ops
    from repro.kernels import ref

    cpu = jax.devices("cpu")[0]
    ids, fill_slots, table, rows, bag_grads = kernel_inputs(
        np.random.default_rng(0))
    lr = 0.05

    def compare(name, kernel_fn, ref_fn, *args):
        t0 = time.perf_counter()
        got = jax.block_until_ready(kernel_fn(*args))
        dt = time.perf_counter() - t0
        with jax.default_device(cpu):
            want = jax.jit(ref_fn)(*args)
        got, want = jax.tree.leaves(got), jax.tree.leaves(want)
        check(len(got) == len(want), f"{name}: output count")
        for i, (g, w) in enumerate(zip(got, want)):
            g, w = np.asarray(g), np.asarray(w)
            check(g.shape == w.shape and g.dtype == w.dtype,
                  f"{name}[{i}]: {g.shape} {g.dtype} vs {w.shape} {w.dtype}")
            n_bad = int(np.sum(bits(g) != bits(w)))
            check(n_bad == 0, f"{name}[{i}]: {n_bad} elements differ from "
                  "kernels/ref.py")
        print(f"kernel {name:<28} == ref (bitwise)  first call {dt:.2f} s")

    for dt_name, dtype in (("fp32", jnp.float32), ("bf16", jnp.bfloat16)):
        st = np.asarray(jnp.asarray(table, dtype))
        fr = np.asarray(jnp.asarray(rows, dtype))
        compare(f"gather_reduce/{dt_name}", ops.gather_reduce,
                ref.gather_reduce_ref, st, ids)
        compare(f"fill/{dt_name}", ops.fill, ref.fill_ref, st, fill_slots, fr)
        compare(f"fill_gather_reduce/{dt_name}", ops.fill_gather_reduce,
                ref.fill_gather_reduce_ref, st, fill_slots, fr, ids)
        compare(f"coalesce_apply/{dt_name}",
                lambda s, i, g: ops.coalesce_apply(s, i, g, lr),
                lambda s, i, g: ref.coalesce_apply_ref(s, i, g, lr),
                st, ids, bag_grads)

    q, scale = qz.quantize_rows_np(table, "int8")
    qrows, rscale = qz.quantize_rows_np(rows, "int8")
    live = fill_slots < KN
    filled_scale = scale.copy()
    filled_scale[fill_slots[live]] = rscale[live]
    compare("gather_reduce_q/int8", ops.gather_reduce_q,
            ref.gather_reduce_q_ref, q, scale, ids)
    compare("fill/int8", ops.fill, ref.fill_ref, q, fill_slots, qrows)
    compare("fill_gather_reduce_q/int8", ops.fill_gather_reduce_q,
            ref.fill_gather_reduce_q_ref, q, filled_scale, fill_slots, qrows,
            ids)
    deltas = ref.scatter_deltas(table, bag_grads, lr)
    compare("coalesce_deltas/fp32", ops.coalesce_deltas,
            ref.coalesce_deltas_ref, np.zeros((KN, KD), np.float32), ids,
            np.asarray(deltas))

    try:  # fp16 has no kernel on the chip: it must say so, not fall back
        ops.gather_reduce_q(np.zeros((KN, KD), np.float16), None, ids)
    except NotImplementedError as e:
        print(f"kernel gather_reduce_q/fp16 refused as documented: {e}")
    else:
        raise RuntimeError("chip_smoke: fp16 pallas storage did not raise")


# --------------------------------------------------------------------- #
# 3. train
# --------------------------------------------------------------------- #
RUNS = {  # label -> (runtime, make_runtime / trainer options)
    "a": ("nocache", dict(kernel="xla")),
    "b": ("scratchpipe", dict(kernel="xla")),
    "c": ("scratchpipe", dict(kernel="pallas")),
    "d": ("scratchpipe", dict(kernel="pallas", planner="device",
                              executor="overlapped", fused=True)),
}


@dataclasses.dataclass
class RunResult:
    losses: np.ndarray
    mlps: list
    table_digest: str  # host tiers are compared by digest: 4 GB each
    evicts: int


def train_phase(dev, log: CompileLog):
    from repro.configs import get_config
    from repro.core.dlrm_runtime import DLRMTrainer
    from repro.core.host_table import HostEmbeddingTable
    from repro.core.runtime import make_runtime
    from repro.core.table_group import TableGroup
    from repro.data.lookahead import LookaheadStream
    from repro.data.synthetic import TraceConfig, dlrm_batches

    cfg = dataclasses.replace(get_config("dlrm-scratchpipe"),
                              rows_per_table=ROWS_PER_TABLE)
    group = TableGroup.from_config(cfg)
    B, T, L = cfg.batch_size, cfg.num_tables, cfg.lookups_per_table
    # the §VI-D floor: six in-flight batches of B*T*L distinct rows
    slots = group.window_floor(B * T * L)
    print(f"train: {cfg.name} tables={T} rows/table={cfg.rows_per_table} "
          f"dim={cfg.embed_dim} lookups={L} batch={B} "
          f"bottom={cfg.bottom_mlp} top={cfg.top_mlp} slots={slots} "
          f"steps={STEPS}")

    t0 = time.perf_counter()
    master = HostEmbeddingTable(group.total_rows, cfg.embed_dim, seed=0).data
    items = list(dlrm_batches(
        TraceConfig(num_tables=T, rows_per_table=cfg.rows_per_table,
                    lookups_per_table=L, batch_size=B, seed=0), STEPS))
    print(f"train: host tier {master.nbytes / 1e9:.2f} GB and {STEPS} batches "
          f"made in {time.perf_counter() - t0:.1f} s ({host_rss()})")

    def run(label) -> RunResult:
        runtime, opts = RUNS[label]
        opts = dict(opts)
        fused = opts.pop("fused", False)
        host = HostEmbeddingTable(group.total_rows, cfg.embed_dim,
                                  data=master.copy())
        trainer = DLRMTrainer(cfg, jax.random.key(0), lr=LR,
                              kernel=opts["kernel"])
        kw = {}
        if runtime == "scratchpipe":
            kw = dict(num_slots=slots, past_window=cfg.past_window,
                      future_window=cfg.future_window, precision="fp32",
                      **opts)
            if fused:
                kw["fused_train_fn"] = trainer.fused_train_fn
        pipe = make_runtime(runtime, host, trainer.train_fn, **kw)
        c0 = log.snapshot()
        t0 = time.perf_counter()
        stream = LookaheadStream(iter(items[:WARM]))
        stats = pipe.run(stream, lookahead_fn=stream.peek_ids)
        stream = LookaheadStream(iter(items[WARM:]))
        c1 = log.snapshot()
        t1 = time.perf_counter()
        stats += pipe.run(stream, lookahead_fn=stream.peek_ids)
        losses = np.array([s.aux["loss"] for s in stats if s.aux], np.float32)
        jax.block_until_ready(trainer.mlps)
        t2 = time.perf_counter()
        c2 = log.snapshot()
        pipe.flush_to_host()
        evicts = sum(s.n_evict for s in stats)
        check(losses.size == STEPS and np.all(np.isfinite(losses)),
              f"run ({label}): {losses.size} finite losses of {STEPS}")
        steady = STEPS - WARM
        print(f"run ({label}) {runtime} {json.dumps(RUNS[label][1])}: "
              f"loss {losses[0]:.6f} -> {losses[-1]:.6f}  evicts={evicts}  "
              f"hit={np.mean([s.hit_rate for s in stats[WARM:]]):.3f}")
        print(f"run ({label}) smoke timing, not a benchmark: first {WARM} "
              f"steps {t1 - t0:.1f} s with {c1[0] - c0[0]} compiles "
              f"({c1[1] - c0[1]:.1f} s); next {steady} steps "
              f"{(t2 - t1) / steady * 1e3:.1f} ms/step after "
              f"block_until_ready with {c2[0] - c1[0]} compiles "
              f"({c2[1] - c1[1]:.1f} s); peak_bytes_in_use="
              f"{dev.memory_stats()['peak_bytes_in_use'] / 1e9:.2f} GB "
              f"(process peak); host {host_rss()}")
        if runtime == "scratchpipe":
            pipe.close()  # join the overlapped executor's worker threads
            check(evicts > 0, f"run ({label}): the scratchpad never evicted")
        if opts["kernel"] == "pallas":
            check_native_step(trainer, cfg, slots, fused)
        return RunResult(losses, [np.asarray(x) for x in
                                  jax.tree.leaves(trainer.mlps)],
                         hashlib.sha256(host.data).hexdigest(), evicts)

    def identical(x: RunResult, y: RunResult) -> bool:
        return (same(x.losses, y.losses)
                and all(same(p, q) for p, q in zip(x.mlps, y.mlps))
                and x.table_digest == y.table_digest)

    a = run("a")
    b = run("b")
    check(identical(a, b), "(b) scratchpipe/xla differs from (a) nocache")
    print("parity (b) == (a): bit-identical losses, MLPs and host table")
    c = run("c")
    rel = np.abs(c.losses - b.losses) / np.abs(b.losses)
    check(bool(np.all(rel <= REL_TOL)),
          f"(c) pallas vs (b) xla: max relative loss diff {rel.max():.3e}")
    print(f"parity (c) vs (b): max relative loss diff {rel.max():.3e} "
          f"(limit {REL_TOL:g}); bit-identical={identical(b, c)}")
    d = run("d")
    check(identical(c, d), "(d) fused/device/overlapped differs from (c)")
    print("parity (d) == (c): bit-identical losses, MLPs and host table")


def check_native_step(trainer, cfg, slots, fused):
    """The compiled pallas [Train] step holds Mosaic custom calls: the
    kernels are native, not interpreted or swapped for the reference."""
    from repro.core.dlrm_runtime import dlrm_fill_train_step, dlrm_train_step

    B, T, L, D = (cfg.batch_size, cfg.num_tables, cfg.lookups_per_table,
                  cfg.embed_dim)
    sds = jax.ShapeDtypeStruct
    storage = sds((slots, D), jnp.float32)
    ids = sds((B, T, L), jnp.int32)
    dense = sds((B, cfg.num_dense_features), jnp.float32)
    label = sds((B,), jnp.float32)
    if fused:
        F = 1024
        lowered = dlrm_fill_train_step.lower(
            storage, trainer.mlps, sds((F,), jnp.int32), sds((F, D), jnp.float32),
            ids, dense, label, lr=LR, kernel="pallas")
    else:
        lowered = dlrm_train_step.lower(storage, trainer.mlps, ids, dense,
                                        label, lr=LR, kernel="pallas")
    text = lowered.compile().as_text()
    n = text.count("tpu_custom_call")
    check(n > 0, "the pallas train step holds no tpu_custom_call")
    print(f"compiled pallas {'fused ' if fused else ''}train step: "
          f"{n} tpu_custom_call sites")


def main():
    from repro.launch.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache()
    log = CompileLog()
    t0 = time.perf_counter()
    dev = device_phase(cache_dir)
    kernel_phase()
    print(f"kernels done in {time.perf_counter() - t0:.1f} s; {log}")
    train_phase(dev, log)
    print(f"all phases done in {time.perf_counter() - t0:.1f} s; {log}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
