#!/usr/bin/env python3
"""Readings of the check's numbers for the control and a planted fault.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1 2 3

The control is the plain reference put in the program's place and computed
one precision below what the configuration states: bfloat16 parameters,
rows and activations at the default matmul precision, against the float32
reference at ``highest``. The planted fault ``half`` is the reference with
half of each batch left out and the mean taken over the rest. (A step that
returns its state unchanged reads 1 on ``grad_gap``, ``change_gap`` and
``writeback_gap`` by their definition, and needs no run.)

For each seed and variant it prints one JSON line with the numbers that
``check.py`` computes, at the cell's own sizes: the same set-up batches,
the same rows and the same checked states as a run of the benchmark with
that seed. The write-back rows are those the set-up steps touch and the
next ``LATER_STEPS`` batches do not, about what a run's window leaves
untouched. The limits in the configuration are set between the program's
readings over seeds and these.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import check  # noqa: E402
import host_tier  # noqa: E402
import run  # noqa: E402
import traffic_gen  # noqa: E402

VARIANTS = ("bf16", "half")
#: batches after set-up whose rows are not write-back rows (a 45 s window
#: of ``paper-l20-medium`` runs about 120 steps)
LATER_STEPS = 100


def readings(cfg: dict, mix: dict, seed: int, variants=VARIANTS):
    """{variant: numbers} for one seed, and the reference's losses."""
    import importlib

    import jax
    import jax.numpy as jnp

    ref = importlib.import_module(cfg["reference"])
    pts = check.points(int(mix["warmup_steps"]))
    C = pts["last"]
    gen = traffic_gen.dlrm_batches(**traffic_gen.stream_kwargs(cfg, mix, seed))
    pre = [next(gen) for _ in range(C)]
    batches = [(ids, pl["dense"], pl["label"]) for ids, pl in pre]
    ids_of = check.row_ids(pts, [ids for ids, _ in pre])
    u_all = np.unique(np.concatenate([p[0].ravel() for p in pre]))
    later = np.unique(np.concatenate([next(gen)[0].ravel()
                                      for _ in range(LATER_STEPS)]))
    ids_of["writeback"] = np.setdiff1d(u_all, later, assume_unique=True)
    rows0 = host_tier.rows_of(seed, u_all, cfg["embed_dim"])
    seed32 = int(np.random.default_rng(host_tier.seed_key(seed)).integers(2**31))
    key = jax.random.key(seed32)
    pos = {nm: np.searchsorted(u_all, ids) for nm, ids in ids_of.items()}
    want = check.reads(pts)
    keep = {n: {nm: pos[nm] for nm in names} for n, names in want.items()}
    keep.setdefault(C, {})["writeback"] = pos["writeback"]
    offsets = traffic_gen.row_offsets(cfg)
    tbl = {nm: traffic_gen.table_of(offsets, ids) for nm, ids in ids_of.items()}

    def side(**kw):
        s = ref.train(cfg, key, u_all, rows0, batches, cfg["lr"], keep, **kw)
        s["loss"] = dict(enumerate(s["loss"], 1))
        s["rows"][("end", "writeback")] = s["rows"].pop((C, "writeback"))
        for nm in ids_of:
            s["rows"][(0, nm)] = rows0[pos[nm]]
        return s

    r = side()
    out = {}
    for v in variants:
        kw = ({"dtype": jnp.bfloat16, "precision": "default"} if v == "bf16"
              else {"half": True})
        out[v] = check.numbers(cfg, cfg["lr"], pts, side(**kw), r, tbl)
    return out, [r["loss"][k] for k in pts["losses"]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    _w, cfg, mix, _e2e, _pl = run.load_cell(args.workload)
    for seed in args.seeds:
        nums, losses = readings(cfg, mix, seed)
        for v, n in nums.items():
            ok, _rows = check.verdict(n, {k: l for k, l in cfg["limits"].items()
                                          if k in n})
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "variant": v, "correct": ok, "ref_loss": losses,
                              **n}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
