import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ must precede the jax import: the roofline lowers on the production mesh.

"""Three-term roofline per (arch x shape) on the single-pod mesh, derived
from compiled artifacts.

Methodology (documented in EXPERIMENTS.md §Roofline): XLA's cost_analysis
counts while-loop (lax.scan) bodies ONCE, so per-step FLOPs/bytes/collective
bytes are measured on small UNROLLED calibration variants and extrapolated:

  * layer count: lower L=1 and L=2 (unrolled) -> per_layer = c2 - c1,
    outside = c1 - per_layer, total = outside + L_full * per_layer.
    (hybrid archs use 3 variants: groups / in-group mamba layers / tail.)
  * sequence (prefill_32k only): every per-layer cost is an exact polynomial
    a + b*S + c*S^2 for fixed depth (attention quadratic, everything else
    linear), so three aligned S points {2048,4096,8192} determine it and
    S=32768 is evaluated exactly.

Terms (per chip, TPU v5e): compute = FLOPs / 197e12; memory = bytes / 819e9;
collective = collective operand bytes / 50e9.

Run:  python -m benchmarks.roofline [--cell arch shape] [--force]
Results cached under benchmarks/results/roofline/.
"""
import argparse
import dataclasses
import gc
import json
import sys
import time

import jax

from repro.configs import ASSIGNED_ARCHS, SHAPES_BY_NAME, dryrun_cells, get_entry
from repro.launch import dryrun as DR
from repro.launch.hlo_stats import collective_stats
from repro.launch.mesh import make_production_mesh

RESULTS = os.path.join(os.path.dirname(__file__), "results", "roofline")
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
CHIPS = 256

CAL_S = (2048, 4096, 8192)  # aligned to attn_block_kv/xent chunk/ssd chunk


def _variant_cfg(cfg, **kw):
    return dataclasses.replace(
        cfg, scan_layers=False, unroll_scans=True, remat=False, **kw
    )


def _measure(cfg, shape, mesh) -> dict:
    """Lower+compile one calibration variant, return flops/bytes/coll_bytes
    (per partition)."""
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            lowered = DR._lower_train(cfg, mesh, shape)
        elif shape.kind == "prefill":
            lowered = DR._lower_prefill(cfg, mesh, shape)
        else:
            lowered = DR._lower_decode(cfg, mesh, shape)
        compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    coll = collective_stats(compiled.as_text())
    out = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": float(coll["total"]["bytes_in"]),
        "coll_counts": {k: v["count"] for k, v in coll.items() if k != "total"},
    }
    del compiled, lowered
    gc.collect()
    return out


def _depth_variants(cfg, n):
    """Config with effective depth n for each family."""
    if cfg.family == "hybrid":
        raise ValueError("use _hybrid_variants")
    return _variant_cfg(cfg, num_layers=n)


def _combine(c1, c2, L):
    out = {}
    for k in ("flops", "bytes", "coll_bytes"):
        per = c2[k] - c1[k]
        outside = c1[k] - per
        out[k] = outside + L * per
        out[k + "_per_layer"] = per
        out[k + "_outside"] = outside
    return out


def _poly_eval(vals, xs, x):
    """Exact quadratic through 3 points (Lagrange)."""
    (x0, x1, x2), (y0, y1, y2) = xs, vals
    l0 = (x - x1) * (x - x2) / ((x0 - x1) * (x0 - x2))
    l1 = (x - x0) * (x - x2) / ((x1 - x0) * (x1 - x2))
    l2 = (x - x0) * (x - x1) / ((x2 - x0) * (x2 - x1))
    return y0 * l0 + y1 * l1 + y2 * l2


def _coerce(v: str):
    if v in ("True", "true"):
        return True
    if v in ("False", "false"):
        return False
    if v in ("None", "none"):
        return None
    try:
        return int(v)
    except ValueError:
        pass
    try:
        return float(v)
    except ValueError:
        return v


def calibrate_cell(arch: str, shape_name: str, mesh, overrides=None) -> dict:
    entry = get_entry(arch)
    cfg = entry.config
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES_BY_NAME[shape_name]
    t0 = time.time()

    def totals_at(shape_s) -> dict:
        if cfg.family == "hybrid":
            a = _measure(
                _variant_cfg(cfg, hybrid_groups=1, hybrid_layers_per_group=1,
                             hybrid_tail_layers=0, num_layers=1),
                shape_s, mesh)
            b = _measure(
                _variant_cfg(cfg, hybrid_groups=2, hybrid_layers_per_group=1,
                             hybrid_tail_layers=0, num_layers=2),
                shape_s, mesh)
            c = _measure(
                _variant_cfg(cfg, hybrid_groups=1, hybrid_layers_per_group=2,
                             hybrid_tail_layers=0, num_layers=2),
                shape_s, mesh)
            G, m, tail = (
                cfg.hybrid_groups, cfg.hybrid_layers_per_group, cfg.hybrid_tail_layers
            )
            out = {}
            for k in ("flops", "bytes", "coll_bytes"):
                pg = b[k] - a[k]  # one group (1 mamba + shared block)
                pm = c[k] - a[k]  # one extra mamba layer
                outside = a[k] - pg
                out[k] = outside + G * pg + (G * (m - 1) + tail) * pm
            return out
        c1 = _measure(_depth_variants(cfg, 1), shape_s, mesh)
        c2 = _measure(_depth_variants(cfg, 2), shape_s, mesh)
        return _combine(c1, c2, cfg.num_layers)

    if shape.kind == "prefill" and shape.seq_len > max(CAL_S):
        pts = []
        for s in CAL_S:
            sh = dataclasses.replace(shape, seq_len=s)
            pts.append(totals_at(sh))
        tot = {
            k: float(
                _poly_eval([p[k] for p in pts], CAL_S, shape.seq_len)
            )
            for k in ("flops", "bytes", "coll_bytes")
        }
    else:
        tot = totals_at(shape)
    tot["calibration_s"] = round(time.time() - t0, 1)
    return tot


# ---------------------------------------------------------------------------
# MODEL_FLOPS (spec formula: 6*N*D dense / 6*N_active*D MoE; fwd-only = 2*N*D)
# ---------------------------------------------------------------------------


def model_flops(arch: str, shape) -> float:
    cfg = get_entry(arch).config
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def bottleneck_advice(dom: str, arch: str, shape) -> str:
    if dom == "compute":
        return ("compute-bound: cut redundant FLOPs (remat policy, head "
                "padding, causal block skipping) or grow per-chip batch")
    if dom == "memory":
        return ("HBM-bound: fuse gather/reduce (Pallas), shrink activation "
                "dtypes, raise arithmetic intensity with larger tiles")
    return ("collective-bound: overlap collectives with compute, hierarchical "
            "reduce (in-pod RS + cross-pod psum), or reshard to cut "
            "all-gather volume")


def build_row(arch: str, shape_name: str, tot: dict) -> dict:
    shape = SHAPES_BY_NAME[shape_name]
    comp_s = tot["flops"] / PEAK_FLOPS
    mem_s = tot["bytes"] / HBM_BW
    coll_s = tot["coll_bytes"] / ICI_BW
    dom = max(
        (("compute", comp_s), ("memory", mem_s), ("collective", coll_s)),
        key=lambda kv: kv[1],
    )[0]
    mf = model_flops(arch, shape) / CHIPS
    bound = max(comp_s, mem_s, coll_s)
    return {
        "arch": arch,
        "shape": shape_name,
        "compute_s": comp_s,
        "memory_s": mem_s,
        "collective_s": coll_s,
        "dominant": dom,
        "model_flops_per_chip": mf,
        "hlo_flops_per_chip": tot["flops"],
        "useful_flops_ratio": mf / tot["flops"] if tot["flops"] else 0.0,
        "roofline_fraction": (mf / PEAK_FLOPS) / bound if bound else 0.0,
        "advice": bottleneck_advice(dom, arch, shape),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", nargs=2, metavar=("ARCH", "SHAPE"))
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")  # variant tag for perf iterations
    ap.add_argument(
        "--override", nargs="*", default=[], metavar="KEY=VALUE",
        help="ModelConfig overrides for §Perf variants",
    )
    args = ap.parse_args()
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    overrides = {}
    for kv in args.override:
        k, _, v = kv.partition("=")
        overrides[k] = _coerce(v)
    os.makedirs(RESULTS, exist_ok=True)
    mesh = make_production_mesh(multi_pod=False)
    cells = (
        [{"arch": args.cell[0], "shape": args.cell[1], "skip": None}]
        if args.cell
        else [c for c in dryrun_cells() if not c["skip"]]
    )
    for c in cells:
        tag = f"{c['arch']}__{c['shape']}" + (f"__{args.tag}" if args.tag else "")
        path = os.path.join(RESULTS, tag.replace("/", "_") + ".json")
        if os.path.exists(path) and not args.force:
            print(f"[cached] {tag}")
            continue
        print(f"[roofline] {tag} ...", flush=True)
        try:
            tot = calibrate_cell(c["arch"], c["shape"], mesh, overrides)
            row = build_row(c["arch"], c["shape"], tot)
            row["raw"] = tot
            row["ok"] = True
            if args.tag:
                row["tag"] = args.tag
                row["overrides"] = overrides
        except Exception as e:  # noqa: BLE001
            import traceback

            row = {
                "arch": c["arch"], "shape": c["shape"], "ok": False,
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-3000:],
            }
            print("  FAILED:", row["error"], flush=True)
        with open(path, "w") as f:
            json.dump(row, f, indent=1)
        if row.get("ok"):
            print(
                f"  {row['dominant']:10s} comp={row['compute_s']*1e3:8.2f}ms "
                f"mem={row['memory_s']*1e3:8.2f}ms coll={row['collective_s']*1e3:8.2f}ms "
                f"roofline={row['roofline_fraction']:.3f}",
                flush=True,
            )


if __name__ == "__main__":
    main()
