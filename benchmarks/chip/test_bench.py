"""Self-tests of the chip benchmark's harness, on the CPU at tiny sizes.

    python -m pytest benchmarks/chip

They check the yardstick, not the program: the copied traffic generator,
the operation and byte counts, the reduction of a trace recorded on a TPU
v5e, that files are found by name, and that ``correct`` comes out false for
the control and for each planted fault of the timed path.
"""
from __future__ import annotations

import functools
import json
import pathlib
import shutil
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import check  # noqa: E402
import reference_dlrm as ref  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import traffic_gen  # noqa: E402

TESTDATA = HERE / "testdata"


def _l20():
    return json.loads((HERE / "configs" / "dlrm-paper-l20.json").read_text())


# ---------------------------------------------------------------------- #
# the yardstick
# ---------------------------------------------------------------------- #
def test_generator_matches_program():
    from repro.data.synthetic import TraceConfig, dlrm_batches

    kw = dict(num_tables=3, rows_per_table=10_007, lookups_per_table=5,
              batch_size=16)
    mine = traffic_gen.dlrm_batches(
        seed=1234, s=traffic_gen.LOCALITY_S["medium"], num_dense_features=13,
        **kw)
    theirs = dlrm_batches(TraceConfig(locality="medium", seed=1234, **kw), 4)
    for (ids_a, pa), (ids_b, pb) in zip(mine, theirs):
        np.testing.assert_array_equal(ids_a, ids_b)
        np.testing.assert_array_equal(pa["dense"], pb["dense"])
        np.testing.assert_array_equal(pa["label"], pb["label"])


def test_flop_and_byte_counts():
    cfg = _l20()
    # bottom 13-512-256-128, dot interaction of 9 features x 128, top
    # (36 + 128)-1024-1024-512-256-1
    bottom = 13 * 512 + 512 * 256 + 256 * 128
    inter = 9 * 9 * 128
    top = 164 * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256 * 1
    assert (bottom, inter, top) == (170_496, 10_368, 1_872_128)
    assert ref.forward_macs_per_example(cfg) == bottom + inter + top
    assert ref.model_flops_per_example(cfg) == 6 * (bottom + inter + top)
    flops, bytes_ = ref.train_step_cost(cfg, n_unique=1000, n_fill=200)
    lookups = 2048 * 8 * 20
    params = (13 * 512 + 512 + 512 * 256 + 256 + 256 * 128 + 128
              + 164 * 1024 + 1024 + 1024 * 1024 + 1024 + 1024 * 512 + 512
              + 512 * 256 + 256 + 256 * 1 + 1)
    assert bytes_ == (lookups * 512 + 2 * 1000 * 512 + 2 * 200 * 512
                      + 8 * params + 2048 * 14 * 4)
    assert flops == 6 * (bottom + inter + top) * 2048 + 2 * lookups * 128


def test_files_found_by_name(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs" / "new-model.json").write_text('{"num_tables": 2}')
    (tmp_path / "traffic" / "new-mix.json").write_text('{"locality": "high"}')
    (tmp_path / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return ctx.steps * 2\n")
    (tmp_path / "metrics" / "silent.py").write_text(
        "def read(ctx):\n    return None\n")
    bench["configs"].append({"name": "new-model", "source": "x",
                             "file": "configs/new-model.json", "reduced": [],
                             "why": "x"})
    for cell in ("new-cell", "other-cell"):
        bench["workloads"].append({"name": cell, "config": "new-model",
                                   "traffic": "new-mix", "chips": 1, "why": "x"})
    bench["per_layer"] += [
        {"name": "new_metric", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "x", "moves": "examples_per_s",
         "workloads": ["new-cell"]},
        {"name": "silent", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "x", "moves": "examples_per_s"},
    ]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    w, cfg, mix, _e2e, per_layer = run.load_cell("new-cell", tmp_path, tmp_path)
    assert (w["name"], cfg, mix) == (
        "new-cell", {"num_tables": 2, "name": "new-model"}, {"locality": "high"})
    mine = [m for m in per_layer if m["name"] in ("new_metric", "silent")]
    got = run.read_metrics(mine, run.Ctx(steps=21), tmp_path)
    assert got == {"new_metric": {"value": 42.0, "unit": "ms"}}
    # a cell the new metric does not list does not report it
    _, _, _, _, other = run.load_cell("other-cell", tmp_path, tmp_path)
    assert "new_metric" not in [m["name"] for m in other]


def test_trace_reduction():
    """A traced window of ``paper-l1-medium`` recorded on a TPU v5e,
    reduced; ``expected.json`` holds what the reduction read when the
    trace was recorded, and the invariants below hold for any trace."""
    xs = sorted(TESTDATA.glob("*.xplane.pb"))
    assert xs, "no recorded trace in testdata/"
    red = trace_reduce.reduce_trace(str(xs[0]))
    want = json.loads((TESTDATA / "expected.json").read_text())
    assert red["chips"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    n, s = trace_reduce.module_seconds(red, r"dlrm_\w*train_step")
    assert (n, s) == (want["train_modules"], pytest.approx(want["train_s"]))
    # ops never overlap on one chip, so their sum is the busy time
    assert sum(red["ops"].values()) == pytest.approx(red["busy_s"], rel=1e-6)
    idle = red["window_s"] - red["busy_s"]
    assert sum(red["idle_by_span"].values()) == pytest.approx(idle, rel=1e-6)
    b = trace_reduce.breakdown(red)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_span_self_time():
    ev = [
        {"ph": "I", "name": "b", "ts": 10.0, "tid": 0},
        {"ph": "B", "name": "plan", "ts": 0.0, "tid": 0},
        {"ph": "B", "name": "inner", "ts": 20.0, "tid": 0},
        {"ph": "E", "ts": 30.0, "tid": 0},
        {"ph": "E", "ts": 50.0, "tid": 0},
        {"ph": "B", "name": "gather", "ts": 40.0, "tid": 1},
        {"ph": "E", "ts": 200.0, "tid": 1},
        {"ph": "I", "name": "e", "ts": 100.0, "tid": 0},
    ]
    got = run.span_self_seconds(ev, "b", "e")
    assert got == pytest.approx({"plan": 30e-6, "inner": 10e-6, "gather": 60e-6})


# ---------------------------------------------------------------------- #
# correct: the control and the planted faults fail, a sound run passes
# ---------------------------------------------------------------------- #
TINY = dict(num_tables=4, rows_per_table=20_000, embed_dim=16,
            lookups_per_table=4, bottom_mlp=[32, 16], top_mlp=[32, 16, 1],
            batch_size=32, num_slots=3072)
TINY_MIX = {"locality": "medium", "warmup_steps": 12}


@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout with one tiny cell of the l20 configuration, for the CPU."""
    import jax

    for d in ("configs", "traffic"):
        (tmp_path / d).mkdir()
    (tmp_path / "src").symlink_to(ROOT / "src")
    shutil.copytree(HERE / "metrics", tmp_path / "metrics")
    cfg = dict(_l20(), **TINY)
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (tmp_path / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    peaks = json.loads((HERE / "peaks.json").read_text())
    peaks["devices"][jax.devices()[0].device_kind] = peaks["devices"]["TPU v5 lite"]
    (tmp_path / "peaks.json").write_text(json.dumps(peaks))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [dict(bench["configs"][0], name="tiny",
                             file="configs/tiny.json")]
    bench["workloads"] = [dict(bench["workloads"][0], name="tiny",
                               config="tiny", traffic="tiny")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def _run_tiny(root, capsys, seed=11):
    rc = run.main(["--workload", "tiny", "--seed", str(seed), "--seconds", "1",
                   "--trace", "0"], root=root, here=root, require_tpu=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(tiny_root, capsys):
    res = _run_tiny(tiny_root, capsys, seed=2**31 + 12345)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(_l20()["limits"])
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"examples_per_s", "step_ms_p90", "setup_s"}


def _half_batch_step():
    import jax

    from repro.core import scratchpad as sp
    from repro.models import dlrm

    @functools.partial(jax.jit, donate_argnums=(0,),
                       static_argnames=("kernel", "lr"))
    def step(storage, mlps, slots, dense, label, lr, kernel="xla"):
        n = dense.shape[0] // 2
        slots, dense, label = slots[:n], dense[:n], label[:n]

        def loss_fn(m, bags):
            return dlrm.bce_loss(dlrm.forward_from_bags(m, dense, bags), label)

        bags = sp.gather_reduce(storage, slots, kernel=kernel)
        loss, (gm, gb) = jax.value_and_grad(loss_fn, argnums=(0, 1))(mlps, bags)
        mlps = jax.tree.map(lambda p, g: p - lr * g, mlps, gm)
        return sp.apply_grad(storage, slots, gb, lr, kernel=kernel), mlps, loss

    return step


def _unchanged_step():
    import jax

    from repro.core import scratchpad as sp
    from repro.models import dlrm

    @functools.partial(jax.jit, static_argnames=("kernel", "lr"))
    def loss_only(storage, mlps, slots, dense, label, lr, kernel="xla"):
        bags = sp.gather_reduce(storage, slots, kernel=kernel)
        return dlrm.bce_loss(dlrm.forward_from_bags(mlps, dense, bags), label)

    def step(storage, mlps, slots, dense, label, lr, kernel="xla"):
        return storage, mlps, loss_only(storage, mlps, slots, dense, label,
                                        lr=lr, kernel=kernel)

    return step


def _late_writeback(monkeypatch, lag: int = 8):
    """Write-backs land ``lag`` cycles late (all of them before the final
    flush): a row evicted and missed again within that time is fetched
    from the host tier stale, the hazard the hold windows guard."""
    from repro.core.pipeline import ScratchPipe

    pending, cycle = [], [0]
    flush = ScratchPipe.flush_to_host

    def land(self, upto):
        while pending and pending[0][0] <= upto:
            _, ids, rows = pending.pop(0)
            self.host.scatter(ids, rows)

    def insert_host(self, entry):
        cycle[0] += 1
        p = entry.plan
        if p.evict_ids.size:
            pending.append((cycle[0], p.evict_ids.copy(),
                            self._dequant(entry.evicted_host)))
        land(self, cycle[0] - lag)

    def flush_to_host(self):
        land(self, cycle[0])
        flush(self)

    monkeypatch.setattr(ScratchPipe, "_stage_insert_host", insert_host)
    monkeypatch.setattr(ScratchPipe, "flush_to_host", flush_to_host)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "writeback_lost", "stale_refetch"])
def test_planted_fault_is_not_correct(fault, tiny_root, capsys, monkeypatch):
    from repro.core import dlrm_runtime
    from repro.core.pipeline import ScratchPipe

    if fault == "state_unchanged":
        monkeypatch.setattr(dlrm_runtime, "dlrm_train_step", _unchanged_step())
    elif fault == "half_batch":
        monkeypatch.setattr(dlrm_runtime, "dlrm_train_step", _half_batch_step())
    elif fault == "writeback_lost":  # evicted rows never reach the host tier
        monkeypatch.setattr(ScratchPipe, "_stage_insert_host",
                            lambda self, entry: None)
    else:
        _late_writeback(monkeypatch)
    res = _run_tiny(tiny_root, capsys)
    assert res["correct"] is False, res["checks"]


def test_control_is_not_correct():
    import control

    cfg = dict(_l20(), **TINY)
    nums, _ = control.readings(cfg, TINY_MIX, seed=7)
    for variant, n in nums.items():
        ok, _ = check.verdict(n, {k: v for k, v in cfg["limits"].items() if k in n})
        assert not ok, (variant, n)


def test_no_tpu_no_result(capsys):
    rc = run.main(["--workload", "paper-l20-medium", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""
