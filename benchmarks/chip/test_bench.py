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


def _tiny_checkout(tmp_path, cfg):
    """A checkout whose one cell, ``tiny``, runs ``cfg`` on the CPU."""
    import jax

    for d in ("configs", "traffic"):
        (tmp_path / d).mkdir()
    (tmp_path / "src").symlink_to(ROOT / "src")
    shutil.copytree(HERE / "metrics", tmp_path / "metrics")
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


@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout with one tiny cell of the l20 configuration, for the CPU."""
    return _tiny_checkout(tmp_path, dict(_l20(), **TINY))


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


@pytest.mark.parametrize("tables", ["equal", "unequal"])
def test_control_is_not_correct(tables):
    import control

    cfg = (dict(_l20(), **TINY) if tables == "equal"
           else dict(_unequal_cfg(), num_tables=4))
    nums, _ = control.readings(cfg, TINY_MIX, seed=7)
    for variant, n in nums.items():
        ok, _ = check.verdict(n, {k: v for k, v in cfg["limits"].items() if k in n})
        assert not ok, (variant, n)


def test_no_tpu_no_result(capsys):
    rc = run.main(["--workload", "paper-l20-medium", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""


# ---------------------------------------------------------------------- #
# tables of unequal rows and pooling
# ---------------------------------------------------------------------- #
UNEQUAL_ROWS = [3, 64, 5_000, 200_000]


def test_generator_per_table_rows_matches_program():
    """With ``table_rows`` and one pooling, each table draws from its own
    Zipf, table by table, as the program's ``dlrm_batches_group`` does."""
    from repro.core.table_group import TableGroup, TableSpec
    from repro.data.synthetic import dlrm_batches_group

    cfg = {"table_rows": UNEQUAL_ROWS, "num_tables": 4, "lookups_per_table": 5,
           "batch_size": 16, "num_dense_features": 13}
    kw = traffic_gen.stream_kwargs(cfg, {"locality": "medium"}, 4321)
    assert kw["table_rows"] == UNEQUAL_ROWS and kw["pooling"] == [5] * 4
    group = TableGroup([TableSpec(f"t{i}", r, 8)
                        for i, r in enumerate(UNEQUAL_ROWS)])
    theirs = dlrm_batches_group(group, 4, batch_size=16, lookups_per_table=5,
                                locality="medium", seed=4321)
    for (ids_a, pa), (ids_b, pb) in zip(traffic_gen.dlrm_batches(**kw), theirs):
        assert ids_a.shape == (16, 4, 5)
        np.testing.assert_array_equal(ids_a, ids_b)
        np.testing.assert_array_equal(pa["dense"], pb["dense"])
        np.testing.assert_array_equal(pa["label"], pb["label"])


def test_generator_unequal_pooling():
    pool = [1, 3, 1, 7]
    cfg = {"table_rows": UNEQUAL_ROWS, "pooling": pool, "num_tables": 4,
           "batch_size": 64, "num_dense_features": 13}
    gen = traffic_gen.dlrm_batches(
        **traffic_gen.stream_kwargs(cfg, {"locality": "high"}, 2**33 + 1))
    offsets = traffic_gen.row_offsets(cfg)
    cols = np.repeat(np.arange(4), pool)  # the table of each column
    for _ in range(3):
        ids, pl = next(gen)
        assert ids.shape == (64, sum(pool)) and ids.dtype == np.int64
        assert pl["dense"].shape == (64, 13) and pl["label"].shape == (64,)
        assert (ids >= offsets[cols]).all() and (ids < offsets[cols + 1]).all()
        np.testing.assert_array_equal(traffic_gen.table_of(offsets, ids),
                                      np.broadcast_to(cols, ids.shape))
    assert np.unique(ids[:, 0]).size <= 3  # the 3-row table


def test_generator_without_new_keys_is_unchanged():
    """The l20 stream at a fixed seed: a digest of its first three batches,
    recorded before per-table rows and pooling were added."""
    import hashlib

    mix = json.loads((HERE / "traffic" / "l20-medium.json").read_text())
    kw = traffic_gen.stream_kwargs(_l20(), mix, 2**33 + 7)
    assert "table_rows" not in kw and "pooling" not in kw
    gen = traffic_gen.dlrm_batches(**kw)
    h = hashlib.sha256()
    for _ in range(3):
        ids, pl = next(gen)
        assert ids.shape == (2048, 8, 20)
        for a in (ids, pl["dense"], pl["label"]):
            h.update(a.tobytes())
    assert h.hexdigest() == (
        "c6dd3976c9a5ef7054f5782f5ed28c03aced327f1ee30e97a4d7c0b9609830ef")
    offsets = traffic_gen.row_offsets(_l20())
    np.testing.assert_array_equal(traffic_gen.table_of(offsets, ids),
                                  ids // 4_000_000)


def _dlrm_states():
    """(program, reference) states of a small DLRM tree, 3 tables x 50
    rows, for ``check.numbers`` at a warm-up of 8 steps."""
    rng = np.random.default_rng(5)

    def mlp(dims):
        return [{"w": rng.standard_normal((a, b)).astype(np.float32),
                 "b": rng.standard_normal(b).astype(np.float32)}
                for a, b in zip(dims[:-1], dims[1:])]

    def jitter(tree, k):
        return {p: [{n: (lyr[n] + k * rng.standard_normal(lyr[n].shape))
                     .astype(np.float32) for n in ("w", "b")}
                    for lyr in tree[p]] for p in tree}

    base = {"bottom": mlp([5, 8, 4]), "top": mlp([10, 6, 1])}
    ids_of = {"first": np.arange(0, 150, 3), "step1": np.arange(0, 150, 5),
              "steady1": np.arange(1, 150, 4), "steady3": np.arange(2, 150, 7),
              "returning": np.arange(0, 150, 11),
              "writeback": np.arange(3, 150, 13)}
    states = (0, 1, 3, 8, 9, 11)

    def side(k):
        return {"loss": {i: 0.7 + 0.01 * i + k * rng.standard_normal()
                         for i in range(1, 12)},
                "mlps": {n: jitter(base, 0.01 * (n + 1) if n else 0.0)
                         for n in states},
                "rows": {}}

    p, r = side(1e-3), side(0.0)
    for n in states:
        r["mlps"][n] = jitter(p["mlps"][n], 1e-3)
    for n, names in [(0, ids_of), (1, ["step1"]), (3, ["first"]),
                     (8, ["steady1", "returning"]), (9, ["steady1"]),
                     (11, ["steady3"])]:
        for nm in names:
            x = rng.standard_normal((ids_of[nm].size, 4)).astype(np.float32)
            p["rows"][(n, nm)] = x
            r["rows"][(n, nm)] = (x + 1e-3 * rng.standard_normal(x.shape)
                                  ).astype(np.float32) if n else x
    for s in (p, r):
        s["rows"][("end", "writeback")] = rng.standard_normal(
            (ids_of["writeback"].size, 4)).astype(np.float32)
    return p, r, {k: v // 50 for k, v in ids_of.items()}


def test_check_numbers_on_dlrm_tree_unchanged():
    """``check.numbers`` on a DLRM tree reads what it read before the dense
    leaves were taken from any tree (values recorded then)."""
    p, r, tbl = _dlrm_states()
    got = check.numbers({"num_tables": 3}, 0.01, check.points(8), p, r, tbl)
    assert got == {
        "loss_gap": 0.0012521562479898773,
        "grad_gap": 0.01975329382715514,
        "grad_where": "step1@1 bottom1.w",
        "grad_at": {"step1@1": 0.01975329382715514,
                    "steady1@9": 0.0028270774146114293},
        "change_gap": 0.009054533560260942,
        "change_where": "first@3 top0.b",
        "change_at": {"first@3": 0.009054533560260942,
                      "returning@8": 0.00023723899092500237,
                      "steady1@9": 0.004600195482153308,
                      "steady3@11": 0.004027161804920857},
        "writeback_gap": 0.05993570256716395,
        "writeback_where": "table1",
    }
    names = [n for n, _ in check.leaves(p["mlps"][1], p["rows"][(1, "step1")],
                                        tbl["step1"], 3)]
    assert names == ["bottom0.b", "bottom0.w", "bottom1.b", "bottom1.w",
                     "top0.b", "top0.w", "top1.b", "top1.w",
                     "table0", "table1", "table2"]


def test_table_with_no_rows_has_no_leaf():
    rows = np.ones((3, 2), np.float32)
    got = check.leaves({}, rows, np.array([0, 2, 2]), 4)
    assert [n for n, _ in got] == ["table0", "table2"]
    gap, where, n = check.worst_leaf_gap([], [])
    assert np.isnan(gap) and n == 0
    nums = {"writeback_gap": gap}
    assert not check.verdict(nums, {"writeback_gap": 0.05})[0]


def test_check_compares_every_dense_leaf():
    """A part of the tree beyond the MLPs (a cross network) is compared: a
    step that leaves it unchanged fails."""
    p, r, tbl = _dlrm_states()
    rng = np.random.default_rng(6)
    for n in p["mlps"]:
        u = rng.standard_normal((12, 4)).astype(np.float32) * (1 + 0.01 * n)
        p["mlps"][n]["cross"] = [{"u": u, "v": u.T.copy()}]
        r["mlps"][n]["cross"] = [{"u": u * np.float32(1 + 1e-4),
                                  "v": u.T * np.float32(1 + 1e-4)}]
    limits = {k: _l20()["limits"][k] for k in ("grad_gap", "change_gap")}
    pts = check.points(8)
    nums = check.numbers({"num_tables": 3}, 0.01, pts, p, r, tbl)
    assert check.verdict(nums, limits)[0], nums
    p["mlps"][9]["cross"] = p["mlps"][8]["cross"]  # step 9 left it unchanged
    nums = check.numbers({"num_tables": 3}, 0.01, pts, p, r, tbl)
    assert nums["grad_where"].startswith("steady1@9 cross0."), nums
    assert nums["grad_gap"] == pytest.approx(1.0)
    assert not check.verdict(nums, limits)[0]


def _unequal_cfg():
    cfg = dict(_l20(), **TINY, table_rows=UNEQUAL_ROWS)
    del cfg["num_tables"], cfg["rows_per_table"]  # load_cell fills num_tables
    return cfg


@pytest.mark.parametrize("fault", [None, "half_batch"])
def test_unequal_tables_cell(fault, tmp_path, capsys, monkeypatch):
    """A cell of unequal tables runs end to end on the CPU through the
    unchanged ``run.py``: correct when sound, not with half of each batch
    left out."""
    from repro.core import dlrm_runtime

    root = _tiny_checkout(tmp_path, _unequal_cfg())
    _w, cfg, _mix, _e2e, _pl = run.load_cell("tiny", root, root)
    assert cfg["num_tables"] == 4
    if fault == "half_batch":
        monkeypatch.setattr(dlrm_runtime, "dlrm_train_step", _half_batch_step())
    res = _run_tiny(root, capsys, seed=2**32 + 99)
    assert res["correct"] is (fault is None), res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_table_count_must_agree(tmp_path):
    cfg = dict(_unequal_cfg(), num_tables=5)
    root = _tiny_checkout(tmp_path, cfg)
    with pytest.raises(SystemExit, match="table_rows lists 4 tables"):
        run.load_cell("tiny", root, root)


def test_unequal_pooling_is_refused():
    import driver_dlrm

    cfg = dict(_unequal_cfg(), name="tiny", num_tables=4, pooling=[1, 4, 4, 2])
    data = np.zeros((sum(UNEQUAL_ROWS), 16), np.float32)
    with pytest.raises(NotImplementedError, match="Reach 2"):
        driver_dlrm.build(cfg, None, data)


def test_lookups_count_per_table_pooling():
    cfg = dict(_l20(), pooling=[20] * 8)
    assert ref.train_step_cost(cfg, 1000, 200) == ref.train_step_cost(
        _l20(), 1000, 200)
    flops, _ = ref.train_step_cost(dict(_l20(), pooling=[1, 2, 3, 4, 5, 6, 7, 8]),
                                   0, 0)
    assert flops == ref.model_flops_per_example(_l20()) * 2048 + 2 * 2048 * 36 * 128


def test_pad_sizes_cover_every_count():
    import driver_dlrm
    from repro.core.plan import pad_len

    class Pipe:
        pad_buckets = None

    assert driver_dlrm.pad_sizes(Pipe, 16_384) == [256 << k for k in range(7)]
    sizes = driver_dlrm.pad_sizes(Pipe, 2048 * 8 * 20)
    assert sizes[-1] == 524_288
    assert {pad_len(n) for n in range(1, 2048 * 8 * 20 + 1, 97)} <= set(sizes)


def test_no_read_or_fill_compiles_after_build():
    """Every victim read and fill a run makes has the shape that ``build``
    warmed up, whichever seed draws its ids."""
    import driver_dlrm
    import jax
    from repro.core import scratchpad as sp

    cfg = dict(_l20(), **TINY, name="tiny")
    rows = cfg["num_tables"] * cfg["rows_per_table"]
    data = np.zeros((rows, cfg["embed_dim"]), np.float32)
    for seed in (3, 2**33 + 1):
        _host, _trainer, pipe = driver_dlrm.build(cfg, jax.random.key(0), data)
        n_read, n_fill = sp.read._cache_size(), sp.fill._cache_size()
        gen = traffic_gen.dlrm_batches(**traffic_gen.stream_kwargs(
            cfg, TINY_MIX, seed))
        driver_dlrm.run(pipe, (next(gen) for _ in range(40)))
        assert max(s.n_evict for s in pipe.stats) > 0
        assert (sp.read._cache_size(), sp.fill._cache_size()) == (n_read, n_fill)
