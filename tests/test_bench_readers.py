"""The chip benchmark's readers of the runtime's spans and link counters
(``benchmarks/chip/metrics/``), each loaded by path through the harness's
own ``load_reader`` and given a hand-made ``Ctx``: the value it reports,
and None when the span or counter it reads is missing (as on a program
that does not record it)."""
import importlib.util
import pathlib

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


@pytest.fixture(scope="module")
def harness():
    spec = importlib.util.spec_from_file_location("chip_bench_run",
                                                  CHIP / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


STEPS = 16
SPANS = {  # self seconds over the traced window
    "input": 0.064, "plan": 0.32, "plan.unique": 0.24, "plan.evict": 0.40,
    "exchange": 0.08, "exchange.pad": 0.48, "exchange.h2d": 1.12,
    "exchange.d2h": 0.75,
}
COUNTERS = {  # windowed cache.* deltas
    "cache.fills": 3_000_000, "cache.evicts": 3_000_000,
    "cache.h2d_rows": 4_194_304, "cache.d2h_rows": 4_194_304,
    "cache.h2d_bytes": 2_147_483_648, "cache.d2h_bytes": 2_147_483_648,
}
# metric -> (value on SPANS/COUNTERS, the inputs whose absence gives None)
EXPECT = {
    "input_ms": (4.0, ["input"]),
    "plan_unique_ms": (15.0, ["plan.unique"]),
    "plan_evict_ms": (25.0, ["plan.evict"]),
    "pad_copy_ms": (30.0, ["exchange.pad"]),
    "h2d_ms": (70.0, ["exchange.h2d"]),
    "d2h_gbps": (2_147_483_648 / 0.75 / 1e9,
                 ["exchange.d2h", "cache.d2h_bytes"]),
    "pad_share_pct": (100 * (1 - 6_000_000 / 8_388_608),
                      ["cache.h2d_rows", "cache.d2h_rows"]),
}


def _ctx(harness, drop=()):
    return harness.Ctx(
        steps=STEPS,
        spans={k: v for k, v in SPANS.items() if k not in drop},
        counters={k: v for k, v in COUNTERS.items() if k not in drop})


@pytest.mark.parametrize("metric", sorted(EXPECT))
def test_reader_value(harness, metric):
    want, _ = EXPECT[metric]
    got = harness.load_reader(metric, CHIP).read(_ctx(harness))
    assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("metric", sorted(EXPECT))
def test_reader_none_without_inputs(harness, metric):
    reader = harness.load_reader(metric, CHIP)
    _, needs = EXPECT[metric]
    for name in needs:  # each input alone is needed
        assert reader.read(_ctx(harness, drop=[name])) is None, name
    # the parent program's traced run: the older spans and counters only
    old = harness.Ctx(steps=STEPS,
                      spans={"plan": 0.9, "exchange": 2.4, "exchange.d2h": 0.75},
                      counters={"cache.fills": 1, "cache.evicts": 1})
    assert reader.read(old) is None
    assert reader.read(harness.Ctx(steps=STEPS, spans={}, counters={})) is None


@pytest.mark.parametrize("metric, zero", [
    ("d2h_gbps", {"exchange.d2h": 0.0}),
    ("pad_share_pct", {"cache.h2d_rows": 0, "cache.d2h_rows": 0}),
])
def test_ratio_reader_none_on_zero_denominator(harness, metric, zero):
    """A ratio whose denominator read zero in the window has no value."""
    ctx = _ctx(harness)
    for k, v in zero.items():
        (ctx.spans if k in SPANS else ctx.counters)[k] = v
    assert harness.load_reader(metric, CHIP).read(ctx) is None


def test_readers_are_registered():
    import json

    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for metric in EXPECT:
        m = per_layer[metric]
        assert m["moves"] == "examples_per_s" and "workloads" not in m
        assert (CHIP / "metrics" / f"{metric}.py").is_file()
