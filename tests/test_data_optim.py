"""Data pipeline (trace locality calibration, lookahead semantics) and
optimizer math."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.data.lookahead import LookaheadStream, make_stream
from repro.data.synthetic import (
    LOCALITY_S,
    TraceConfig,
    access_counts,
    dlrm_batches,
    sample_ids,
)
from repro.optim import AdamW, RowWiseAdagrad, SGD, clip_by_global_norm, warmup_cosine


def _top2_share(locality, n=20000, draws=400000):
    rng = np.random.default_rng(0)
    ids = sample_ids(rng, n, draws, locality)
    counts = np.bincount(ids, minlength=n)
    counts = np.sort(counts)[::-1]
    return counts[: max(1, int(0.02 * n))].sum() / draws


def test_locality_calibration_matches_paper_fig3():
    """top-2% traffic shares: random ~2%, low ~8.5%, high >=70% (§III-A)."""
    shares = {loc: _top2_share(loc) for loc in LOCALITY_S}
    assert 0.015 < shares["random"] < 0.04
    assert 0.05 < shares["low"] < 0.15
    assert shares["low"] < shares["medium"] < shares["high"]
    assert shares["high"] > 0.6


def test_trace_determinism_and_offsets():
    tc = TraceConfig(num_tables=3, rows_per_table=50, lookups_per_table=4,
                     batch_size=6, locality="medium", seed=7)
    a = [ids.copy() for ids, _ in dlrm_batches(tc, 5)]
    b = [ids.copy() for ids, _ in dlrm_batches(tc, 5)]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    # global row ids land in each table's range
    for ids in a:
        for t in range(3):
            assert (ids[:, t] >= t * 50).all() and (ids[:, t] < (t + 1) * 50).all()


def test_lookahead_peek_does_not_consume():
    s = LookaheadStream(iter([(np.array([i]), i) for i in range(6)]))
    ids0, _ = next(s)
    peek = s.peek_ids(3)
    assert [int(p[0]) for p in peek] == [1, 2, 3]
    ids1, _ = next(s)
    assert int(ids1[0]) == 1  # peek did not consume
    assert s.consumed == 2


def test_make_stream_skip_replays_identically():
    def factory():
        return iter([(np.array([i]), i) for i in range(10)])

    full = [next(LookaheadStream(factory()))[1] for _ in range(1)]
    s = make_stream(factory, skip=4)
    assert next(s)[1] == 4
    assert s.consumed == 5


def test_adamw_matches_manual_math():
    opt = AdamW(b1=0.9, b2=0.99, eps=1e-8, master_fp32=True)
    p = {"w": jnp.asarray([1.0, -2.0])}
    g = {"w": jnp.asarray([0.5, 0.5])}
    st = opt.init(p)
    p1, st = opt.step(p, g, st, lr=0.1)
    m = 0.1 * 0.5
    v = 0.01 * 0.25
    step = 0.1 * (m / (1 - 0.9)) / (np.sqrt(v / (1 - 0.99)) + 1e-8)
    np.testing.assert_allclose(np.asarray(p1["w"]), [1.0 - step, -2.0 - step], rtol=1e-6)


def test_adamw_bf16_master_weights_accumulate():
    """bf16 params alone would lose small updates; the fp32 master keeps them."""
    opt = AdamW(master_fp32=True)
    p = {"w": jnp.ones((4,), jnp.bfloat16)}
    st = opt.init(p)
    g = {"w": jnp.full((4,), 1e-3, jnp.bfloat16)}
    for _ in range(10):
        p, st = opt.step(p, g, st, lr=1e-5)
    assert float(st["master"]["w"][0]) < 1.0  # master moved
    assert st["master"]["w"].dtype == jnp.float32


def test_rowwise_adagrad():
    opt = RowWiseAdagrad()
    rows = jnp.ones((3, 4))
    grads = jnp.ones((3, 4)) * 2.0
    acc = jnp.zeros((3,))
    new, acc = opt.step_rows(rows, grads, acc, lr=0.1)
    np.testing.assert_allclose(np.asarray(acc), [4.0, 4.0, 4.0])
    np.testing.assert_allclose(np.asarray(new), 1.0 - 0.1 * 2.0 / 2.0, rtol=1e-5)


def test_clip_and_schedule():
    g = {"a": jnp.asarray([3.0, 4.0])}
    clipped, norm = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(norm), 5.0, rtol=1e-6)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(clipped["a"])), 1.0, rtol=1e-5
    )
    lr0 = float(warmup_cosine(0, base_lr=1.0, warmup=10, total=100))
    lr10 = float(warmup_cosine(10, base_lr=1.0, warmup=10, total=100))
    lr100 = float(warmup_cosine(100, base_lr=1.0, warmup=10, total=100))
    assert lr0 == 0.0 and abs(lr10 - 1.0) < 1e-6 and lr100 < 0.11


def test_sgd_momentum():
    opt = SGD(momentum=0.9)
    p = {"w": jnp.asarray([1.0])}
    st = opt.init(p)
    g = {"w": jnp.asarray([1.0])}
    p, st = opt.step(p, g, st, lr=0.1)
    p, st = opt.step(p, g, st, lr=0.1)
    np.testing.assert_allclose(
        float(p["w"][0]), 1.0 - 0.1 - 0.1 * 1.9, rtol=1e-6
    )


def test_host_table_chunked_init_equals_one_draw(monkeypatch):
    """The host tier draws its init in row chunks to bound host RAM; the
    values must be those of a single (rows, dim) draw, bit for bit."""
    from repro.core import host_table

    rows, dim, seed = 1003, 128, 3
    monkeypatch.setattr(host_table, "_INIT_CHUNK_VALUES", 100 * dim)
    got = host_table.HostEmbeddingTable(rows, dim, seed=seed).data
    rng = np.random.default_rng(seed)
    want = (rng.standard_normal((rows, dim)) * (1.0 / np.sqrt(dim))).astype(
        np.float32
    )
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
