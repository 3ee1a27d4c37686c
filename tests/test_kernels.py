"""Per-kernel parity suites against the ref.py jnp oracles.

The embedding-cycle kernels (gather_reduce / coalesce_apply / fill /
fill_gather_reduce) are checked for EXACT bit parity — the reference path in
``kernels/ref.py`` reproduces the kernels' operation order (ordered f32
accumulation; pre-rounded update deltas), so ``kernel="xla"`` and
``kernel="pallas"`` are interchangeable to the last ulp and every
integration test can assert bit-identity. The LM-side kernels (flash
attention, SSD) keep their original tolerance-based sweeps.
interpret=True executes the Pallas kernel bodies on CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # fall back to deterministic fixed examples
    from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def assert_bit_identical(out, want, msg=""):
    out, want = np.asarray(out), np.asarray(want)
    assert out.dtype == want.dtype, (msg, out.dtype, want.dtype)
    assert out.shape == want.shape, (msg, out.shape, want.shape)
    np.testing.assert_array_equal(out, want, err_msg=msg)


# ---------------------------------------------------------------------------
# gather_reduce: [Train] forward
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("N,D", [(32, 128), (64, 256), (16, 384)])
@pytest.mark.parametrize("shape", [(4, 5), (2, 3, 7), (1, 1)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_gather_reduce_sweep(N, D, shape, dtype):
    st_ = jnp.asarray(RNG.standard_normal((N, D)), dtype=dtype)
    ids = jnp.asarray(RNG.integers(0, N, shape + (5,)), jnp.int32)
    assert_bit_identical(
        ops.gather_reduce(st_, ids), ref.gather_reduce_ref(st_, ids)
    )


@pytest.mark.parametrize("D", [8, 40, 192])  # D % min(128, D) != 0 tails
def test_gather_reduce_ragged_lanes(D):
    st_ = jnp.asarray(RNG.standard_normal((24, D)).astype(np.float32))
    ids = jnp.asarray(RNG.integers(0, 24, (6, 4)), jnp.int32)
    assert_bit_identical(
        ops.gather_reduce(st_, ids), ref.gather_reduce_ref(st_, ids)
    )


def test_gather_reduce_duplicates_within_and_across_bags():
    st_ = jnp.asarray(RNG.standard_normal((16, 128)).astype(np.float32))
    ids = jnp.asarray([[3, 3, 3, 5], [5, 3, 5, 3], [0, 0, 0, 0]], jnp.int32)
    assert_bit_identical(
        ops.gather_reduce(st_, ids), ref.gather_reduce_ref(st_, ids)
    )


@pytest.mark.parametrize("shape", [(0, 5), (3, 0), (0, 0)])
def test_gather_reduce_empty_operands(shape):
    """Empty cycles skip the pallas_call entirely (grid would be size 0)."""
    st_ = jnp.asarray(RNG.standard_normal((8, 128)).astype(np.float32))
    ids = jnp.zeros(shape, jnp.int32)
    assert_bit_identical(
        ops.gather_reduce(st_, ids), ref.gather_reduce_ref(st_, ids)
    )


def test_gather_reduce_custom_vjp_matches_ref_grad():
    """Forward values are bit-identical; gradients are allclose-checked —
    the cotangent accumulation order for duplicate slots belongs to the
    autodiff engine (reverse loop vs one flat scatter), not the kernel."""
    st_ = jnp.asarray(RNG.standard_normal((20, 128)).astype(np.float32))
    ids = jnp.asarray(RNG.integers(0, 20, (5, 3)), jnp.int32)
    loss_p = lambda s: jnp.sum(ops.gather_reduce(s, ids) ** 2)  # noqa: E731
    loss_r = lambda s: jnp.sum(ref.gather_reduce_ref(s, ids) ** 2)  # noqa: E731
    np.testing.assert_allclose(
        np.asarray(jax.grad(loss_p)(st_)), np.asarray(jax.grad(loss_r)(st_)),
        rtol=1e-6, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# coalesce_apply: [Train] backward (segment-sum by slot + in-place update)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("N,D,nb,L", [(16, 128, 8, 4), (64, 256, 12, 7)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_coalesce_apply_sweep(N, D, nb, L, dtype):
    st_ = jnp.asarray(RNG.standard_normal((N, D)), dtype=dtype)
    # heavy duplication on purpose: many bags update the same slot
    ids = jnp.asarray(RNG.integers(0, max(2, N // 4), (nb, L)), jnp.int32)
    g = jnp.asarray(RNG.standard_normal((nb, D)).astype(np.float32))
    assert_bit_identical(
        ops.coalesce_apply(st_, ids, g, 0.07),
        ref.coalesce_apply_ref(st_, ids, g, 0.07),
    )


@pytest.mark.parametrize("D", [8, 40, 192])
def test_coalesce_apply_ragged_lanes(D):
    st_ = jnp.asarray(RNG.standard_normal((24, D)).astype(np.float32))
    ids = jnp.asarray(RNG.integers(0, 6, (5, 3)), jnp.int32)
    g = jnp.asarray(RNG.standard_normal((5, D)).astype(np.float32))
    assert_bit_identical(
        ops.coalesce_apply(st_, ids, g, 0.05),
        ref.coalesce_apply_ref(st_, ids, g, 0.05),
    )


def test_coalesce_apply_empty_operands():
    st_ = jnp.asarray(RNG.standard_normal((8, 128)).astype(np.float32))
    out = ops.coalesce_apply(
        st_, jnp.zeros((0, 4), jnp.int32), jnp.zeros((0, 128), jnp.float32), 0.05
    )
    assert_bit_identical(out, st_)


# ---------------------------------------------------------------------------
# fill + fused fill_gather_reduce: [Insert]+[Train] in one launch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_fill_drop_mode_sentinel(dtype):
    """Slots == num_slots are the planner's drop sentinel; the kernel must
    predicate those writes off, exactly like the drop-mode scatter."""
    N, D, F = 32, 128, 6
    st_ = jnp.asarray(RNG.standard_normal((N, D)), dtype=dtype)
    slots = jnp.asarray([1, 5, N, 9, N, 2], jnp.int32)
    rows = jnp.asarray(RNG.standard_normal((F, D)).astype(np.float32))
    assert_bit_identical(
        ops.fill(st_, slots, rows), ref.fill_ref(st_, slots, rows)
    )


def test_fp16_storage_refused_when_native():
    """Mosaic cannot load float16 on the chip: a native (non-interpreted)
    call must say so, not fall back to interpret mode or the reference."""
    st_ = jnp.zeros((16, 128), jnp.float16)
    ids = jnp.zeros((2, 3), jnp.int32)
    with pytest.raises(NotImplementedError, match="float16"):
        ops.gather_reduce_q(st_, None, ids, interpret=False)


def test_interpret_only_on_cpu(monkeypatch):
    """Kernels interpret on the CPU backend, compile on TPU, and refuse any
    other backend instead of silently interpreting there."""
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._interpret_default() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret_default() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._interpret_default()


def test_fill_empty_operands():
    st_ = jnp.asarray(RNG.standard_normal((8, 128)).astype(np.float32))
    out = ops.fill(
        st_, jnp.zeros((0,), jnp.int32), jnp.zeros((0, 128), jnp.float32)
    )
    assert_bit_identical(out, st_)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("D", [128, 40, 192])
def test_fused_fill_gather_reduce_parity(dtype, D):
    """Fill feeds gather inside ONE launch: gathers must see just-filled
    rows (the intra-kernel [Insert]->[Train] RAW dependency)."""
    N, F, nb, L = 48, 7, 9, 5
    st_ = jnp.asarray(RNG.standard_normal((N, D)), dtype=dtype)
    fill_slots = jnp.asarray(
        list(RNG.permutation(N)[: F - 1]) + [N], jnp.int32  # + drop sentinel
    )
    rows = jnp.asarray(RNG.standard_normal((F, D)).astype(np.float32))
    ids = jnp.asarray(RNG.integers(0, N, (nb, L)), jnp.int32)
    # make some bags read freshly filled slots
    ids = ids.at[0, :3].set(fill_slots[0])
    st_p, bags_p = ops.fill_gather_reduce(st_, fill_slots, rows, ids)
    st_r, bags_r = ref.fill_gather_reduce_ref(st_, fill_slots, rows, ids)
    assert_bit_identical(st_p, st_r, "storage")
    assert_bit_identical(bags_p, bags_r, "bags")


@pytest.mark.parametrize("nb", [1, 3, 5, 9])  # non-pow-2 bag counts
def test_fused_non_pow2_bag_counts(nb):
    N, D, F, L = 32, 128, 4, 4
    st_ = jnp.asarray(RNG.standard_normal((N, D)).astype(np.float32))
    fill_slots = jnp.asarray(RNG.permutation(N)[:F], jnp.int32)
    rows = jnp.asarray(RNG.standard_normal((F, D)).astype(np.float32))
    ids = jnp.asarray(RNG.integers(0, N, (nb, L)), jnp.int32)
    st_p, bags_p = ops.fill_gather_reduce(st_, fill_slots, rows, ids)
    st_r, bags_r = ref.fill_gather_reduce_ref(st_, fill_slots, rows, ids)
    assert_bit_identical(st_p, st_r)
    assert_bit_identical(bags_p, bags_r)


def test_fused_empty_fill_falls_back_to_gather():
    st_ = jnp.asarray(RNG.standard_normal((16, 128)).astype(np.float32))
    ids = jnp.asarray(RNG.integers(0, 16, (4, 3)), jnp.int32)
    st_p, bags_p = ops.fill_gather_reduce(
        st_, jnp.zeros((0,), jnp.int32), jnp.zeros((0, 128), jnp.float32), ids
    )
    assert_bit_identical(st_p, st_)
    assert_bit_identical(bags_p, ref.gather_reduce_ref(st_, ids))


def test_fused_custom_vjp_matches_ref_grad():
    """d(storage), d(rows) through the fused op == jax.grad of the jnp
    reference composition (fill is a scatter-overwrite: overwritten slots'
    incoming gradient flows to the fill rows, not the old storage).
    allclose, not bitwise: when a slot is both gathered and read directly,
    XLA sums the two cotangent partials in an order of its choosing."""
    N, D, F, nb, L = 24, 128, 5, 6, 3
    st_ = jnp.asarray(RNG.standard_normal((N, D)).astype(np.float32))
    fill_slots = jnp.asarray(RNG.permutation(N)[:F], jnp.int32)
    rows = jnp.asarray(RNG.standard_normal((F, D)).astype(np.float32))
    ids = jnp.asarray(RNG.integers(0, N, (nb, L)), jnp.int32)

    def loss(op):
        def fn(s, r):
            s2, bags = op(s, fill_slots, r, ids)
            return jnp.sum(bags ** 2) + jnp.sum(s2[:3] ** 2)
        return fn

    gp = jax.grad(loss(ops.fill_gather_reduce), argnums=(0, 1))(st_, rows)
    gr = jax.grad(loss(ref.fill_gather_reduce_ref), argnums=(0, 1))(st_, rows)
    for got, want, name in ((gp[0], gr[0], "d_storage"), (gp[1], gr[1], "d_rows")):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6,
            err_msg=name,
        )


# ---------------------------------------------------------------------------
# hypothesis sweep
# ---------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(st.data())
def test_gather_reduce_property(data):
    """Hypothesis sweep: random (N, D multiple of 128, bags, L)."""
    N = data.draw(st.integers(4, 80))
    D = data.draw(st.sampled_from([128, 256]))
    nb = data.draw(st.integers(1, 10))
    L = data.draw(st.integers(1, 9))
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    st_ = jnp.asarray(rng.standard_normal((N, D)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, N, (nb, L)), jnp.int32)
    assert_bit_identical(
        ops.gather_reduce(st_, ids), ref.gather_reduce_ref(st_, ids)
    )


# ---------------------------------------------------------------------------
# LM-side kernels (quarantined in kernels/__init__.py; tolerance oracles)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "Sq,Skv,H,K,hd,causal,window",
    [
        (128, 128, 4, 2, 64, True, None),
        (256, 256, 4, 4, 32, True, None),
        (128, 128, 8, 2, 64, True, 64),
        (96, 96, 2, 2, 16, False, None),  # encoder (bidirectional) + padding
        (160, 160, 4, 1, 32, True, None),  # MQA + padding path
    ],
)
def test_flash_attention_sweep(Sq, Skv, H, K, hd, causal, window):
    q = jnp.asarray(RNG.standard_normal((2, Sq, H, hd)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((2, Skv, K, hd)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((2, Skv, K, hd)).astype(np.float32))
    out = ops.flash_attention(q, k, v, causal, window)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.standard_normal((1, 128, 4, 64)), jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((1, 128, 2, 64)), jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((1, 128, 2, 64)), jnp.bfloat16)
    out = ops.flash_attention(q, k, v, True, None)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=3e-2
    )


def test_flash_attention_backward_matches_ref():
    q = jnp.asarray(RNG.standard_normal((1, 128, 4, 32)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((1, 128, 2, 32)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((1, 128, 2, 32)).astype(np.float32))
    g1 = jax.grad(lambda *a: ops.flash_attention(*a).sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(
        lambda *a: ref.flash_attention_ref(*a).sum(), argnums=(0, 1, 2)
    )(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize(
    "B,S,ng,hpg,hd,ds,Q",
    [(2, 32, 1, 4, 8, 16, 8), (1, 64, 2, 3, 16, 8, 16), (1, 40, 1, 2, 8, 8, 16)],
)
def test_ssd_chunk_kernel_vs_scan(B, S, ng, hpg, hd, ds, Q):
    """Fused SSD Pallas kernel == the pure-jnp chunked scan (incl. padding)."""
    from repro.models.mamba2 import ssd_scan

    nh = ng * hpg
    x = jnp.asarray(RNG.standard_normal((B, S, nh, hd)).astype(np.float32))
    dt = jnp.asarray(RNG.uniform(0.05, 1.0, (B, S, nh)).astype(np.float32))
    A = -jnp.asarray(RNG.uniform(0.3, 4.0, (nh,)).astype(np.float32))
    Bm = jnp.asarray(RNG.standard_normal((B, S, ng, ds)).astype(np.float32))
    Cm = jnp.asarray(RNG.standard_normal((B, S, ng, ds)).astype(np.float32))
    y1, h1 = ops.ssd_chunk_scan(x, dt, A, Bm, Cm, chunk=Q)
    y2, h2 = ssd_scan(x, dt, A, Bm, Cm, Q)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=2e-4)
