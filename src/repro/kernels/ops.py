"""jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to False on a TPU backend, where the kernels compile
natively, and to True on the CPU backend, where the tests run. Any other
backend is an error: a kernel never runs interpreted on an accelerator.
Wrappers own everything the raw kernels assert away:

  * natural shapes — leading batch/table dims are flattened to (nb, L) and
    restored on the way out;
  * empty-operand cycles — zero bags, zero lookups or zero fill rows skip
    the ``pallas_call`` entirely (the same discipline as the pipeline's
    empty-dispatch guard);
  * packed row counts — a packed (bf16/fp16/int8) storage moves in aligned
    ``row_block``-row DMA blocks, so a row count that is not a multiple of
    that block is zero-padded and sliced back after. That copies storage
    and costs the in-place alias, so size packed storages to the block;
  * float16 on the chip — Mosaic cannot load float16 vectors ("Invalid
    vector type for load" on v5e), so a float16 storage raises on TPU
    (``kernel="xla"`` serves fp16 there);
  * differentiation — ``gather_reduce`` and ``fill_gather_reduce`` carry a
    ``jax.custom_vjp`` whose backward reuses the coalescing scatter-add
    kernel (grad_coalesce), so ``jax.grad`` straight through the kernel
    pair matches the reference path.

The embedding-cache primitives (gather_reduce / coalesce_apply / fill /
fill_gather_reduce) are the paper's workload. ``flash_attention`` and
``ssd_chunk_scan`` below are LM-side kernels for the unrelated arch configs
— quarantined behind lazy imports (see kernels/__init__.py), they never
load in a DLRM process.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import gather_reduce as _gr
from repro.kernels import grad_coalesce as _gc
from repro.kernels import ref as _ref


def _interpret_default() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels compile for TPU and are interpreted on CPU; "
            f"backend {backend!r} is neither (use kernel='xla')"
        )
    return backend == "cpu"


def _rows(interpret, storage):
    """Storage as the kernels take it: padded to whole DMA row blocks.
    Returns (storage, original row count)."""
    if not interpret and storage.dtype == jnp.float16:
        raise NotImplementedError(
            "kernel='pallas' cannot take float16 storage on TPU: Mosaic "
            "cannot load float16 vectors. Use kernel='xla' for fp16, or "
            "precision='int8'."
        )
    n = storage.shape[0]
    pad = (-n) % _gr.row_block(storage.dtype)
    if pad:
        storage = jnp.pad(storage, ((0, pad), (0, 0)))
    return storage, n


# --------------------------------------------------------------------- #
# forward: gather + bag reduce
# --------------------------------------------------------------------- #
def _gather_call(interpret, storage, flat_slots, scale=None):
    storage, _ = _rows(interpret, storage)
    return _gr.gather_reduce(storage, flat_slots, scale, interpret=interpret)


def _scatter_call(interpret, storage, flat_slots, bag_deltas):
    storage, n = _rows(interpret, storage)
    return _gc.scatter_add(
        storage, flat_slots, bag_deltas, interpret=interpret
    )[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _gather_reduce(interpret, n_slots, dtype_name, storage, flat_slots):
    return _gather_call(interpret, storage, flat_slots)


def _gr_fwd(interpret, n_slots, dtype_name, storage, flat_slots):
    return _gather_call(interpret, storage, flat_slots), (flat_slots,)


def _gr_bwd(interpret, n_slots, dtype_name, res, g):
    # d(storage) = duplicate each bag cotangent to its looked-up rows and
    # coalesce — exactly the backward kernel, scattered into a zero buffer.
    (flat_slots,) = res
    dtype = jnp.dtype(dtype_name)
    zeros = jnp.zeros((n_slots, g.shape[-1]), dtype)
    return (_scatter_call(interpret, zeros, flat_slots, g.astype(dtype)), None)


_gather_reduce.defvjp(_gr_fwd, _gr_bwd)


def gather_reduce(storage, slot_ids, *, interpret=None):
    """storage (N, D); slot_ids (..., L) -> (..., D) summed bags."""
    interpret = _interpret_default() if interpret is None else interpret
    lead = slot_ids.shape[:-1]
    L = slot_ids.shape[-1]
    D = storage.shape[1]
    if L == 0 or slot_ids.size == 0:  # empty cycle: no dispatch
        return jnp.zeros(lead + (D,), storage.dtype)
    out = _gather_reduce(
        interpret, storage.shape[0], storage.dtype.name,
        storage, slot_ids.reshape(-1, L),
    )
    return out.reshape(*lead, D).astype(storage.dtype)


def gather_reduce_q(storage, scale, slot_ids, *, interpret=None):
    """Quantized-storage gather -> fp32 bags (no cast back to the storage
    dtype: the MLP consumes fp32). ``scale=None`` means dequantization is
    the exact widening cast (fp16 storage) and the plain gather — whose
    accumulator is already fp32 — is the quantized kernel; an (N, 1)
    ``scale`` makes the kernel dequantize int8 rows in-kernel."""
    interpret = _interpret_default() if interpret is None else interpret
    lead = slot_ids.shape[:-1]
    L = slot_ids.shape[-1]
    D = storage.shape[1]
    if L == 0 or slot_ids.size == 0:  # empty cycle: no dispatch
        return jnp.zeros(lead + (D,), jnp.float32)
    out = _gather_call(interpret, storage, slot_ids.reshape(-1, L), scale)
    return out.reshape(*lead, D)


# --------------------------------------------------------------------- #
# backward: duplicate + coalesce + scatter SGD update
# --------------------------------------------------------------------- #
def coalesce_deltas(buf, slot_ids, deltas, *, interpret=None):
    """Duplicate + coalesce PRE-COMPUTED per-bag deltas into ``buf`` (the
    quantized backward's fp32 accumulation buffer; ref:
    ``coalesce_deltas_ref``). Same kernel as ``coalesce_apply`` — only the
    delta pre-scaling differs, which the quantized update epilogue owns."""
    interpret = _interpret_default() if interpret is None else interpret
    L = slot_ids.shape[-1]
    if L == 0 or slot_ids.size == 0:  # empty cycle: no dispatch
        return buf
    D = deltas.shape[-1]
    return _scatter_call(
        interpret, buf, slot_ids.reshape(-1, L),
        deltas.reshape(-1, D).astype(buf.dtype),
    )


def coalesce_apply(storage, slot_ids, bag_grads, lr, *, interpret=None):
    """storage (N, D); slot_ids (..., L); bag_grads (..., D). The SGD delta
    is pre-rounded per bag (ref.scatter_deltas) so the kernel's sequential
    accumulation is bit-identical to XLA's scatter-add (no FMA contraction
    inside the loop)."""
    interpret = _interpret_default() if interpret is None else interpret
    L = slot_ids.shape[-1]
    D = bag_grads.shape[-1]
    if L == 0 or slot_ids.size == 0:  # empty cycle: no dispatch
        return storage
    deltas = _ref.scatter_deltas(storage, bag_grads, float(lr)).reshape(-1, D)
    return _scatter_call(interpret, storage, slot_ids.reshape(-1, L), deltas)


# --------------------------------------------------------------------- #
# [Insert]-fill (standalone) and the fused fill+gather forward
# --------------------------------------------------------------------- #
def fill(storage, fill_slots, rows, *, interpret=None):
    """storage (N, D); fill_slots (F,) padded with out-of-bounds sentinels
    (>= N, dropped); rows (F, D). Drop-mode scatter of fetched rows."""
    interpret = _interpret_default() if interpret is None else interpret
    if fill_slots.size == 0:  # empty cycle: no dispatch
        return storage
    padded, n = _rows(interpret, storage)
    # sentinels are == n; re-point them past the padded rows too
    slots = jnp.where(fill_slots < n, fill_slots, padded.shape[0])
    return _gr.fill(padded, slots, rows, interpret=interpret)[:n]


def _fused_call(interpret, storage, fill_slots, fill_rows, flat_slots,
                scale=None):
    padded, n = _rows(interpret, storage)
    slots = jnp.where(fill_slots < n, fill_slots, padded.shape[0])
    st, bags = _gr.fill_gather_reduce(
        padded, slots, fill_rows, flat_slots, scale, interpret=interpret
    )
    return st[:n], bags


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _fill_gather_reduce(
    interpret, n_slots, dtype_name, rows_dtype_name,
    storage, fill_slots, fill_rows, flat_slots,
):
    return _fused_call(interpret, storage, fill_slots, fill_rows, flat_slots)


def _fgr_fwd(interpret, n_slots, dtype_name, rows_dtype_name,
             storage, fill_slots, fill_rows, flat_slots):
    out = _fused_call(interpret, storage, fill_slots, fill_rows, flat_slots)
    return out, (fill_slots, flat_slots)


def _fgr_bwd(interpret, n_slots, dtype_name, rows_dtype_name, res, cts):
    # Outputs: (new_storage, bags). Both are functions of the post-fill
    # storage S' = fill(storage, fill_slots, fill_rows):
    #   d(S') = g_storage + scatter_add(g_bags at flat_slots)   (kernel)
    #   d(fill_rows) = d(S') at the (valid, unique) filled slots
    #   d(storage)   = d(S') with the filled slots zeroed (overwritten rows
    #                  contribute nothing to the original storage)
    fill_slots, flat_slots = res
    g_storage, g_bags = cts
    dtype = jnp.dtype(dtype_name)
    ds = _scatter_call(
        interpret, g_storage.astype(dtype), flat_slots, g_bags.astype(dtype)
    )
    d_rows = jnp.take(ds, fill_slots, axis=0, mode="fill", fill_value=0)
    d_rows = jnp.where((fill_slots < n_slots)[:, None], d_rows, 0)
    d_storage = ds.at[fill_slots].set(0, mode="drop")
    return (d_storage, None, d_rows.astype(jnp.dtype(rows_dtype_name)), None)


_fill_gather_reduce.defvjp(_fgr_fwd, _fgr_bwd)


def fill_gather_reduce(storage, fill_slots, fill_rows, slot_ids, *,
                       interpret=None):
    """One fused dispatch for a pipeline cycle's [Insert]-fill + gather/
    bag-reduce: returns (filled storage (N, D), bags (..., D)). Degenerate
    operands fall back to the single-kernel paths (empty-dispatch guard)."""
    interpret = _interpret_default() if interpret is None else interpret
    lead = slot_ids.shape[:-1]
    L = slot_ids.shape[-1]
    D = storage.shape[1]
    if L == 0 or slot_ids.size == 0:
        return (
            fill(storage, fill_slots, fill_rows, interpret=interpret),
            jnp.zeros(lead + (D,), storage.dtype),
        )
    if fill_slots.size == 0:
        return storage, gather_reduce(storage, slot_ids, interpret=interpret)
    st, bags = _fill_gather_reduce(
        interpret, storage.shape[0], storage.dtype.name, fill_rows.dtype.name,
        storage, fill_slots, fill_rows, slot_ids.reshape(-1, L),
    )
    return st, bags.reshape(*lead, D).astype(storage.dtype)


def fill_gather_reduce_q(storage, scale, fill_slots, fill_rows, slot_ids, *,
                         interpret=None):
    """Fused quantized fill + gather -> (payload storage, fp32 bags).
    ``scale=None`` is the fp16 path (plain fused kernel, fp32 accumulator);
    an (N, 1) ``scale`` — already scatter-updated with this cycle's fill
    scales — makes the fused kernel dequantize int8 rows in-kernel. No
    custom_vjp: the production step takes bag cotangents explicitly and the
    quantized backward runs through ``coalesce_deltas`` + the requantize
    epilogue (core/quantize.py)."""
    interpret = _interpret_default() if interpret is None else interpret
    lead = slot_ids.shape[:-1]
    L = slot_ids.shape[-1]
    D = storage.shape[1]
    if L == 0 or slot_ids.size == 0:
        return (
            fill(storage, fill_slots, fill_rows, interpret=interpret),
            jnp.zeros(lead + (D,), jnp.float32),
        )
    if fill_slots.size == 0:
        return storage, gather_reduce_q(
            storage, scale, slot_ids, interpret=interpret
        )
    st, bags = _fused_call(
        interpret, storage, fill_slots, fill_rows, slot_ids.reshape(-1, L),
        scale,
    )
    return st, bags.reshape(*lead, D)


# --------------------------------------------------------------------- #
# quarantined LM-side kernels (lazy imports; see kernels/__init__.py)
# --------------------------------------------------------------------- #
def ssd_chunk_scan(x, dt, A, Bm, Cm, *, chunk=256, interpret=None):
    """Fused Mamba2/SSD chunk scan (see kernels/ssd_chunk.py). Pads S up to a
    chunk multiple. Returns (y (B,S,nh,hd), h_final (B,nh,hd,ds))."""
    from repro.kernels import ssd_chunk as _ssd  # noqa: PLC0415 (quarantine)

    interpret = _interpret_default() if interpret is None else interpret
    S = x.shape[1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    y, h = _ssd.ssd_chunk_scan(x, dt, A, Bm, Cm, chunk=Q, interpret=interpret)
    return y[:, :S], h


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def flash_attention(
    q, k, v, causal=True, window=None, block_q=128, block_kv=128, interpret=None
):
    from repro.kernels import flash_attention as _fa  # noqa: PLC0415 (quarantine)

    interpret = _interpret_default() if interpret is None else interpret
    Sq, Skv = q.shape[1], k.shape[1]
    pq = (-Sq) % min(block_q, max(Sq, 1))
    pkv = (-Skv) % min(block_kv, max(Skv, 1))
    if pq or pkv:
        qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, pkv), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pkv), (0, 0), (0, 0)))
    else:
        qp, kp, vp = q, k, v
    out = _fa.flash_attention(
        qp, kp, vp, causal=causal, window=window,
        block_q=min(block_q, qp.shape[1]), block_kv=min(block_kv, kp.shape[1]),
        interpret=interpret,
    )
    return out[:, :Sq]


def _fa_fwd(q, k, v, causal, window, block_q, block_kv, interpret):
    out = flash_attention(q, k, v, causal, window, block_q, block_kv, interpret)
    return out, (q, k, v)


def _fa_bwd(causal, window, block_q, block_kv, interpret, res, g):
    # Backward via the jnp reference (recompute) — the fwd kernel is the
    # TPU-optimized piece; bwd runs the XLA path.
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ref.flash_attention_ref(
            q_, k_, v_, causal=causal, window=window
        ),
        q,
        k,
        v,
    )
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
