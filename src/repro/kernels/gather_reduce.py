"""Pallas TPU kernels: the [Insert]/[Train] forward primitives (paper §II-B).

Storage never enters the Pallas block pipeline. It stays in HBM
(``memory_space=pl.ANY``) and every row access is an explicit DMA that the
kernel starts and waits for itself, so the order of row reads and writes is
the program order below and nothing else:

  * a read DMAs the row into VMEM and waits before the row is used;
  * a write DMAs the row out and waits before the next access starts, so a
    later access to the same row sees it;
  * no kernel reads an output block it has not written in the same step.

Row copies follow the HBM tiling. A 32-bit row is its own DMA slice. A
packed row (bf16, int8) can only move as the aligned ``PACKED_ROWS``-row
block that holds it: reads pick the row out of the block, writes put it
into the block and copy the whole block back (``row_block``).

  * ``gather_reduce`` — embedding gather + bag reduction. Each grid step
    reduces ``G`` bags: a bag's L row copies are started together, then
    summed in lookup order (sequential-in-l, the order kernels/ref.py pins).
    An int8 payload dequantizes in-kernel against its per-row fp32 scale.
  * ``fill`` — [Insert]-stage drop-mode scatter of fetched rows. Slots are
    bucket-padded with out-of-bounds sentinels (>= N); those ops do nothing.
  * ``fill_gather_reduce`` — the FUSED forward: one pallas_call whose grid
    runs the fill steps first and the gather steps after, so every gather
    of a just-filled row reads the filled value — bit-identical to
    fill-then-gather. Storage is input/output-aliased (in-place fill); bags
    are a second fp32 output.

Grid sizes come from the pipeline's pow-2/adaptive pad buckets (plan.py):
static shapes => one cached executable per bucket. Row-count padding for
packed storages and the empty-operand guards live in kernels/ops.py; these
kernels assert ``N % row_block(dtype) == 0``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bags (gather steps) or fill rows (fill steps) per grid step: the fp32
#: sublane tile, so the (G, D) bag and fill-row blocks meet the block rule
G = 8
#: rows per DMA for packed storage dtypes: the HBM tile height
PACKED_ROWS = 8
#: lanes per row of the 2-D view of the int8 scale column (see _scale_rows)
SCALE_LANES = 128

_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def row_block(dtype) -> int:
    """Rows moved per storage DMA: 1 for 32-bit rows, the aligned tile
    height for packed (16- and 8-bit) rows."""
    return 1 if jnp.dtype(dtype).itemsize == 4 else PACKED_ROWS


def _rows_at(hbm, slot, R):
    """The HBM window a DMA of row ``slot`` moves (R rows, R-aligned)."""
    if R == 1:
        return hbm.at[pl.ds(slot, 1)]
    return hbm.at[pl.ds(pl.multiple_of((slot // R) * R, R), R)]


def pick_row(block, slot, R):
    """Row ``slot`` of a fetched (R, D) block, as fp32 (1, D). The other rows
    are masked to -0.0, the exact additive identity, so the sum is the row
    bit for bit."""
    x = block.astype(jnp.float32)
    if R == 1:
        return x
    sel = lax.broadcasted_iota(jnp.int32, x.shape, 0) == slot % R
    return jnp.sum(jnp.where(sel, x, -0.0), axis=0, keepdims=True)


def put_row(block, slot, new, R):
    """``block`` with row ``slot`` replaced by the same row of ``new``
    (``new`` broadcasts: a (1, D) row or a full (R, D) block)."""
    if R == 1:
        return jnp.broadcast_to(new, block.shape).astype(block.dtype)
    sel = lax.broadcasted_iota(jnp.int32, block.shape, 0) == slot % R
    return jnp.where(sel, new, block)


def _scale_rows(scale):
    """(N, 1) fp32 scale column -> (ceil(N/128), 128): one DMA-able lane
    row per 128 slots (a single-element slice of the column is not)."""
    flat = scale.reshape(-1)
    pad = (-flat.shape[0]) % SCALE_LANES
    return jnp.pad(flat, (0, pad)).reshape(-1, SCALE_LANES)


def _gather_bags(step, ids_ref, st_hbm, sc_hbm, out_ref, buf, sbuf, sem, *,
                 nb, L, R):
    """Reduce this step's G bags (ids_ref[g, l]) into out_ref (G, D)."""

    def bag(g, carry):
        @pl.when(step * G + g < nb)  # padded bags do no work
        def _bag():
            def copies(l):
                s = ids_ref[g, l]
                cps = [pltpu.make_async_copy(
                    _rows_at(st_hbm, s, R), buf.at[l], sem.at[0, l])]
                if sc_hbm is not None:
                    cps.append(pltpu.make_async_copy(
                        sc_hbm.at[pl.ds(s // SCALE_LANES, 1)],
                        sbuf.at[pl.ds(l, 1)], sem.at[1, l]))
                return cps

            def start(l, carry):
                for cp in copies(l):
                    cp.start()
                return carry

            def addend(l):
                for cp in copies(l):
                    cp.wait()
                s = ids_ref[g, l]
                x = pick_row(buf[l], s, R)
                if sc_hbm is not None:
                    # exact product (snapped scales, core/quantize.py), so an
                    # FMA contraction rounds like mul-then-add
                    x = x * sbuf[l, s % SCALE_LANES]
                return x

            lax.fori_loop(0, L, start, 0)
            acc = lax.fori_loop(1, L, lambda l, a: a + addend(l), addend(0))
            out_ref[pl.ds(g, 1), :] = acc

        return carry

    lax.fori_loop(0, G, bag, 0)


def _fill_rows(step, slots_ref, rows_ref, st_hbm, buf, sem, *, N, R):
    """Write this step's G fill rows into their slots (sentinels >= N skip)."""

    def fill_one(g, carry):
        s = slots_ref[g, 0]

        @pl.when((s >= 0) & (s < N))
        def _fill():
            dst = _rows_at(st_hbm, s, R)
            if R == 1:
                src = rows_ref.at[pl.ds(g, 1)]
            else:  # read-modify-write of the packed block holding the row
                rd = pltpu.make_async_copy(dst, buf, sem)
                rd.start()
                rd.wait()
                row = pick_row(rows_ref[...], g, G).astype(buf.dtype)
                buf[...] = put_row(buf[...], s, row, R)
                src = buf
            wr = pltpu.make_async_copy(src, dst, sem)
            wr.start()
            wr.wait()

        return carry

    lax.fori_loop(0, G, fill_one, 0)


def _gather_scratch(L, R, D, dtype, quant):
    return [
        pltpu.VMEM((L, R, D), dtype),
        pltpu.SMEM((L, SCALE_LANES), jnp.float32) if quant else None,
        pltpu.SemaphoreType.DMA((2, L)),
    ]


def _pad_bags(slot_ids):
    nb = slot_ids.shape[0]
    return jnp.pad(slot_ids.astype(jnp.int32), ((0, (-nb) % G), (0, 0)))


def _pad_fills(fill_slots, rows, N):
    pad = (-fill_slots.shape[0]) % G
    slots = jnp.pad(fill_slots.astype(jnp.int32), (0, pad), constant_values=N)
    return slots[:, None], jnp.pad(rows, ((0, pad), (0, 0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_reduce(storage, slot_ids, scale=None, *, interpret=False):
    """storage (N, D); slot_ids (nb, L) int32 -> (nb, D) fp32 bags. With an
    (N, 1) fp32 ``scale`` (int8 storage) each row dequantizes in-kernel."""
    nb, L = slot_ids.shape
    N, D = storage.shape
    R = row_block(storage.dtype)
    assert N % R == 0, (N, R)  # row padding lives in ops.py
    quant = scale is not None
    ids = _pad_bags(slot_ids)

    def kernel(ids_ref, st_hbm, *refs):
        if quant:
            sc_hbm, out_ref, buf, sbuf, sem = refs
        else:
            (out_ref, buf, sem), sc_hbm, sbuf = refs, None, None
        _gather_bags(pl.program_id(0), ids_ref, st_hbm, sc_hbm, out_ref, buf,
                     sbuf, sem, nb=nb, L=L, R=R)

    operands = [ids, storage] + ([_scale_rows(scale)] if quant else [])
    out = pl.pallas_call(
        kernel,
        grid=(ids.shape[0] // G,),
        in_specs=[
            pl.BlockSpec((G, L), lambda i: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ] + ([pl.BlockSpec(memory_space=pl.ANY)] if quant else []),
        out_specs=pl.BlockSpec((G, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(ids.shape[:1] + (D,), jnp.float32),
        scratch_shapes=[s for s in _gather_scratch(L, R, D, storage.dtype, quant)
                        if s is not None],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(*operands)
    return out[:nb]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fill(storage, fill_slots, rows, *, interpret=False):
    """storage (N, D); fill_slots (F,) int32, sentinel-padded with values
    >= N (dropped); rows (F, D). Returns the filled storage (in place)."""
    N, D = storage.shape
    R = row_block(storage.dtype)
    assert N % R == 0, (N, R)
    slots, rows = _pad_fills(fill_slots, rows.astype(storage.dtype), N)

    def kernel(slots_ref, rows_ref, st_in, st_hbm, buf, sem):
        del st_in  # aliased with st_hbm
        _fill_rows(pl.program_id(0), slots_ref, rows_ref, st_hbm, buf, sem,
                   N=N, R=R)

    return pl.pallas_call(
        kernel,
        grid=(slots.shape[0] // G,),
        in_specs=[
            pl.BlockSpec((G, 1), lambda i: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((G, D), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((N, D), storage.dtype),
        scratch_shapes=[pltpu.VMEM((R, D), storage.dtype),
                        pltpu.SemaphoreType.DMA(())],
        input_output_aliases={2: 0},  # (slots=0, rows=1, storage=2)
        compiler_params=_PARAMS,
        interpret=interpret,
    )(slots, rows, storage)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fill_gather_reduce(storage, fill_slots, fill_rows, slot_ids, scale=None,
                       *, interpret=False):
    """Fused [Insert]-fill + gather/bag-reduce: storage (N, D); fill_slots
    (F,) sentinel-padded; fill_rows (F, D); slot_ids (nb, L) int32; for int8
    storage the (N, 1) ``scale`` must ALREADY hold the fill rows' scales.
    Returns (filled storage (N, D), fp32 bags (nb, D)) from ONE pallas_call.

    Grid: ceil(F/G) fill steps, then ceil(nb/G) gather steps. Each block
    spec parks on its last (or first) block during the other phase."""
    nb, L = slot_ids.shape
    N, D = storage.shape
    R = row_block(storage.dtype)
    assert N % R == 0, (N, R)
    assert fill_slots.shape[0] > 0 and nb * L > 0  # empty guards: ops.py
    quant = scale is not None
    slots, rows = _pad_fills(fill_slots, fill_rows.astype(storage.dtype), N)
    ids = _pad_bags(slot_ids)
    nf = slots.shape[0] // G

    def kernel(slots_ref, rows_ref, ids_ref, st_in, *refs):
        del st_in  # aliased with st_hbm
        if quant:
            sc_hbm, st_hbm, out_ref, buf, rbuf, sbuf, sem = refs
        else:
            (st_hbm, out_ref, buf, rbuf, sem), sc_hbm, sbuf = refs, None, None
        i = pl.program_id(0)

        @pl.when(i < nf)
        def _fills():
            _fill_rows(i, slots_ref, rows_ref, st_hbm, rbuf, sem.at[0, 0],
                       N=N, R=R)

        @pl.when(i >= nf)
        def _gathers():
            _gather_bags(i - nf, ids_ref, st_hbm, sc_hbm, out_ref, buf, sbuf,
                         sem, nb=nb, L=L, R=R)

    fill_step = lambda i: (jnp.minimum(i, nf - 1), 0)  # noqa: E731
    bag_step = lambda i: (jnp.maximum(i - nf, 0), 0)  # noqa: E731
    scratch = _gather_scratch(L, R, D, storage.dtype, quant)
    scratch.insert(1, pltpu.VMEM((R, D), storage.dtype))
    operands = [slots, rows, ids, storage] + (
        [_scale_rows(scale)] if quant else [])
    storage_out, bags = pl.pallas_call(
        kernel,
        grid=(nf + ids.shape[0] // G,),
        in_specs=[
            pl.BlockSpec((G, 1), fill_step, memory_space=pltpu.SMEM),
            pl.BlockSpec((G, D), fill_step),
            pl.BlockSpec((G, L), bag_step, memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ] + ([pl.BlockSpec(memory_space=pl.ANY)] if quant else []),
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((G, D), bag_step),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, D), storage.dtype),
            jax.ShapeDtypeStruct(ids.shape[:1] + (D,), jnp.float32),
        ],
        scratch_shapes=[s for s in scratch if s is not None],
        input_output_aliases={3: 0},  # (slots=0, rows=1, ids=2, storage=3)
        compiler_params=_PARAMS,
        interpret=interpret,
    )(*operands)
    return storage_out, bags[:nb]
