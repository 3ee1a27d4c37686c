"""Pallas TPU kernel: gradient duplication + coalescing + scatter update
(the paper's memory-bound backward primitive, §II-B Fig. 2(b)).

The storage buffer is input/output-aliased and stays in HBM
(``memory_space=pl.ANY``). Every lookup is one explicit read-modify-write
of its row: DMA the row in, wait, add the bag's delta, DMA it back, wait.
The next lookup starts only after that write has landed, so duplicate rows
within and across bags coalesce in flat bag-major order — exactly XLA's
``at[].add`` — without a separate sort pass. Packed storages move the
aligned block that holds the row (gather_reduce.row_block).

The kernel body is a PURE add of a pre-rounded per-bag delta. The SGD
scaling (``-lr * bag_grads``) is applied ONCE per bag in the wrapper
(kernels/ref.py:scatter_deltas) — an in-kernel ``acc += -lr * g`` would
contract to an FMA (one rounding for mul+add) and break bit-parity with
XLA's rounded-product-then-scatter-add. It also makes the kernel the
generic coalescing scatter-add the custom_vjp backward reuses (scatter the
bag cotangent into a zero buffer).

grid = (ceil(n_bags / G),), G bags per step, one RMW per lookup.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather_reduce import (
    G, _PARAMS, _pad_bags, _rows_at, put_row, row_block,
)


def _make_kernel(nb: int, L: int, R: int):
    def kernel(ids_ref, delta_ref, st_in, st_hbm, buf, sem):
        del st_in  # aliased with st_hbm
        step = pl.program_id(0)

        def bag(g, carry):
            @pl.when(step * G + g < nb)  # padded bags do no work
            def _bag():
                # deltas ride in fp32 holding storage-dtype values: exact
                delta = delta_ref[pl.ds(g, 1), :].astype(buf.dtype)

                def lookup(l, carry):
                    s = ids_ref[g, l]
                    rows = _rows_at(st_hbm, s, R)
                    rd = pltpu.make_async_copy(rows, buf, sem)
                    rd.start()
                    rd.wait()
                    block = buf[...]
                    buf[...] = put_row(block, s, block + delta, R)
                    wr = pltpu.make_async_copy(buf, rows, sem)
                    wr.start()
                    wr.wait()
                    return carry

                lax.fori_loop(0, L, lookup, 0)

            return carry

        lax.fori_loop(0, G, bag, 0)

    return kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_add(storage, slot_ids, bag_deltas, *, interpret=False):
    """storage (N, D); slot_ids (nb, L) int32; bag_deltas (nb, D) in the
    storage dtype. Adds each bag's delta to every row it looked up,
    coalescing duplicates in flat bag-major order (== XLA's ``at[].add``)."""
    nb, L = slot_ids.shape
    N, D = storage.shape
    R = row_block(storage.dtype)
    assert N % R == 0, (N, R)  # row padding lives in ops.py
    ids = _pad_bags(slot_ids)
    deltas = jnp.pad(
        bag_deltas.astype(storage.dtype).astype(jnp.float32),
        ((0, ids.shape[0] - nb), (0, 0)),
    )
    return pl.pallas_call(
        _make_kernel(nb, L, R),
        grid=(ids.shape[0] // G,),
        in_specs=[
            pl.BlockSpec((G, L), lambda i: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((G, D), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((N, D), storage.dtype),
        scratch_shapes=[pltpu.VMEM((R, D), storage.dtype),
                        pltpu.SemaphoreType.DMA(())],
        input_output_aliases={2: 0},  # (ids=0, deltas=1, storage=2)
        compiler_params=_PARAMS,
        interpret=interpret,
    )(ids, deltas, storage)
