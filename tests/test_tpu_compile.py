"""Ahead-of-time compiles of the cycle kernels for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: block shapes off the (8, 128) tile, DMA slices not aligned to the
HBM tiling of packed dtypes, SMEM overflow. These tests hand the TPU
compiler the kernels at the paper's widths — N = 2^20 slots, D = 128,
2048·8 bags × 20 lookups — on a chip that is described, not attached.
Nothing runs; a compile that passes is not a chip run.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and several test workers import
this file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import gather_reduce as gr
from repro.kernels import grad_coalesce as gc

N, D, NB, L, F = 1 << 20, 128, 2048 * 8, 20, 1 << 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # a native kernel, not an interpreter


STORE = {
    "fp32": jnp.float32,
    "bf16": jnp.bfloat16,
    "int8": jnp.int8,
}


@pytest.mark.parametrize("precision", ["fp32", "bf16", "int8"])
def test_gather_reduce_compiles(one_chip, precision):
    dt = STORE[precision]
    if precision == "int8":
        _compile(one_chip, gr.gather_reduce,
                 ((N, D), dt), ((NB, L), jnp.int32), ((N, 1), jnp.float32))
    else:
        _compile(one_chip, gr.gather_reduce, ((N, D), dt), ((NB, L), jnp.int32))


@pytest.mark.parametrize("precision", ["fp32", "bf16", "int8"])
def test_fill_compiles(one_chip, precision):
    dt = STORE[precision]
    _compile(one_chip, gr.fill, ((N, D), dt), ((F,), jnp.int32), ((F, D), dt))


@pytest.mark.parametrize("precision", ["fp32", "bf16", "int8"])
def test_fill_gather_reduce_compiles(one_chip, precision):
    dt = STORE[precision]
    shapes = [((N, D), dt), ((F,), jnp.int32), ((F, D), dt),
              ((NB, L), jnp.int32)]
    if precision == "int8":
        shapes.append(((N, 1), jnp.float32))
    _compile(one_chip, gr.fill_gather_reduce, *shapes)


@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_scatter_add_compiles(one_chip, precision):
    dt = STORE[precision]
    _compile(one_chip, gc.scatter_add,
             ((N, D), dt), ((NB, L), jnp.int32), ((NB, D), dt))
