"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Interpret mode cannot show what the chip's compiler refuses (block
shapes off the (8, 128) tiling, a dynamic_slice of a loaded value,
scoped-VMEM overflow).  These tests compile each kernel of the main path
at real widths for a ``v5e:2x2`` topology that is described, not
attached, and check that the kernel is really in the program
(``tpu_custom_call``).  Nothing runs, so they say nothing about results
or times.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and each xdist
worker imports every test file.  All such compiles live in this one
file, so they land on one worker.
"""

import pytest

import jax
import jax.numpy as jnp

from cuvite_tpu.kernels.heavy_bincount import heavy_argmax_pallas
from cuvite_tpu.kernels.row_argmax import row_argmax_pallas

SENTINEL = 2**31 - 1


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_heavy_argmax_compiles_at_hub_width(one_chip):
    """128 hubs of D = 16384 neighbor slots against a 2^20 community
    range: lane-aligned [d_chunk, 128] tiles, refs read by pl.ds."""
    D, H, nv_ceil = 16384, 128, 1 << 20
    f32, i32 = jnp.float32, jnp.int32
    args = ([_spec(one_chip, (D, H), i32), _spec(one_chip, (D, H), f32),
             _spec(one_chip, (nv_ceil,), f32), _spec(one_chip, (H,), i32)]
            + [_spec(one_chip, (H,), f32)] * 3 + [_spec(one_chip, (), f32)])
    _assert_kernel(heavy_argmax_pallas.lower(*args).compile())


@pytest.mark.parametrize("with_size", [False, True],
                         ids=["replicated", "sparse-size"])
@pytest.mark.parametrize("width", [8, 64, 512, 2048])
def test_row_argmax_compiles_at_width(one_chip, width, with_size):
    """Every width class up to PALLAS_MAX_WIDTH: the unrolled form
    (<= 32) and the fori_loop form, with and without the sparse
    exchange's attached-size channel."""
    n = 4096
    f32, i32 = jnp.float32, jnp.int32
    args = ([_spec(one_chip, (width, n), i32),
             _spec(one_chip, (width, n), f32),
             _spec(one_chip, (width, n), f32), _spec(one_chip, (n,), i32)]
            + [_spec(one_chip, (n,), f32)] * 3 + [_spec(one_chip, (), f32)])
    sz = _spec(one_chip, (width, n), i32) if with_size else None

    def f(*a, szT=None):
        return row_argmax_pallas(*a, szT=szT, sentinel=SENTINEL)

    _assert_kernel(jax.jit(f).lower(*args, szT=sz).compile())
