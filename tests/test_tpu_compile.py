"""Ahead-of-time compiles for a described TPU v5e.

Interpret mode cannot show what the chip's compiler refuses (block
shapes off the (8, 128) tiling, a dynamic_slice of a loaded value,
scoped-VMEM overflow).  These tests compile the Pallas row kernel at
real widths, and the bucketed step with a heavy residual at hub width,
for a ``v5e:2x2`` topology that is described, not attached; a kernel
must really be in its program (``tpu_custom_call``).  Nothing runs, so
they say nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and each xdist
worker imports every test file.  All such compiles live in this one
file, so they land on one worker.
"""

import pytest

import jax
import jax.numpy as jnp

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
    """The bucketed step whose heavy residual holds 8 hubs of 16384
    neighbor slots (2^17 edges) over 2^15 vertices: the sorted heavy
    path (one packed-key sort of the residual, run sums, segment
    argmax) compiles for the chip."""
    import functools

    from cuvite_tpu.louvain.bucketed import bucketed_step

    nv, ne_h = 1 << 15, 8 * 16384
    f32, i32 = jnp.float32, jnp.int32
    heavy = (_spec(one_chip, (ne_h,), i32), _spec(one_chip, (ne_h,), i32),
             _spec(one_chip, (ne_h,), f32))
    step = jax.jit(functools.partial(bucketed_step, nv_total=nv,
                                     sentinel=SENTINEL))
    compiled = step.lower(
        (), heavy, _spec(one_chip, (nv,), f32), _spec(one_chip, (nv,), i32),
        _spec(one_chip, (nv,), f32), _spec(one_chip, (), f32)).compile()
    assert "sort" in compiled.as_text()


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
