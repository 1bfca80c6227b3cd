"""Ask the chip's compiler, without the chip.

The TPU compiler is installed here and compiles for a chip that is
described and not attached (`on-chip-measurement` guide, section 2).
These tests compile the programs of the served path at published tile
widths (512x512 uint16, 256x256 RGB8) and a small lane count, so a
program the chip would refuse is refused here, at no chip time. A
compile that passes is not a chip run: it says nothing about results
or times.

The topology is described inside a module-scoped fixture, never at
import, and every compile runs in the test's own process: only one
process at a time may load the TPU's library, and the xdist worker
that is handed this file is the one that loads it.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

LANES = 4  # small on purpose: compile seconds scale with the lane count


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e device to compile for (compile cache off
    around the module: such entries cannot be read back without a
    chip)."""
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args, **static):
    return jax.jit(partial(fn, **static)).lower(*args).compile()


def _is_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# -- the Pallas filter kernel (the TPU default for the filter stage) ----


@pytest.mark.parametrize(
    "shape,dtype",
    [((LANES, 512, 512), jnp.uint16), ((LANES, 256, 256, 3), jnp.uint8)],
    ids=["u16-512", "rgb8-256"],
)
def test_pallas_filter_kernel_lowers(one_chip, shape, dtype):
    from omero_ms_pixel_buffer_tpu.ops.pallas.filter import _filter_tiles

    compiled = _filter_tiles.lower(
        _shape(one_chip, shape, dtype), "up", False
    ).compile()
    assert _is_kernel(compiled)


# -- the encode chain the chip serves: filter + deflate ----------------


def test_fused_filter_deflate_rle(one_chip):
    from omero_ms_pixel_buffer_tpu.ops.device_deflate import (
        filter_deflate_local,
    )

    _compile(
        filter_deflate_local,
        _shape(one_chip, (LANES, 512, 512), jnp.uint16),
        rows=512, row_bytes=1 + 512 * 2, bpp=2, filter_mode="up",
        mode="rle",
    )


def test_dynamic_pass1_filter_histogram(one_chip):
    from omero_ms_pixel_buffer_tpu.ops.device_deflate import (
        _filter_histogram_core,
    )

    _compile(
        _filter_histogram_core,
        _shape(one_chip, (LANES, 512, 512), jnp.uint16),
        rows=512, row_bytes=1 + 512 * 2, bpp=2, filter_mode="up",
    )


def _dynamic_emit_args(one_chip, lanes=LANES, payload=512 * (1 + 512 * 2)):
    """Pass-2 operands as shapes: the payload lanes plus the per-lane
    code tables, whose shapes the host table builder fixes."""
    from omero_ms_pixel_buffer_tpu.ops.device_deflate import (
        build_dynamic_tables,
    )

    tables = build_dynamic_tables(
        np.zeros((lanes, 286), np.int64), np.zeros(lanes, np.int64), real=0
    )
    return [_shape(one_chip, (lanes, payload), jnp.uint8)] + [
        _shape(one_chip, t.shape, t.dtype) for t in tables
    ]


def test_dynamic_pass2_emit(one_chip):
    from omero_ms_pixel_buffer_tpu.ops.device_deflate import (
        dynamic_emit_local,
    )

    _compile(dynamic_emit_local, *_dynamic_emit_args(one_chip))


# -- /render: the fused composite program and the super-tile carve ------


def test_render_composite_filter_deflate(one_chip):
    from omero_ms_pixel_buffer_tpu.render.engine import (
        render_filter_deflate_local,
    )

    channels = 3
    _compile(
        render_filter_deflate_local,
        _shape(one_chip, (LANES, channels, 512, 512), jnp.uint16),
        _shape(one_chip, (channels, 65536), jnp.uint8),
        _shape(one_chip, (channels, 256, 3), jnp.uint8),
        rows=512, row_bytes=1 + 512 * 3, filter_mode="up", mode="rle",
    )


def test_supertile_composite_carve(one_chip):
    from omero_ms_pixel_buffer_tpu.render import supertile

    # the jitted carve is built on first use; build it on the CPU
    # backend with a toy call, then lower the same program for the chip
    supertile.composite_carve_batch(
        jnp.zeros((1, 8, 8), jnp.uint8), jnp.zeros((1, 256), jnp.uint8),
        jnp.zeros((1, 256, 3), jnp.uint8), [(0, 0)], 8, 8,
    )
    channels, tiles = 3, 16  # a 4x4 burst of 512x512 tiles
    supertile._composite_carve_jit.lower(
        _shape(one_chip, (channels, 2048, 2048), jnp.uint16),
        _shape(one_chip, (channels, 65536), jnp.uint8),
        _shape(one_chip, (channels, 256, 3), jnp.uint8),
        _shape(one_chip, (tiles, 2), jnp.int32),
        512, 512,
    ).compile()
