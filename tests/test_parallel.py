"""Multi-chip sharding: data-parallel batch filtering and
space-parallel plane filtering with halo exchange, on the 8-virtual-
device CPU mesh (conftest). Results must be bit-identical to the
single-device path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from omero_ms_pixel_buffer_tpu.ops.convert import to_big_endian_bytes
from omero_ms_pixel_buffer_tpu.ops.png import _filter_batch, assemble_png
from omero_ms_pixel_buffer_tpu.parallel.mesh import (
    batch_sharding,
    make_mesh,
    row_sharding,
)
from omero_ms_pixel_buffer_tpu.parallel.sharding import (
    distributed_filter_plane,
    shard_batch,
    shard_rows,
    sharded_batch_filter,
)

rng = np.random.default_rng(11)


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest should provide 8 CPU devices"
    return make_mesh(("data",))


class TestDataParallel:
    @pytest.mark.parametrize("dtype", [np.uint8, np.uint16])
    def test_batch_matches_single_device(self, mesh, dtype):
        bpp = np.dtype(dtype).itemsize
        batch = rng.integers(
            0, np.iinfo(dtype).max, (16, 32, 48), dtype=dtype
        )
        sharded = shard_batch(mesh, jnp.asarray(batch))
        out = np.asarray(sharded_batch_filter(mesh, sharded, bpp=bpp))
        ref = np.asarray(
            _filter_batch(to_big_endian_bytes(jnp.asarray(batch)), bpp, "up")
        )
        np.testing.assert_array_equal(out, ref)

    def test_output_stays_sharded(self, mesh):
        batch = rng.integers(0, 60000, (8, 16, 16), dtype=np.uint16)
        sharded = shard_batch(mesh, jnp.asarray(batch))
        out = sharded_batch_filter(mesh, sharded, bpp=2)
        assert out.sharding.is_equivalent_to(
            batch_sharding(mesh), ndim=out.ndim
        )


class TestSpaceParallel:
    def test_plane_matches_single_device(self, mesh):
        plane = rng.integers(0, 60000, (64, 40), dtype=np.uint16)
        rows_sharded = shard_rows(mesh, jnp.asarray(plane))
        out = np.asarray(distributed_filter_plane(mesh, rows_sharded))
        ref = np.asarray(
            _filter_batch(to_big_endian_bytes(jnp.asarray(plane[None])), 2, "up")
        )[0]
        np.testing.assert_array_equal(out, ref)

    def test_distributed_scanlines_make_valid_png(self, mesh):
        from PIL import Image
        import io

        plane = rng.integers(0, 60000, (64, 40), dtype=np.uint16)
        rows_sharded = shard_rows(mesh, jnp.asarray(plane))
        filtered = np.asarray(distributed_filter_plane(mesh, rows_sharded))
        png = assemble_png(filtered.tobytes(), 40, 64, 16, 0)
        decoded = np.array(Image.open(io.BytesIO(png)))
        np.testing.assert_array_equal(decoded.astype(np.uint16), plane)

    def test_sharding_layout(self, mesh):
        plane = rng.integers(0, 200, (32, 16), dtype=np.uint8)
        rows_sharded = shard_rows(mesh, jnp.asarray(plane))
        out = distributed_filter_plane(mesh, rows_sharded)
        assert out.sharding.is_equivalent_to(row_sharding(mesh), ndim=2)


class TestGraftEntry:
    def test_entry_compiles(self):
        import __graft_entry__ as g

        fn, args = g.entry()
        out = jax.jit(fn)(*args)
        assert out.shape == (8, 256, 513)

    def test_dryrun_multichip(self):
        import __graft_entry__ as g

        g.dryrun_multichip(8)
        g.dryrun_multichip(4)
        g.dryrun_multichip(1)

    def test_dryrun_with_too_few_devices_names_the_flag(self):
        import __graft_entry__ as g

        with pytest.raises(
            RuntimeError, match="xla_force_host_platform_device_count=64"
        ):
            g.dryrun_multichip(64)
