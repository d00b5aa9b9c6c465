"""Compiles the served path for a described TPU v5e, without a chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and refuses what Mosaic or XLA would
refuse on the chip (unaligned slices, block shapes that break the (8, 128)
rule, collectives that cannot be partitioned).  Interpret-mode tests
cannot see any of that.  Every test asserts that the Pallas kernel is
really in the executable (``tpu_custom_call``), not interpreted.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.crossbar_reduce import (
    SCHEDULE_BITMAP_DTYPE, crossbar_reduce_pallas,
)
from repro.kernels.sharded import (
    _emulated_fn, _mesh_fn, _mesh_single_fn, _mesh_subset_fn, lower_sharded,
)

TILE_ROWS, DIM, Q_BLOCK = 64, 128, 8
NUM_TILES, NB, MAX_TILES = 256, 16, 12


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    devices = np.asarray(topo.devices).reshape(1, len(topo.devices))
    return Mesh(devices, ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def _assert_kernel_compiled(lowered) -> str:
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("dynamic_switch", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("q_block", [Q_BLOCK, None], ids=["blocked", "per_query"])
def test_crossbar_kernel_compiles(one_chip, q_block, dtype, dynamic_switch):
    """The blocked kernel, and the per-query form that runs as q_block=1."""
    bitmap_shape = (NB, MAX_TILES) + ((q_block,) if q_block else ()) + (TILE_ROWS,)
    args = (
        jax.ShapeDtypeStruct((NUM_TILES, TILE_ROWS, DIM), dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((NB, MAX_TILES), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct(bitmap_shape, dtype, sharding=one_chip),
    )
    fn = jax.jit(lambda img, ids, bm: crossbar_reduce_pallas(
        img, ids, bm, dynamic_switch=dynamic_switch, interpret=False
    ))
    _assert_kernel_compiled(fn.lower(*args))


def _stacked_args(shards, sharding):
    return (
        jax.ShapeDtypeStruct(
            (shards, NUM_TILES, TILE_ROWS, DIM), jnp.float32, sharding=sharding
        ),
        jax.ShapeDtypeStruct((shards, NB, MAX_TILES), jnp.int32, sharding=sharding),
        jax.ShapeDtypeStruct(
            (shards, NB, MAX_TILES, Q_BLOCK, TILE_ROWS), jnp.float32,
            sharding=sharding,
        ),
    )


def test_one_shard_dispatch_compiles(one_chip):
    """The jitted flush program a one-chip server dispatches."""
    fn = _emulated_fn((0,), 2, True, False)
    _assert_kernel_compiled(fn.lower(*_stacked_args(1, one_chip)))


def test_mesh_psum_scatter_dispatch_compiles(mesh):
    """The full-axis combine (psum_scatter + all_gather) over four chips."""
    fn = _mesh_fn(mesh, "model", 2, True, False, True)
    args = _stacked_args(mesh.shape["model"], NamedSharding(mesh, P("model")))
    lowered = fn.lower(*args)
    assert "reduce_scatter" in lowered.as_text()
    text = _assert_kernel_compiled(lowered)
    assert "all-gather" in text


def test_mesh_single_participant_dispatch_compiles(mesh):
    """The per-shard flush over four chips: no collective at all."""
    fn = _mesh_single_fn(mesh, "model", 2, True, False)
    args = _stacked_args(mesh.shape["model"], NamedSharding(mesh, P("model")))
    _assert_kernel_compiled(fn.lower(*args))


def test_mesh_subset_dispatch_compiles(mesh):
    """The owner-set flush: a grouped psum among two of the four chips."""
    fn = _mesh_subset_fn(mesh, "model", 2, True, False, ((0, 1), (2, 3)))
    args = _stacked_args(mesh.shape["model"], NamedSharding(mesh, P("model")))
    text = _assert_kernel_compiled(fn.lower(*args))
    assert "all-reduce" in text


def test_flush_program_names_its_kernel(one_chip):
    """The flush program is ``jit_recross_flush`` and each of its Pallas
    calls is an HLO instruction named ``recross_crossbar_reduce``, so a
    device trace can find the kernel by name."""
    fn = _emulated_fn((0,), 2, True, False)
    text = _assert_kernel_compiled(fn.lower(*_stacked_args(1, one_chip)))
    assert text.startswith("HloModule jit_recross_flush")
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2
    assert all(line.lstrip().startswith("%recross_crossbar_reduce")
               for line in calls)


@pytest.mark.parametrize(
    "shard_ids", [None, (0,), (0, 2), (0, 1, 2, 3)],
    ids=["one_chip", "mesh_single", "mesh_subset", "mesh_full"],
)
def test_lower_flush_takes_one_byte_bitmaps(one_chip, mesh, shard_ids):
    """What ``lower_flush`` lowers: the schedule as ``put_schedule`` puts
    it, with int8 bitmaps (a mesh subset already scattered into the full
    stack), compiled with the kernel in Mosaic and an s8 bitmap input
    (one shard's schedule per device)."""
    if shard_ids is None:
        shards, sharding, kw = 1, one_chip, {}
    else:
        shards = mesh.shape["model"]
        sharding = NamedSharding(mesh, P("model"))
        kw = {"mesh": mesh, "shard_ids": np.asarray(shard_ids)}
    args = (
        jax.ShapeDtypeStruct(
            (shards, NUM_TILES, TILE_ROWS, DIM), jnp.float32, sharding=sharding
        ),
        jax.ShapeDtypeStruct((shards, NB, MAX_TILES), jnp.int32, sharding=sharding),
        jax.ShapeDtypeStruct(
            (shards, NB, MAX_TILES, Q_BLOCK, TILE_ROWS), SCHEDULE_BITMAP_DTYPE,
            sharding=sharding,
        ),
    )
    lowered = lower_sharded(*args, combine_chunks=2, interpret=False, **kw)
    text = _assert_kernel_compiled(lowered)
    assert f"s8[1,{NB},{MAX_TILES},{Q_BLOCK},{TILE_ROWS}]" in text


def test_a_mesh_takes_only_the_full_schedule_stack(mesh):
    """On a mesh a subset's schedules come scattered into the full stack
    (``put_schedule``); a stack of the participants alone is refused."""
    sharding = NamedSharding(mesh, P("model"))
    images = jax.ShapeDtypeStruct(
        (mesh.shape["model"], NUM_TILES, TILE_ROWS, DIM), jnp.float32,
        sharding=sharding,
    )
    ids = jax.ShapeDtypeStruct((2, NB, MAX_TILES), jnp.int32)
    bms = jax.ShapeDtypeStruct(
        (2, NB, MAX_TILES, Q_BLOCK, TILE_ROWS), SCHEDULE_BITMAP_DTYPE)
    with pytest.raises(ValueError, match="on a mesh"):
        lower_sharded(images, ids, bms, mesh=mesh,
                      shard_ids=np.asarray([0, 2]), interpret=False)
