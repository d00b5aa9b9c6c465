"""Pallas TPU kernel: padded embedding-bag (gather + sum).

The *naive/nMARS datapath*: each query gathers its rows directly by row id
(no grouping, no tiling locality) and sums them.  Serves two roles:

  * the baseline the ReCross kernel is compared against in benchmarks,
  * the production gather for LM token embedding where every lookup is
    single-hot (the READ-path regime).

Scalar-prefetched ``indices`` drive the BlockSpec index_map so each grid
step DMAs exactly one ``(block_rows, dim)`` slab of the table containing
the needed row — the HBM traffic model is one row-granule per lookup, like
a real gather.

Grid: ``(batch, bag)``; accumulation in f32 VMEM scratch as usual.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    pad_idx_ref,   # scalar-prefetch (batch, bag) int32 row ids, -1 pad
    block_ref,     # scalar-prefetch (batch, bag) int32 block index
    offset_ref,    # scalar-prefetch (batch, bag) int32 row-within-block
    row_ref,       # VMEM (1, block_rows, dim) — slab holding the row
    out_ref,       # VMEM (1, dim)
    acc_ref,       # scratch VMEM (1, dim) f32
    *,
    bag: int,
):
    b = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    off = offset_ref[b, k]
    valid = (pad_idx_ref[b, k] >= 0).astype(jnp.float32)
    row = row_ref[0, pl.ds(off, 1), :].astype(jnp.float32)  # (1, dim)
    acc_ref[...] += row * valid

    @pl.when(k == bag - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def embedding_bag_pallas(
    table: jax.Array,    # (rows, dim); rows % block_rows == 0 after padding
    indices: jax.Array,  # (batch, bag) int32, -1 padding
    *,
    block_rows: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    rows, dim = table.shape
    batch, bag = indices.shape
    if dim % 128 != 0:
        raise ValueError(f"dim={dim} must be a multiple of 128")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    pad_rows = (-rows) % block_rows
    if pad_rows:
        table = jnp.pad(table, ((0, pad_rows), (0, 0)))

    idx = indices.astype(jnp.int32)
    safe = jnp.maximum(idx, 0)
    block = safe // block_rows
    offset = safe % block_rows

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(batch, bag),
        in_specs=[
            pl.BlockSpec(
                (1, block_rows, dim), lambda b, k, pad, blk, off: (blk[b, k], 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, dim), lambda b, k, pad, blk, off: (b, 0)),
        scratch_shapes=[pltpu.VMEM((1, dim), jnp.float32)],
    )

    return pl.pallas_call(
        functools.partial(_kernel, bag=bag),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, dim), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(idx, block, offset, table.reshape(-1, block_rows, dim))
