"""Pallas TPU kernel: tiled embedding reduction with dynamic READ/MAC switch.

TPU-native re-expression of the ReCross crossbar datapath (DESIGN.md §2):

  * a "crossbar" is a ``(tile_rows, dim)`` tile of the permuted embedding
    image, fetched HBM→VMEM on demand via **scalar-prefetch indexing**
    (``tile_ids`` plays the role of crossbar selection; the BlockSpec
    index_map *is* the crossbar decoder),
  * the MAC path multiplies the wordline bitmap against the tile on the
    MXU (``bitmap @ tile``, a one-hot matmul — the in-memory MAC),
  * the READ path (popcount ≤ 1, ReCross §III-D) skips the MXU entirely
    and selects the single active row out of VMEM with a masked sum —
    the dynamic-switch ADC as a datapath branch,
  * partial sums accumulate in a float32 VMEM scratch (the "ADC output
    register"), written back once per query block.

Layout (DESIGN.md §3): ``bitmaps (nb, max_tiles, q_block, tile_rows)``
with ``tile_ids (nb, max_tiles)`` *shared by the whole block* (the host
compiler deduplicates the block's tile set; correlated queries share hot
tiles, so the union stays near one query's tile count).  Grid
``(nb, max_tiles)``; the MAC is a ``(q_block, tile_rows) @ (tile_rows,
dim)`` matmul — one tile DMA is amortized over ``q_block`` queries.  The
accumulator is a ``(q_block, dim)`` VMEM scratch (one live partial sum
per query of the block), flushed once per block.  A per-query batch
``bitmaps (batch, max_tiles, tile_rows)`` runs as ``q_block=1``: its
``(1, tile_rows)`` / ``(1, dim)`` trailing blocks span the full trailing
dims, so they satisfy Mosaic's (8, 128) block rule.

VMEM budget per grid step: one ``(tile_rows, dim)`` tile + one
``(q_block, dim)`` f32 accumulator + one ``(q_block, tile_rows)`` bitmap.
With the production defaults (tile_rows=64, dim ≤ 8192, bf16, q_block ≤ 8)
that is ≲ 1.3 MiB ≪ VMEM; block shapes are asserted MXU-aligned
(dim % 128 == 0, tile_rows % 8 == 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    pad_ids_ref,    # scalar-prefetch: (nb, max_tiles) int32, -1 padding
    safe_ids_ref,   # scalar-prefetch: ids clipped to >= 0 (feeds index_map)
    bitmap_ref,     # VMEM (1, 1, q_block, tile_rows)
    tile_ref,       # VMEM (1, tile_rows, dim) — shared by the whole block
    out_ref,        # VMEM (1, q_block, dim)
    acc_ref,        # scratch VMEM (q_block, dim) float32 — one row per query
    *,
    max_tiles: int,
    dynamic_switch: bool,
):
    n = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bm = bitmap_ref[0, 0].astype(jnp.float32)         # (q_block, tile_rows)
    q_block = bm.shape[0]
    count = jnp.sum(bm)
    # at default precision the MXU rounds f32 operands to bf16 (measured on
    # v5e: max error 2.5e-2 on a 64-row dot of N(0, 1) values); a bf16 tile
    # and the 0/1 bitmap survive that rounding exactly, an f32 table does not
    precision = (
        lax.Precision.HIGHEST if tile_ref.dtype == jnp.float32 else None
    )

    def mac_path():
        tile = tile_ref[0].astype(jnp.float32)        # (tile_rows, dim)
        return jnp.dot(
            bm, tile, precision=precision, preferred_element_type=jnp.float32
        )

    def read_path():
        # exactly one active wordline in the whole block: copy that row
        # into the single active query's accumulator lane, no MXU issue.
        # 2-D index sums (exact for a single set bit) and a masked row
        # select keep every access tile-aligned, which Mosaic requires
        hot = bm > 0
        row = jnp.sum(jnp.where(hot, lax.broadcasted_iota(jnp.int32, bm.shape, 1), 0))
        q = jnp.sum(jnp.where(hot, lax.broadcasted_iota(jnp.int32, bm.shape, 0), 0))
        tile = tile_ref[0].astype(jnp.float32)        # (tile_rows, dim)
        rows = lax.broadcasted_iota(jnp.int32, tile.shape, 0)
        val = jnp.sum(
            jnp.where(rows == row, tile, 0.0), axis=0, keepdims=True
        )                                             # (1, dim)
        lane = (
            lax.broadcasted_iota(jnp.int32, (q_block, 1), 0) == q
        ).astype(jnp.float32)
        return lane * val * (count > 0).astype(jnp.float32)

    if dynamic_switch:
        contrib = lax.cond(count <= 1.0, read_path, mac_path)
    else:
        contrib = mac_path()

    valid = (pad_ids_ref[n, s] >= 0).astype(jnp.float32)
    acc_ref[...] += contrib * valid

    @pl.when(s == max_tiles - 1)
    def _flush():
        out_ref[...] = acc_ref[...][None].astype(out_ref.dtype)


#: dtype of the 0/1 schedule bitmaps a serving flush sends to the device
#: (``repro.kernels.sharded.put_schedule``): a quarter of f32's transfer.
#: The kernel widens each bitmap block to f32 in VMEM; Mosaic on v5e
#: refuses a uint8 → f32 cast, int8 it takes.
SCHEDULE_BITMAP_DTYPE = jnp.int8


def crossbar_reduce_pallas(
    image: jax.Array,     # (num_tiles, tile_rows, dim)
    tile_ids: jax.Array,  # (batch | nb, max_tiles) int32, -1 padding
    bitmaps: jax.Array,   # per query (batch, max_tiles, tile_rows)
                          # or blocked (nb, max_tiles, q_block, tile_rows)
    *,
    dynamic_switch: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Raw pallas_call wrapper (no custom_vjp; see ops.crossbar_reduce).

    4-D bitmaps run the query-blocked kernel (``q_block`` queries share
    each tile DMA; see ``repro.core.reduction.block_compiled_queries``
    for the host-side block compiler) and return ``(nb * q_block, dim)``
    in block-major query order, matching the flat batch order the block
    compiler consumed.  3-D per-query bitmaps run the same kernel as
    ``q_block=1`` and return ``(batch, dim)``.
    """
    num_tiles, tile_rows, dim = image.shape
    batch, max_tiles = tile_ids.shape
    if bitmaps.ndim == 3:
        if bitmaps.shape != (batch, max_tiles, tile_rows):
            raise ValueError(f"bitmaps shape {bitmaps.shape} inconsistent")
        bitmaps = bitmaps[:, :, None, :]
    elif bitmaps.ndim != 4 or (
        bitmaps.shape[:2] + bitmaps.shape[3:] != (batch, max_tiles, tile_rows)
    ):
        raise ValueError(
            f"blocked bitmaps {bitmaps.shape} inconsistent with "
            f"tile_ids {tile_ids.shape} / tile_rows {tile_rows}"
        )
    q_block = bitmaps.shape[2]
    if dim % 128 != 0:
        raise ValueError(f"dim={dim} must be a multiple of 128 (MXU lanes)")
    if tile_rows % 8 != 0:
        raise ValueError(f"tile_rows={tile_rows} must be a multiple of 8 (sublanes)")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # clip padding ids to 0 for the block index map (masked in-kernel)
    safe_ids = jnp.maximum(tile_ids, 0).astype(jnp.int32)
    padded_ids = tile_ids.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # padded_ids (mask), safe_ids (index map)
        grid=(batch, max_tiles),
        in_specs=[
            pl.BlockSpec(
                (1, 1, q_block, tile_rows), lambda n, s, pad, safe: (n, s, 0, 0)
            ),
            pl.BlockSpec(
                (1, tile_rows, dim), lambda n, s, pad, safe: (safe[n, s], 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, q_block, dim), lambda n, s, pad, safe: (n, 0, 0)),
        scratch_shapes=[pltpu.VMEM((q_block, dim), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, max_tiles=max_tiles, dynamic_switch=dynamic_switch
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, q_block, dim), image.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="recross_crossbar_reduce",
    )(padded_ids, safe_ids, bitmaps, image)
    return out.reshape(batch * q_block, dim)
