"""Kernel: bytes of tiles the crossbar's grids fetch in the window over
the bytes the bags require (:func:`bench.reference.needed_bytes`).
Every shard runs a grid of ``stats.grid_cells_per_shard`` cells per
flush, so the tiles fetched are ``shards`` times those cells times one
tile."""

UNIT = "x"
LAYER = "kernel (kernels/crossbar_reduce)"
MOVES = "bags_per_s"


def read(m):
    if not m.needed_bytes:
        return None
    cells = m.after["grid_cells"] - m.before["grid_cells"]
    return m.shards * cells * m.tile_rows * m.dim * m.itemsize / m.needed_bytes
