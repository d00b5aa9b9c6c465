"""Kernel: bytes of tiles the crossbar's grid fetches in the window
(``stats.grid_cells_per_shard`` times one tile) over the bytes the bags
require (:func:`bench.reference.needed_bytes`)."""

UNIT = "x"
LAYER = "kernel (kernels/crossbar_reduce)"
MOVES = "bags_per_s"


def read(m):
    if not m.needed_bytes:
        return None
    cells = m.after["grid_cells"] - m.before["grid_cells"]
    return cells * m.tile_rows * m.dim * m.itemsize / m.needed_bytes
