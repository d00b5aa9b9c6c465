"""The plain reference that decides ``correct``, and its control.

A bag's answer is the sum of the table rows its distinct ids name.  The
reference computes exactly that, with nothing of the program: the table
is made again from the seed, one table at a time (:func:`make_table`),
the bags are padded to fixed-shape blocks of ``BLOCK_BAGS`` bags by a
bag length rounded up to ``LEN_STEP`` (one compile per table of a cell,
not one per bag length), and each block is a gather and an f32 sum on
the device.

The compared number is the widest gap, over every element of every bag
of the window, between the served row and the reference row
(:func:`max_gap`).  Its control is the reference computed one precision
step below the configuration's: the configuration sums float32 rows at
full precision, and the step below is a three-pass bfloat16 product
(``Precision.HIGH``) of the 0/1 lookup bitmap with the rows, which keeps
of each row value only its two-bfloat16 split ``hi + lo``
(:func:`split_bf16x2`).  The split is written out here, so the control
reads the same on every backend.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import numpy as np
import jax
import jax.numpy as jnp

#: bags per reference block
BLOCK_BAGS = 1024
#: bag lengths are padded up to a multiple of this
LEN_STEP = 32


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    state = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(state, dtype=jnp.uint32))


@functools.partial(jax.jit, static_argnames=("index", "count", "shape", "dtype"))
def _table(key, index, count, shape, dtype):
    k = jax.random.split(key, count)[index]
    return jax.random.normal(k, shape, dtype=jnp.float32).astype(dtype)


def make_table(seed: int, shapes: Sequence[tuple], index: int,
               dtype=jnp.float32) -> jax.Array:
    """Table ``index`` of a configuration whose tables have ``shapes``:
    N(0, 1) values from the seed, made on the device in a jitted call of
    its own.  Table ``i`` is ``normal(split(key, len(shapes))[i], shape)``,
    so a caller that makes, uses and frees one table at a time holds no
    more device memory than the largest table needs."""
    return _table(key_for(seed), int(index), len(shapes),
                  tuple(shapes[index]), jnp.dtype(dtype).name)


def round_bf16(x: jax.Array) -> jax.Array:
    """float32 ``x`` rounded to bfloat16's 8-bit significand (to nearest,
    ties to even), kept in float32.  Done on the bits: XLA may drop a
    float32 -> bfloat16 -> float32 round trip as excess precision (the
    TPU backend does), which would leave ``x`` as it was."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def split_bf16x2(x: jax.Array) -> jax.Array:
    """``x`` as a three-pass bfloat16 product with an exact 1 keeps it:
    its bfloat16 head plus the bfloat16 rounding of the remainder."""
    x = jnp.asarray(x, dtype=jnp.float32)
    hi = round_bf16(x)
    return hi + round_bf16(x - hi)


@functools.partial(jax.jit, static_argnames=("control",))
def _block_gap(table, ids, mask, served, control):
    rows = jnp.take(table, ids, axis=0)                # (B, L, dim)
    if control:
        rows = split_bf16x2(rows)
    ref = jnp.sum(rows * mask[:, :, None], axis=1)     # (B, dim)
    return jnp.max(jnp.abs(served - ref))


@jax.jit
def block_rows(table, ids, mask):
    rows = jnp.take(table, ids, axis=0)
    return jnp.sum(rows * mask[:, :, None], axis=1)


def pad_len(bags: Sequence[np.ndarray]) -> int:
    """The block's bag axis: the longest bag, rounded up to LEN_STEP."""
    longest = max((len(b) for b in bags), default=1)
    return max(LEN_STEP, -(-longest // LEN_STEP) * LEN_STEP)


def pack(bags: Sequence[np.ndarray], length: int, block: int = BLOCK_BAGS):
    """All bags as ``(n, length)`` int32 ids and f32 masks, ``n`` rounded
    up to whole blocks.  Each bag's ids are made distinct first; pad
    slots read row 0 under mask 0, pad bags are empty."""
    lens = np.fromiter((len(b) for b in bags), dtype=np.int64, count=len(bags))
    flat = (np.concatenate([np.asarray(b, dtype=np.int64) for b in bags])
            if len(bags) else np.zeros(0, np.int64))
    bag_of = np.repeat(np.arange(len(bags), dtype=np.int64), lens)
    # distinct ids per bag: sort by (bag, id) and drop repeats
    order = np.lexsort((flat, bag_of))
    flat, bag_of = flat[order], bag_of[order]
    keep = np.ones(flat.size, dtype=bool)
    keep[1:] = (flat[1:] != flat[:-1]) | (bag_of[1:] != bag_of[:-1])
    flat, bag_of = flat[keep], bag_of[keep]
    counts = np.bincount(bag_of, minlength=len(bags))
    pos = np.arange(flat.size, dtype=np.int64) - np.repeat(
        np.cumsum(counts) - counts, counts)
    n = max(1, -(-len(bags) // block)) * block
    ids = np.zeros((n, length), dtype=np.int32)
    mask = np.zeros((n, length), dtype=np.float32)
    ids[bag_of, pos] = flat
    mask[bag_of, pos] = 1.0
    return ids, mask


def blocks(bags: Sequence[np.ndarray], length: int, block: int = BLOCK_BAGS):
    """Yields ``(start, ids, mask)``: ``block`` bags at a time as
    ``(block, length)`` arrays (see :func:`pack`)."""
    ids, mask = pack(bags, length, block)
    for start in range(0, ids.shape[0], block):
        yield start, ids[start:start + block], mask[start:start + block]


def max_gap(table: jax.Array, bags: Sequence[np.ndarray], served: np.ndarray,
            *, control: bool = False) -> float:
    """Widest ``|served - reference|`` over every element of every bag.

    ``served`` holds one row per bag, in bag order.  ``control=True``
    computes the reference one precision step down (module docstring).
    """
    if len(bags) != len(served):
        raise ValueError(f"{len(served)} served rows for {len(bags)} bags")
    length = pad_len(bags)
    dim = table.shape[1]
    gap = jnp.zeros((), jnp.float32)
    for start, ids, mask in blocks(bags, length):
        rows = np.zeros((BLOCK_BAGS, dim), dtype=np.float32)
        got = served[start:start + BLOCK_BAGS]
        rows[:len(got)] = got
        gap = jnp.maximum(gap, _block_gap(table, ids, mask, rows, control))
    return float(gap)


@jax.jit
def _block_control_gap(table, ids, mask):
    rows = jnp.take(table, ids, axis=0)
    plain = jnp.sum(rows * mask[:, :, None], axis=1)
    lower = jnp.sum(split_bf16x2(rows) * mask[:, :, None], axis=1)
    return jnp.max(jnp.abs(plain - lower))


def control_gap(table: jax.Array, bags: Sequence[np.ndarray]) -> float:
    """The compared number when the control stands in the program's
    place: the reference one precision step down against the reference."""
    gap = jnp.zeros((), jnp.float32)
    for _, ids, mask in blocks(bags, pad_len(bags)):
        gap = jnp.maximum(gap, _block_control_gap(table, ids, mask))
    return float(gap)


def reference_rows(table: jax.Array, bags: Sequence[np.ndarray]) -> List[jax.Array]:
    """The reference's own rows, block by block (device arrays of
    ``BLOCK_BAGS`` rows each; pad rows are zero)."""
    length = pad_len(bags)
    return [block_rows(table, ids, mask) for _, ids, mask in blocks(bags, length)]


def needed_bytes(tables_of: np.ndarray, bags: Sequence[np.ndarray], flush: int,
                 dim: int, itemsize: int) -> int:
    """Bytes the bags require, whoever serves them: per group of
    ``flush`` consecutive bags, its distinct ``(table, row)`` pairs
    times a row, plus one output row per bag, plus four bytes per id."""
    total = 0
    for start in range(0, len(bags), flush):
        chunk = bags[start:start + flush]
        tabs = tables_of[start:start + flush]
        keys: Dict[int, List[np.ndarray]] = {}
        for t, bag in zip(tabs.tolist(), chunk):
            keys.setdefault(t, []).append(np.asarray(bag, dtype=np.int64))
        distinct = sum(np.unique(np.concatenate(v)).size for v in keys.values())
        ids = sum(len(b) for b in chunk)
        total += distinct * dim * itemsize + len(chunk) * dim * itemsize + 4 * ids
    return total
