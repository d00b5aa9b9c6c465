"""JAX embedding reduction through a ReCross layout.

The *numerical* side of ReCross: given the permuted/replicated device image
produced by :meth:`CrossbarLayout.build_image`, perform the embedding-bag
reduction for a batch of queries.  Three executable paths, all producing
identical values:

  * :func:`reduce_dense_oracle` — direct gather+sum on the *logical* table
    (ground truth; layout-independent).
  * :func:`reduce_via_layout`   — pure-jnp tiled one-hot MAC through the
    physical image with dynamic READ/MAC switching expressed as
    ``jnp.where`` (the reference the Pallas kernel is tested against).
  * :mod:`repro.kernels.ops.crossbar_reduce` — the Pallas TPU kernel.

Queries arrive in the framework's *compiled query format* (a fixed-shape
representation so everything jits):

  ``tile_ids``  (batch, max_tiles)            int32, -1 padded
  ``bitmaps``   (batch, max_tiles, tile_rows) activation masks (0/1)

produced by :func:`compile_queries` from the ragged host-side form.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.mapping import CrossbarLayout


@dataclasses.dataclass
class CompiledQueries:
    """Fixed-shape query batch (device-ready)."""

    tile_ids: jax.Array   # (batch, max_tiles) int32, -1 = padding
    bitmaps: jax.Array    # (batch, max_tiles, tile_rows) same dtype as table
    max_tiles: int

    @property
    def batch(self) -> int:
        return self.tile_ids.shape[0]


@dataclasses.dataclass
class BlockedQueries:
    """Query-blocked compiled batch (device-ready, DESIGN.md §3).

    ``q_block`` consecutive queries share one tile schedule (the union of
    their per-query tile lists, deduplicated): one tile DMA serves the
    whole block, and the kernel's MAC is a ``(q_block, tile_rows)``
    matmul.  The batch is padded up to a q_block multiple; kernel output
    rows beyond :attr:`batch` are padding and should be sliced off.
    """

    tile_ids: jax.Array   # (nb, max_tiles) int32, -1 = padding — per block
    bitmaps: jax.Array    # (nb, max_tiles, q_block, tile_rows)
    q_block: int
    batch: int            # original (unpadded) query count

    @property
    def num_blocks(self) -> int:
        return self.tile_ids.shape[0]

    @property
    def max_tiles(self) -> int:
        return self.tile_ids.shape[1]


def compile_queries(
    layout: CrossbarLayout,
    queries: Sequence[Sequence[int]],
    *,
    max_tiles: int | None = None,
    dtype=jnp.float32,
    balance_replicas: bool = True,
    replica_block: int = 1,
) -> CompiledQueries:
    """Ragged host queries → fixed-shape device arrays.

    ``max_tiles`` defaults to the batch's maximum tiles-per-query, rounded
    up to a multiple of 8 for sublane friendliness.  Built directly from
    the sparse :class:`~repro.core.mapping.ActivationSet` with two
    scatters — the dense ``(batch, num_tiles, tile_rows)`` intermediate is
    never materialized.  Pass ``replica_block=q_block`` when the result
    feeds :func:`block_compiled_queries` so replica choice is shared
    inside each block (see :func:`~repro.core.mapping.compile_activations`).
    """
    from repro.core.mapping import compile_activations

    acts = compile_activations(
        layout, queries,
        balance_replicas=balance_replicas, replica_block=replica_block,
    )
    batch = acts.batch
    per_q = acts.per_query_tiles()
    width = int(per_q.max()) if per_q.size else 1
    max_tiles = _padded_width(width, max_tiles, "query")

    from repro.core.cooccurrence import segment_ranks

    tile_ids = np.full((batch, max_tiles), -1, dtype=np.int32)
    bitmaps = np.zeros((batch, max_tiles, layout.tile_rows), dtype=np.float32)
    # slot position of each activation within its query (activations are
    # (query, tile)-sorted, so the run-local rank is the position)
    pos = segment_ranks(per_q)
    tile_ids[acts.act_qid, pos] = acts.act_tile
    # wordline entries inherit their activation's slot position
    ent_pos = np.repeat(pos, acts.act_rows)
    bitmaps[acts.ent_qid, ent_pos, acts.ent_slot] = 1.0
    return CompiledQueries(
        tile_ids=jnp.asarray(tile_ids),
        bitmaps=jnp.asarray(bitmaps, dtype=dtype),
        max_tiles=max_tiles,
    )


def _pad_to_blocks(
    ids: np.ndarray, bms: np.ndarray, q_block: int
) -> tuple[np.ndarray, np.ndarray, int]:
    """Zero/-1-pads a flat compiled batch up to a q_block multiple.

    Shared by every block compiler (flat and sharded) so the padding
    rule — and therefore output row alignment — can never diverge.
    """
    batch, s_flat = ids.shape
    tile_rows = bms.shape[-1]
    nb = -(-batch // q_block) if batch else 0
    pad = nb * q_block - batch
    if pad:
        ids = np.concatenate([ids, np.full((pad, s_flat), -1, ids.dtype)])
        bms = np.concatenate([bms, np.zeros((pad, s_flat, tile_rows), bms.dtype)])
    return ids, bms, nb


def _check_block_key_capacity(n_outer: int, n_inner: int, what: str) -> None:
    """Packed block keys ``outer * n_inner + inner`` must fit in int64.

    Only reachable with absurd block counts, but wraparound here would
    silently merge unrelated (block, tile) pairs instead of raising.
    """
    if n_outer and n_inner and n_outer > ((1 << 63) - 1) // n_inner:
        raise OverflowError(
            f"{what}: {n_outer} x {n_inner} packed keys overflow int64"
        )


def _padded_width(width: int, max_tiles: int | None, what: str) -> int:
    """Union width → tile-axis allocation: sublane-friendly multiple of 8.

    One definition for every block compiler — the per-shard-grid ≤
    flat-grid invariant relies on both rounding widths identically.
    """
    if max_tiles is None:
        max_tiles = max(8, int(np.ceil(width / 8)) * 8)
    if width > max_tiles:
        raise ValueError(f"{what} touches {width} tiles > max_tiles={max_tiles}")
    return max_tiles


def block_compiled_queries(
    cq: CompiledQueries,
    q_block: int,
    *,
    max_tiles: int | None = None,
) -> BlockedQueries:
    """Flat compiled batch → query-blocked layout for the blocked kernel.

    Each block of ``q_block`` consecutive queries gets the deduplicated
    union of its members' tile lists.  With a correlation-aware layout the
    members share hot tiles, so the union width stays close to a single
    query's — that is what shrinks the kernel grid by ~``q_block``×.
    Ragged batches are zero-padded up to a block multiple.

    Compile ``cq`` with ``replica_block=q_block`` so replicated hot groups
    resolve to one tile per block instead of one per query — per-query
    round robin would put identical replica tiles in the same union.
    """
    if q_block < 1:
        raise ValueError("q_block must be >= 1")
    ids, bms, nb = _pad_to_blocks(
        np.asarray(cq.tile_ids), np.asarray(cq.bitmaps), q_block
    )
    batch = cq.tile_ids.shape[0]
    tile_rows = bms.shape[-1]

    vq, vs = np.nonzero(ids >= 0)
    vt = ids[vq, vs].astype(np.int64)
    vblk = vq // q_block
    num_tiles = int(vt.max()) + 1 if vt.size else 1
    _check_block_key_capacity(max(nb, 1), num_tiles, "block_compiled_queries")
    key = vblk * np.int64(num_tiles) + vt
    uniq = np.unique(key)
    ub = (uniq // num_tiles).astype(np.int64)
    ut = (uniq % num_tiles).astype(np.int64)
    per_blk = np.bincount(ub, minlength=max(nb, 1))
    width = int(per_blk.max()) if uniq.size else 0
    max_tiles = _padded_width(width, max_tiles, "block")

    from repro.core.cooccurrence import segment_ranks

    blocked_ids = np.full((max(nb, 1), max_tiles), -1, dtype=np.int32)
    pos_u = segment_ranks(per_blk)
    blocked_ids[ub, pos_u] = ut
    blocked_bms = np.zeros(
        (max(nb, 1), max_tiles, q_block, tile_rows), dtype=np.asarray(bms).dtype
    )
    pos_entry = pos_u[np.searchsorted(uniq, key)]
    blocked_bms[vblk, pos_entry, vq % q_block] = bms[vq, vs]
    return BlockedQueries(
        tile_ids=jnp.asarray(blocked_ids),
        bitmaps=jnp.asarray(blocked_bms),
        q_block=q_block,
        batch=batch,
    )


@dataclasses.dataclass
class ShardedBlockedQueries:
    """Per-shard query-blocked batch for the sharded kernel (DESIGN.md §4).

    The stacked form of ``num_shards`` shard-local :class:`BlockedQueries`:
    every shard sees the same block axis (so cross-shard partial sums
    align row-for-row) but its own tile schedule — shard-local tile ids,
    shard-local tile unions.  ``max_tiles`` is the widest per-(shard,
    block) union over the whole batch, so each shard's grid is
    ``(nb, max_tiles)`` with ``max_tiles`` bounded by the busiest shard,
    never by the global union.

    An activation (query, tile) is owned by exactly one shard: the tile's
    owner for sharded-once tiles, ``block % num_shards`` for tiles
    replicated on every shard (hot-group work round-robins over blocks).
    Summing the shards' kernel outputs therefore reproduces the
    single-device blocked reduction exactly once per activation.
    """

    tile_ids: jax.Array   # (P, nb, max_tiles) int32 shard-LOCAL ids, -1 pad
    bitmaps: jax.Array    # (P, nb, max_tiles, q_block, tile_rows)
    q_block: int
    batch: int            # original (unpadded) query count
    shard_widths: np.ndarray  # (P,) widest per-shard block union, pre-pad
    shards: np.ndarray | None = None  # (P,) global shard ids of the stack
    # (None = all shards in order, the full-flush compile)

    @property
    def num_shards(self) -> int:
        return self.tile_ids.shape[0]

    @property
    def shard_ids(self) -> np.ndarray:
        """Global shard id of each stacked schedule (DESIGN.md §7).

        A full-flush compile stacks every shard in order; a subset flush
        (``participants=`` to :func:`shard_block_queries`) stacks only
        the participating shards, and the kernel dispatch needs to know
        which image slices they index.
        """
        if self.shards is not None:
            return self.shards
        return np.arange(self.num_shards, dtype=np.int64)

    @property
    def num_blocks(self) -> int:
        return self.tile_ids.shape[1]

    @property
    def max_tiles(self) -> int:
        return self.tile_ids.shape[2]

    def grid_cells_per_shard(self) -> int:
        """Kernel grid cells each shard runs (= nb × padded max_tiles)."""
        return self.num_blocks * self.max_tiles


def shard_block_queries(
    cq: CompiledQueries,
    plan,
    q_block: int,
    *,
    max_tiles: int | None = None,
    participants: Sequence[int] | None = None,
) -> ShardedBlockedQueries:
    """Flat compiled batch → per-shard blocked layout for ``plan``.

    ``plan`` is a :class:`repro.dist.shard_plan.ShardPlan` (duck-typed:
    only ``num_shards`` / ``shard_of_tile`` / ``local_tile_of`` /
    ``max_local_tiles`` are read, keeping ``repro.core`` free of a
    ``repro.dist`` import).  ``cq.tile_ids`` must be in the plan's fused
    tile space — offset per-table compiles with
    :func:`offset_compiled_queries` first.

    Compile ``cq`` with ``replica_block=q_block``, exactly as for
    :func:`block_compiled_queries`; replicas of a sharded group live on
    the same shard, so block-granular replica choice stays shard-local.

    ``participants`` restricts the compile to a shard subset (DESIGN.md
    §7): the stacked schedules cover only those shards (in the given
    order — :attr:`ShardedBlockedQueries.shards` records the mapping),
    and replicated-everywhere tiles round-robin over the *participants*
    instead of all shards, so a home's batch — one shard's, or an
    owner-set home's exact owner subset — compiles without
    recompiling — or waiting for — the fused global batch.  Every
    sharded-once tile the batch activates must be owned by a
    participant; a query routed to the wrong subset raises.
    """
    if q_block < 1:
        raise ValueError("q_block must be >= 1")
    S = int(plan.num_shards)
    if participants is None:
        parts = np.arange(S, dtype=np.int64)
        shards_field = None
    else:
        parts = np.asarray(list(participants), dtype=np.int64)
        if parts.size == 0 or parts.size != np.unique(parts).size:
            raise ValueError(f"participants must be non-empty unique ids, got {parts}")
        if parts.min() < 0 or parts.max() >= S:
            raise ValueError(f"participants {parts} out of range for {S} shards")
        shards_field = parts
    P = int(parts.size)
    ids, bms, nb = _pad_to_blocks(
        np.asarray(cq.tile_ids), np.asarray(cq.bitmaps), q_block
    )
    batch = cq.tile_ids.shape[0]
    tile_rows = bms.shape[-1]
    nb_safe = max(nb, 1)

    vq, vs = np.nonzero(ids >= 0)
    vt = ids[vq, vs].astype(np.int64)
    vblk = vq // q_block
    shard_of_tile = np.asarray(plan.shard_of_tile)
    own = shard_of_tile[vt].astype(np.int64)
    # cold (host-tier) tiles are held by NO shard — a capacity-bounded
    # plan serves them via the host gather+sum path, and the server's
    # residency router must divert such queries before compile.  -2 is
    # repro.dist.shard_plan.COLD (literal here: repro.core stays free of
    # a repro.dist import).
    if (own == -2).any():
        raise ValueError(
            "batch activates cold (host-tier) tiles; cold queries must "
            "take the host gather+sum path, not the crossbar kernels"
        )
    # replicated-everywhere tiles: block-level round robin over the
    # participating shards (degrades to "the one flushing shard owns
    # everything" for a single-shard flush)
    own = np.where(own < 0, parts[vblk % P], own)
    # global shard id → stack position
    part_pos = np.full(S, -1, dtype=np.int64)
    part_pos[parts] = np.arange(P, dtype=np.int64)
    pos_own = part_pos[own]
    if pos_own.size and pos_own.min() < 0:
        missing = np.unique(own[pos_own < 0]).tolist()
        raise ValueError(
            f"batch activates tiles owned by non-participating shards "
            f"{missing}; participants={parts.tolist()}"
        )
    lt = np.asarray(plan.local_tile_of)[own, vt].astype(np.int64)
    if lt.size and lt.min() < 0:
        raise ValueError("plan does not hold an activated tile on its owner")

    Lmax = max(int(plan.max_local_tiles), 1)
    _check_block_key_capacity(P * nb_safe, Lmax, "shard_block_queries")
    key = (pos_own * nb_safe + vblk) * Lmax + lt
    uniq = np.unique(key)
    usb = uniq // Lmax
    ult = (uniq % Lmax).astype(np.int64)
    us = (usb // nb_safe).astype(np.int64)
    ub = (usb % nb_safe).astype(np.int64)
    per_sb = np.bincount(usb, minlength=P * nb_safe)
    width = int(per_sb.max()) if uniq.size else 0
    max_tiles = _padded_width(width, max_tiles, "shard block")

    from repro.core.cooccurrence import segment_ranks

    blocked_ids = np.full((P, nb_safe, max_tiles), -1, dtype=np.int32)
    pos_u = segment_ranks(per_sb)
    blocked_ids[us, ub, pos_u] = ult
    blocked_bms = np.zeros(
        (P, nb_safe, max_tiles, q_block, tile_rows), dtype=bms.dtype
    )
    pos_entry = pos_u[np.searchsorted(uniq, key)]
    blocked_bms[pos_own, vblk, pos_entry, vq % q_block] = bms[vq, vs]
    widths = per_sb.reshape(P, nb_safe).max(axis=1) if uniq.size else np.zeros(P, np.int64)
    return ShardedBlockedQueries(
        tile_ids=jnp.asarray(blocked_ids),
        bitmaps=jnp.asarray(blocked_bms),
        q_block=q_block,
        batch=batch,
        shard_widths=widths.astype(np.int64),
        shards=shards_field,
    )


class BlockUnionTracker:
    """Incremental block-union fill accounting for one pending stream.

    The flush scheduler (DESIGN.md §7) needs to know, as queries
    accumulate on a flush home — one shard, or a frozen owner set of
    shards — how large that home's kernel grid would be
    if it flushed *now* — without compiling anything.  With
    ``replica_block=q_block`` every block resolves each activated group
    to exactly one replica tile, so a block's union width equals the
    number of distinct groups its members touch; this tracker maintains
    exactly that, one ``set`` union per in-progress block:

      * :attr:`fill` — Σ union widths over all pending blocks (the raw
        tile-DMA count of a flush-now);
      * :meth:`grid_cells` — ``nb × padded max width``, the same
        sublane-padded accounting as :func:`shard_block_queries`.

    :meth:`extend` takes a run of queries' activated *group* ids
    (host-side routing already computes them) and folds the run's blocks
    in one vectorized pass.
    """

    def __init__(self, q_block: int):
        if q_block < 1:
            raise ValueError("q_block must be >= 1")
        self.q_block = q_block
        self.reset()

    def reset(self) -> None:
        self._n = 0
        self._filled = 0          # Σ union widths of completed blocks
        self._max_width = 0
        self._block: set = set()  # current partial block's union

    def _reference_add(self, groups) -> None:
        """Per-query set-union oracle of :meth:`extend`."""
        if self._n and self._n % self.q_block == 0:
            self._filled += len(self._block)
            self._max_width = max(self._max_width, len(self._block))
            self._block = set()
        self._block.update(int(g) for g in groups)
        self._n += 1

    def extend(self, groups, sizes) -> None:
        """Appends a run of queries in one pass.

        Args:
          groups: the run's group ids, query after query (``sizes[i]``
            ids for query ``i``; duplicates are harmless).
          sizes: ids per query, one entry per appended query (0 for a
            query that touches nothing).
        """
        sizes = np.asarray(sizes, dtype=np.int64)
        m = int(sizes.size)
        if m == 0:
            return
        q, n0 = self.q_block, self._n
        if n0 and n0 % q == 0:
            # the current block is complete: close it before the run
            self._filled += len(self._block)
            self._max_width = max(self._max_width, len(self._block))
            self._block = set()
        groups = np.asarray(groups, dtype=np.int64).ravel()
        b0 = n0 % q                     # the run's place in its first block
        last = (b0 + m - 1) // q        # the run's last block, relative
        if last:
            # blocks 0..last-1 complete inside the run: one sort of
            # packed (block, group) keys gives each one's union width
            k = last * q - b0           # first query of the last block
            cut = int(sizes[:k].sum())
            head = groups[:cut]
            blk = np.repeat((b0 + np.arange(k, dtype=np.int64)) // q, sizes[:k])
            if self._block:             # the open block is block 0
                held = np.fromiter(self._block, np.int64, len(self._block))
                head = np.concatenate([held, head])
                blk = np.concatenate([np.zeros(held.size, np.int64), blk])
            span = int(head.max()) + 1 if head.size else 1
            _check_block_key_capacity(last, span, "BlockUnionTracker.extend")
            key = np.unique(blk * span + head)
            widths = np.bincount(key // span, minlength=last)
            self._filled += int(widths.sum())
            self._max_width = max(self._max_width, int(widths.max()))
            self._block = set()
            groups = groups[cut:]
        self._block.update(groups.tolist())
        self._n += m

    @property
    def pending(self) -> int:
        """Queries added since the last reset."""
        return self._n

    @property
    def fill(self) -> int:
        """Σ block-union widths of the pending stream (tile DMA count)."""
        return self._filled + len(self._block)

    def grid_cells(self) -> int:
        """Kernel grid cells of a flush-now (nb × sublane-padded width)."""
        if self._n == 0:
            return 0
        nb = -(-self._n // self.q_block)
        width = max(self._max_width, len(self._block))
        return nb * _padded_width(width, None, "pending block")


def fused_group_loads(
    cq: CompiledQueries, tile_group: np.ndarray, num_groups: int
) -> np.ndarray:
    """Per-fused-group active-row counts of a compiled batch.

    The serve-time observation feeding drift tracking (DESIGN.md §6):
    instead of re-walking the ragged host queries, the load is read off
    the batch that was compiled for the kernel anyway.  Each valid
    (query, tile) slot contributes its wordline popcount to the tile's
    group, so a query touching *k* rows of a group counts *k* — the same
    per-row semantics as ``CoOccurrenceGraph.freq`` aggregated by
    ``Grouping.group_freq``, which is what the shard plan's
    ``group_load`` was built from.  Replica choice does not matter: all
    replicas of a group map to the same group id.

    Args:
      cq: a compiled batch in the *fused* tile space (post
        :func:`offset_compiled_queries` / :func:`concat_compiled_queries`).
      tile_group: ``(num_tiles,)`` fused tile id → fused group id
        (``repeat(arange(G), group_copies)``).
      num_groups: fused group count G.

    Returns:
      ``(G,)`` float64 active-row counts.
    """
    ids = np.asarray(cq.tile_ids)
    valid = ids >= 0
    if not valid.any():
        return np.zeros(num_groups, dtype=np.float64)
    groups = np.asarray(tile_group)[ids[valid].astype(np.int64)]
    rows = np.asarray(cq.bitmaps)[valid].sum(axis=-1)
    return np.bincount(
        groups, weights=rows.astype(np.float64), minlength=num_groups
    ).astype(np.float64)


def offset_compiled_queries(cq: CompiledQueries, tile_offset: int) -> CompiledQueries:
    """Rebases a per-table compile into the fused multi-table tile space."""
    ids = np.asarray(cq.tile_ids)
    return CompiledQueries(
        tile_ids=jnp.asarray(np.where(ids >= 0, ids + tile_offset, ids)),
        bitmaps=cq.bitmaps,
        max_tiles=cq.max_tiles,
    )


def concat_compiled_queries(
    cqs: Sequence[CompiledQueries], q_block: int
) -> tuple[CompiledQueries, list[tuple[int, int]]]:
    """Stacks per-table compiled batches for one fused kernel invocation.

    Each table's batch is padded up to a ``q_block`` multiple (so blocks
    never span tables) and all are padded to a common tile width, then
    concatenated on the query axis.

    Returns:
      (fused CompiledQueries, per-table ``(row_start, batch)`` spans into
      the fused — and therefore into the kernel output — row space).
    """
    if q_block < 1:
        raise ValueError("q_block must be >= 1")
    if not cqs:
        raise ValueError("need at least one compiled batch")
    width = max(cq.max_tiles for cq in cqs)
    ids_parts, bms_parts, spans = [], [], []
    row = 0
    for cq in cqs:
        ids = np.asarray(cq.tile_ids)
        bms = np.asarray(cq.bitmaps)
        batch, s_flat = ids.shape
        rows = -(-batch // q_block) * q_block if batch else 0
        tile_rows = bms.shape[-1]
        pid = np.full((rows, width), -1, dtype=ids.dtype)
        pbm = np.zeros((rows, width, tile_rows), dtype=bms.dtype)
        pid[:batch, :s_flat] = ids
        pbm[:batch, :s_flat] = bms
        ids_parts.append(pid)
        bms_parts.append(pbm)
        spans.append((row, batch))
        row += rows
    fused = CompiledQueries(
        tile_ids=jnp.asarray(np.concatenate(ids_parts)),
        bitmaps=jnp.asarray(np.concatenate(bms_parts)),
        max_tiles=width,
    )
    return fused, spans


def reduce_dense_oracle(
    table: jax.Array, queries: Sequence[Sequence[int]]
) -> jax.Array:
    """Ground-truth gather+sum on the logical table (host-ragged input)."""
    out = []
    for q in queries:
        ids = jnp.asarray(sorted(set(int(i) for i in q)), dtype=jnp.int32)
        out.append(table[ids].sum(axis=0) if len(q) else jnp.zeros(table.shape[-1], table.dtype))
    return jnp.stack(out)


@partial(jax.jit, static_argnames=("tile_rows", "dynamic_switch"))
def reduce_via_layout(
    image: jax.Array,      # (num_tiles * tile_rows, dim) physical image
    tile_ids: jax.Array,   # (batch, max_tiles)
    bitmaps: jax.Array,    # (batch, max_tiles, tile_rows)
    *,
    tile_rows: int,
    dynamic_switch: bool = True,
) -> jax.Array:
    """Pure-jnp tiled one-hot MAC through the physical image.

    Per (query, slot): fetch the tile, then either
      * READ path  (popcount==1): select the single active row, or
      * MAC path: ``bitmap @ tile`` (one-hot MXU matmul).
    Padding slots (tile_id == -1) have all-zero bitmaps and contribute 0.
    """
    num_tiles = image.shape[0] // tile_rows
    dim = image.shape[-1]
    tiles3 = image.reshape(num_tiles, tile_rows, dim)

    def per_query(tids, bms):
        def per_slot(tid, bm):
            tile = tiles3[jnp.clip(tid, 0, num_tiles - 1)]  # (tile_rows, dim)
            mac = bm @ tile  # (dim,)
            if dynamic_switch:
                count = bm.sum()
                # READ path: arg-select the active row without a matmul.
                row = jnp.argmax(bm)
                read = tile[row] * (count > 0)
                out = jnp.where(count <= 1, read, mac)
            else:
                out = mac
            return out * (tid >= 0)

        return jax.vmap(per_slot)(tids, bms).sum(axis=0)

    return jax.vmap(per_query)(tile_ids, bitmaps)


def reduction_flops(bitmaps: np.ndarray, dim: int, dynamic_switch: bool) -> int:
    """FLOPs of the layout reduction (for benchmark reporting)."""
    counts = np.asarray(bitmaps).sum(axis=-1)
    tiles_active = counts > 0
    if dynamic_switch:
        mac_tiles = counts > 1
    else:
        mac_tiles = tiles_active
    tile_rows = np.asarray(bitmaps).shape[-1]
    # MAC tile: 2*tile_rows*dim; READ tile: dim (copy, counted as 0 FLOP)
    return int(mac_tiles.sum()) * 2 * tile_rows * dim
