"""Sharded multi-table crossbar reduction over the ``model`` mesh axis.

The serving-scale entry point (DESIGN.md §4): each model shard holds its
slice of the fused multi-table crossbar image (``repro.dist.shard_plan``)
and runs the query-blocked Pallas kernel over its *own* tile schedule
(``repro.core.reduction.shard_block_queries``); the per-shard partial
sums are combined with a psum-scatter-style reduction.

Combine / DMA overlap: the block axis is split into ``combine_chunks``
contiguous chunks, each lowered as kernel-then-combine.  Chunk *c*'s
reduce-scatter has no data dependence on chunk *c+1*'s pallas_call, whose
grid is ``("parallel", "arbitrary")``, so XLA's async collectives overlap
chunk *c*'s ICI transfer with chunk *c+1*'s HBM→VMEM tile DMAs — the TPU
re-expression of "overlap the cross-shard combine with the next block's
tile fetches".

Two execution paths, numerically identical:

  * **emulation** (``mesh=None``) — a host loop over the shard axis with
    an f32 partial-sum accumulator; runs on a single device of any
    backend (tests, CPU benchmarks).
  * **shard_map** (``mesh=`` a mesh whose ``axis_name`` axis has size
    ``num_shards``) — each device runs its shard's kernel; partials
    combine with ``lax.psum_scatter`` over the embedding dim (payload is
    OUTPUT-sized, never table-sized) + ``all_gather``, or plain
    ``lax.psum`` when the dim does not divide.

Both paths dispatch through ``functools.lru_cache``-keyed ``jax.jit``
wrappers (DESIGN.md §7.2): the serving loop re-invokes one flush shape
over and over, so repeat flushes skip retracing and — crucially for the
async engine — a dispatch returns immediately with the computation
executing asynchronously, which is what the double-buffered
host-compile / device-execute overlap overlaps with.  Every wrapped
function is named ``recross_flush`` and every Pallas call
``recross_crossbar_reduce``, so a device trace shows the flush program
and its kernel by name.

This is inference-path machinery: no custom VJP (training through the
sharded image goes through the single-shard ``crossbar_reduce`` entries).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.kernels.crossbar_reduce import crossbar_reduce_pallas


# Bound on each jit-dispatch cache below.  The caches are keyed on the
# participants tuple (plus static knobs), and an adversarial mix of
# owner-set flush shapes can mint a fresh participants tuple per flush —
# unbounded caches would pin every retraced executable forever.  64
# distinct keys per path comfortably covers every steady-state policy
# (global: 1; per-shard: S; owner-set: S + the small sets that survive
# ``owner_set_max`` pooling) while evicting the long tail LRU-style.
DISPATCH_CACHE_MAXSIZE = 64


@functools.lru_cache(maxsize=DISPATCH_CACHE_MAXSIZE)
def _emulated_fn(shards, chunks, dynamic_switch, interpret):
    """jit-cached single-device emulation of the sharded reduction.

    Keyed by the participating shard ids + static knobs; jax.jit's own
    cache handles shapes.  Caching matters twice: repeat flushes of one
    shape skip retracing (the serving loop's per-flush host cost), and
    a jitted dispatch returns immediately with the computation running
    ASYNCHRONOUSLY — without it the §7 engine's host-compile /
    device-execute overlap would have nothing to overlap with off-TPU.
    """

    def recross_flush(images, tile_ids, bitmaps):
        nb, q_block = bitmaps.shape[1], bitmaps.shape[3]
        dim = images.shape[-1]
        bounds = _chunk_bounds(nb, chunks)
        out = jnp.zeros((nb * q_block, dim), jnp.float32)
        for p, s in enumerate(shards):
            parts = [
                crossbar_reduce_pallas(
                    images[s], tile_ids[p][c0:c1], bitmaps[p][c0:c1],
                    dynamic_switch=dynamic_switch, interpret=interpret,
                ).astype(jnp.float32)
                for c0, c1 in bounds
            ]
            out = out + jnp.concatenate(parts, axis=0)
        return out.astype(images.dtype)

    return jax.jit(recross_flush)


@functools.lru_cache(maxsize=DISPATCH_CACHE_MAXSIZE)
def _mesh_fn(mesh, axis_name, chunks, dynamic_switch, interpret, scatter):
    """jit-cached shard_map reduction (full-axis combine)."""

    def recross_flush(img, ids, bms):
        img, ids, bms = img[0], ids[0], bms[0]
        bounds = _chunk_bounds(ids.shape[0], chunks)
        outs = []
        for c0, c1 in bounds:
            part = crossbar_reduce_pallas(
                img, ids[c0:c1], bms[c0:c1],
                dynamic_switch=dynamic_switch, interpret=interpret,
            ).astype(jnp.float32)
            # chunk c's combine is independent of chunk c+1's kernel →
            # XLA overlaps this collective with the next chunk's DMAs
            if scatter:
                part = lax.psum_scatter(
                    part, axis_name, scatter_dimension=1, tiled=True
                )
            else:
                part = lax.psum(part, axis_name)
            outs.append(part)
        out = jnp.concatenate(outs, axis=0)
        if scatter:
            out = lax.all_gather(out, axis_name, axis=1, tiled=True)
        return out[None]

    return jax.jit(jax.shard_map(
        recross_flush,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name)),
        out_specs=P(axis_name),
        # pallas_call has no varying-axes rule; replication is
        # re-established explicitly by the psum/all_gather combine
        check_vma=False,
    ))


@functools.lru_cache(maxsize=DISPATCH_CACHE_MAXSIZE)
def _mesh_subset_fn(mesh, axis_name, chunks, dynamic_switch, interpret,
                    groups):
    """jit-cached shard_map reduction combining only a participant
    subgroup (DESIGN.md §7.1): ``groups`` partitions the mesh axis into
    EQUAL-SIZED index groups — the participants as one group, the
    non-participants chunked to the same size (TPU lowering rejects
    unequal ``axis_index_groups``, so this fn is only dispatched when
    the participant count divides the mesh) — and the per-chunk
    ``lax.psum`` rings each subgroup independently: a 2-owner flush on
    an 8-shard mesh moves combine traffic over 2 shards, while the
    non-participants (whose schedules are empty) all-reduce zeros
    among themselves.  psum (not psum_scatter) because a scatter's
    per-shard slice width would depend on the subgroup size, and the
    payload is output-sized either way."""

    index_groups = [list(g) for g in groups]

    def recross_flush(img, ids, bms):
        img, ids, bms = img[0], ids[0], bms[0]
        bounds = _chunk_bounds(ids.shape[0], chunks)
        outs = []
        for c0, c1 in bounds:
            part = crossbar_reduce_pallas(
                img, ids[c0:c1], bms[c0:c1],
                dynamic_switch=dynamic_switch, interpret=interpret,
            ).astype(jnp.float32)
            outs.append(lax.psum(
                part, axis_name, axis_index_groups=index_groups
            ))
        return jnp.concatenate(outs, axis=0)[None]

    return jax.jit(jax.shard_map(
        recross_flush,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name)),
        out_specs=P(axis_name),
        check_vma=False,
    ))


@functools.lru_cache(maxsize=DISPATCH_CACHE_MAXSIZE)
def _mesh_single_fn(mesh, axis_name, chunks, dynamic_switch, interpret):
    """jit-cached shard_map reduction with NO combine — the
    single-participant flush path (the participant's stacked output is
    the result; non-participants run empty masked grids)."""

    def recross_flush(img, ids, bms):
        img, ids, bms = img[0], ids[0], bms[0]
        bounds = _chunk_bounds(ids.shape[0], chunks)
        parts = [
            crossbar_reduce_pallas(
                img, ids[c0:c1], bms[c0:c1],
                dynamic_switch=dynamic_switch, interpret=interpret,
            ).astype(jnp.float32)
            for c0, c1 in bounds
        ]
        return jnp.concatenate(parts, axis=0)[None]

    return jax.jit(jax.shard_map(
        recross_flush,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name)),
        out_specs=P(axis_name),
        check_vma=False,
    ))


_DISPATCH_CACHES = {
    "emulated": _emulated_fn,
    "mesh": _mesh_fn,
    "mesh_subset": _mesh_subset_fn,
    "mesh_single": _mesh_single_fn,
}


def dispatch_cache_stats() -> dict:
    """Hit/miss/size counters of the bounded jit-dispatch caches.

    Process-global (the caches are module-level, shared by every server
    in the process); surfaced by ``ShardedEmbeddingServer.report()``.  A
    "hit" is a flush that reused a cached dispatcher — jax.jit's own
    shape cache then decides whether the *executable* was also reused.
    """
    out = {}
    hits = misses = 0
    for name, fn in _DISPATCH_CACHES.items():
        info = fn.cache_info()
        out[name] = {
            "hits": info.hits, "misses": info.misses,
            "currsize": info.currsize, "maxsize": info.maxsize,
        }
        hits += info.hits
        misses += info.misses
    out["total"] = {"hits": hits, "misses": misses,
                    "maxsize": DISPATCH_CACHE_MAXSIZE}
    return out


def clear_dispatch_caches() -> None:
    """Drops every cached dispatcher (tests that count hits exactly)."""
    for fn in _DISPATCH_CACHES.values():
        fn.cache_clear()


def _chunk_bounds(nb: int, combine_chunks: int) -> list[tuple[int, int]]:
    """Contiguous, roughly equal block-axis chunks (static)."""
    chunks = max(1, min(combine_chunks, nb)) if nb else 1
    if nb == 0:
        return [(0, 0)]
    base, rem = divmod(nb, chunks)
    bounds, start = [], 0
    for c in range(chunks):
        end = start + base + (1 if c < rem else 0)
        bounds.append((start, end))
        start = end
    return bounds


def crossbar_reduce_sharded(
    images: jax.Array,    # (S, local_tiles, tile_rows, dim) stacked shard images
    tile_ids: jax.Array,  # (P, nb, max_tiles) int32 shard-local ids, -1 pad
    bitmaps: jax.Array,   # (P, nb, max_tiles, q_block, tile_rows)
    *,
    mesh=None,
    axis_name: str = "model",
    combine: str = "psum_scatter",
    combine_chunks: int = 1,
    dynamic_switch: bool = True,
    interpret: bool | None = None,
    shard_ids=None,       # (P,) global shard ids of the stacked schedules
) -> jax.Array:
    """Shard-local query-blocked reduction + cross-shard combine.

    Args:
      images: per-shard local images from ``ShardPlan.build_shard_images``
        (trailing padding tiles zero).  Always the full ``S``-deep stack,
        even for a subset dispatch.
      tile_ids / bitmaps: stacked shard-local blocked batch from
        ``shard_block_queries`` (every shard shares the block axis).
      mesh: run under shard_map on this mesh's ``axis_name`` axis (size
        must equal the shard count); ``None`` emulates on one device.
      combine: "psum_scatter" (reduce-scatter over the embedding dim +
        all-gather; falls back to psum when dim % shards != 0) or "psum".
      combine_chunks: block-axis chunks for combine/DMA overlap.
      shard_ids: when the batch was compiled for a shard *subset*
        (``participants=`` — the scheduler's per-shard and owner-set
        flushes, DESIGN.md §7), the global shard id of each stacked
        schedule.  Emulation runs only the participating shards'
        kernels; under shard_map the subset schedules scatter into a
        full-``S`` stack of empty (all ``-1``) schedules and the
        combine shrinks with the subset: a single participant skips the
        collective entirely, a multi-shard subset whose size divides
        the mesh rings only its participants via grouped psum
        (``axis_index_groups`` — equal group sizes are a TPU lowering
        requirement), and any other subset (plus the full stack) runs
        the full-axis combine with exact-zero payloads from
        non-participants.  ``None`` = all shards.

    Returns:
      ``(nb * q_block, dim)`` summed reduction in block-major query
      order — the same contract as ``crossbar_reduce_blocked``.
    """
    fn, args, take = _select_dispatch(
        images, tile_ids, bitmaps, mesh=mesh, axis_name=axis_name,
        combine=combine, combine_chunks=combine_chunks,
        dynamic_switch=dynamic_switch, interpret=interpret,
        shard_ids=shard_ids,
    )
    out = fn(*args)
    return out if take is None else out[take].astype(images.dtype)


def lower_sharded(images, tile_ids, bitmaps, **kw):
    """Lowers, without running, the program :func:`crossbar_reduce_sharded`
    would dispatch for these arguments (same keywords).  Its text shows
    whether the kernel is a compiled Mosaic call (``tpu_custom_call``)
    or runs in interpret mode."""
    fn, args, _ = _select_dispatch(images, tile_ids, bitmaps, **kw)
    return fn.lower(*args)


def _select_dispatch(
    images, tile_ids, bitmaps, *, mesh=None, axis_name="model",
    combine="psum_scatter", combine_chunks=1, dynamic_switch=True,
    interpret=None, shard_ids=None,
):
    """Validates a sharded reduction and picks its cached jit program.

    Returns ``(fn, args, take)``: the program, its arguments (subset
    schedules already scattered into the full mesh stack) and the
    stacked row of its output that holds the result (``None`` when the
    output is the result, as on the emulation path).
    """
    S, _, _, dim = images.shape
    if shard_ids is None:
        if tile_ids.shape[0] != S or bitmaps.shape[0] != S:
            raise ValueError(
                f"shard axes disagree: images {images.shape[0]}, "
                f"tile_ids {tile_ids.shape[0]}, bitmaps {bitmaps.shape[0]}"
            )
        part = np.arange(S, dtype=np.int64)
    else:
        part = np.asarray(shard_ids, dtype=np.int64)
        if tile_ids.shape[0] != part.size or bitmaps.shape[0] != part.size:
            raise ValueError(
                f"shard_ids has {part.size} entries, schedules have "
                f"{tile_ids.shape[0]}/{bitmaps.shape[0]}"
            )
        if part.size and (part.min() < 0 or part.max() >= S):
            raise ValueError(f"shard_ids {part} out of range for {S} shards")
    if combine not in ("psum_scatter", "psum"):
        raise ValueError(f"unknown combine {combine!r}")

    if mesh is None:
        # single-device emulation: shard loop in-program, f32 accumulate.
        # A subset flush runs ONLY the participants' kernels — that is
        # the per-shard scheduler's compute saving on the emulation path.
        fn = _emulated_fn(
            tuple(part.tolist()), combine_chunks, dynamic_switch, interpret
        )
        return fn, (images, tile_ids, bitmaps), None

    mesh_axis = dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis_name)
    if mesh_axis != S:
        raise ValueError(
            f"mesh axis {axis_name!r} has size {mesh_axis}, need {S} shards"
        )
    if part.size != S or not np.array_equal(part, np.arange(S)):
        # shard_map needs one schedule per device: scatter the subset
        # into empty (-1 / zero) schedules — empty grids are masked
        # in-kernel, so non-participants produce exact-zero partials.
        # Device-side functional scatter: no host round-trip of the
        # just-built schedules on the per-shard flush hot path.
        idx = jnp.asarray(part, dtype=jnp.int32)
        tile_ids = jnp.full(
            (S,) + tuple(tile_ids.shape[1:]), -1, dtype=jnp.int32
        ).at[idx].set(tile_ids)
        bitmaps = jnp.zeros(
            (S,) + tuple(bitmaps.shape[1:]), dtype=bitmaps.dtype
        ).at[idx].set(bitmaps)

    if part.size == 1:
        # single-participant flush: the participant's partial IS the
        # result, so no collective runs at all — a per-shard flush
        # crosses zero interconnect on the mesh path too.
        fn = _mesh_single_fn(
            mesh, axis_name, combine_chunks, dynamic_switch, interpret
        )
        return fn, (images, tile_ids, bitmaps), int(part[0])

    P = int(part.size)
    if P < S and S % P == 0:
        # multi-shard subset (owner-set / pool flush) whose size divides
        # the mesh: combine only among the participants via grouped psum
        # — interconnect scales with the owner-set size, not the mesh.
        # axis_index_groups must partition the axis into EQUAL sizes
        # (a TPU lowering requirement), so the non-participants are
        # chunked to the participant count and ring zeros among
        # themselves.  Subsets that do not divide the mesh fall through
        # to the full-axis combine below — non-participants contribute
        # exact-zero partials there, so numerics are identical and only
        # the ring width differs (the stats account the same rule).
        others = np.setdiff1d(np.arange(S), part)
        groups = (tuple(int(s) for s in np.sort(part)),) + tuple(
            tuple(int(s) for s in others[i : i + P])
            for i in range(0, others.size, P)
        )
        fn = _mesh_subset_fn(
            mesh, axis_name, combine_chunks, dynamic_switch, interpret,
            groups,
        )
        return fn, (images, tile_ids, bitmaps), int(part[0])

    scatter = combine == "psum_scatter" and dim % S == 0
    fn = _mesh_fn(
        mesh, axis_name, combine_chunks, dynamic_switch, interpret, scatter
    )
    # every shard returns the full combined batch; take shard 0's copy
    return fn, (images, tile_ids, bitmaps), 0


def crossbar_reduce_tables(
    images: jax.Array,
    sbq,
    spans,
    *,
    mesh=None,
    axis_name: str = "model",
    combine: str = "psum_scatter",
    combine_chunks: int = 1,
    dynamic_switch: bool = True,
    interpret: bool | None = None,
) -> list[jax.Array]:
    """Multi-table entry: one fused sharded reduction, split per table.

    ``sbq`` is the fused :class:`~repro.core.reduction.
    ShardedBlockedQueries` (per-table compiles offset into the fused tile
    space, concatenated with ``concat_compiled_queries``), ``spans`` the
    per-table ``(row_start, batch)`` list that call returned.  A subset
    compile (``sbq.shards`` set) dispatches only the participating
    shards' kernels — the scheduler's independent per-shard flush path.

    Returns one ``(batch_t, dim)`` array per table, padding rows sliced.
    """
    out = crossbar_reduce_sharded(
        images, sbq.tile_ids, sbq.bitmaps,
        mesh=mesh, axis_name=axis_name, combine=combine,
        combine_chunks=combine_chunks, dynamic_switch=dynamic_switch,
        interpret=interpret, shard_ids=sbq.shards,
    )
    return [out[start : start + batch] for start, batch in spans]


def patch_shard_images(
    images: jax.Array,     # (S, capacity, tile_rows, dim) stacked shard images
    patch,                 # repro.dist.replan.PlanPatch (duck-typed)
    fused_image: np.ndarray,  # (num_tiles, tile_rows, dim) host master copy
) -> jax.Array:
    """DMAs ONLY a plan patch's moved tiles into the stacked shard images.

    The device-side half of online replanning (DESIGN.md §6): the host
    master image is the DMA source, and the update is one batched
    scatter of ``len(patch.dma)`` tiles — never a rebuild of the
    ``(S, capacity, tile_rows, dim)`` stack.  Slots freed by demotions
    keep their stale bytes; the plan stops addressing them, so they are
    unreachable (the padding-tile contract only ever covered slots the
    plan could address).

    When promotions outgrow the current capacity the stack is padded
    with zero tiles up to ``patch.new_capacity`` first — an allocation,
    but still no table-sized data movement (the pad is zeros and only
    the moved tiles are copied in).  A patch computed with slack
    age-out (``compute_plan_patch(..., shrink_slack=)``) may instead
    carry ``new_capacity`` *below* the current depth: the stack is
    sliced down, releasing the free tail long demotion streaks left
    behind — every slot the patched plan addresses stays below the new
    depth by construction.

    Tiered storage (DESIGN.md §9) rides the same scatter: a paging
    patch's ``fetch_dma`` triples copy the paged-in groups' tiles from
    the host master image into the slots its evictions (and earlier
    demotions) returned to the free-list.  Evicted slots themselves move
    no data — like demotion-freed slots they just stop being addressed,
    and the host master image stays authoritative for the cold tier.

    Args:
      images: the serving image stack (``ShardPlan.build_shard_images``
        output, possibly already patched and/or slack-padded).
      patch: the :class:`~repro.dist.replan.PlanPatch` being applied;
        only ``dma``, ``fetch_dma``, ``moved`` and ``new_capacity`` are
        read (``fetch_dma`` via getattr — pre-paging patches lack it).
      fused_image: the fused multi-table host image the plan indexes
        (``repro.dist.build_fused_image``).

    Returns:
      The patched image stack (a new array — jax functional update).
    """
    S, capacity = images.shape[0], images.shape[1]
    if patch.new_capacity > capacity:
        pad = jnp.zeros(
            (S, patch.new_capacity - capacity) + images.shape[2:], images.dtype
        )
        images = jnp.concatenate([images, pad], axis=1)
    elif patch.new_capacity < capacity:
        # slack age-out (DESIGN.md §6.2): every slot the patched plan
        # addresses is below the new depth (compaction relocated the
        # rest), so the slice drops only unaddressable bytes
        images = images[:, : patch.new_capacity]
    # promotions' new holders + paged-in tiles + compaction relocations,
    # one batched scatter from the host master image
    writes = list(patch.dma)
    writes += list(getattr(patch, "fetch_dma", ()) or ())
    writes += [(s, new, t) for s, t, _old, new in patch.moved]
    if not writes:
        return images
    shards = jnp.asarray([w[0] for w in writes], dtype=jnp.int32)
    slots = jnp.asarray([w[1] for w in writes], dtype=jnp.int32)
    tiles = np.asarray([w[2] for w in writes], dtype=np.int64)
    moved = jnp.asarray(np.asarray(fused_image)[tiles], dtype=images.dtype)
    return images.at[shards, slots].set(moved)


def combine_bytes_per_batch(
    out_rows: int, dim: int, num_shards: int, *, dtype_bytes: int = 4,
) -> int:
    """Cross-shard combine traffic of one batch, summed over shards.

    Ring accounting: a reduce-scatter (or all-gather) of an ``R × dim``
    f32 payload moves ``(S-1)/S × R × dim × 4`` bytes per shard; both
    combine modes cost two such passes (psum_scatter + all_gather, or a
    ring all-reduce), so the accounting is mode-independent.  Payloads
    are OUTPUT-sized — the whole point of combining partial sums instead
    of gathering tiles.
    """
    if num_shards <= 1:
        return 0
    per_shard = (num_shards - 1) / num_shards * out_rows * dim * dtype_bytes
    passes = 2  # reduce-scatter + all-gather, or all-reduce
    return int(passes * per_shard * num_shards)
