"""Numerical equivalence of every embedding-reduction datapath, plus
hypothesis property tests over random layouts/queries."""

import functools
import types

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import (
    baselines,
    build_cooccurrence,
    build_layout,
    compile_activations,
    compile_queries,
    concat_compiled_queries,
    correlation_aware_grouping,
    fuse_activations,
    offset_compiled_queries,
    plan_replication,
    shard_block_activations,
    shard_block_queries,
)
from repro.core.reduction import reduce_dense_oracle, reduce_via_layout
from repro.data import zipf_queries
from repro.dist import plan_shards
from repro.kernels import crossbar_reduce
from repro.kernels.crossbar_reduce import SCHEDULE_BITMAP_DTYPE


def _setup(rows, dim, n_hist, n_eval, seed, group_size=16):
    qs = zipf_queries(rows, n_hist + n_eval, 8.0, seed=seed)
    graph = build_cooccurrence(qs[:n_hist], rows)
    layout, _ = baselines.recross_pipeline(
        graph, qs[n_hist:], group_size=group_size, dim=dim
    )
    table = np.random.default_rng(seed).normal(size=(rows, dim)).astype(np.float32)
    return layout, table, qs[n_hist:]


@given(st.integers(0, 50))
@settings(max_examples=10, deadline=None)
def test_layout_reduction_equals_oracle(seed):
    """Property: for ANY layout built from ANY trace, reduction through the
    physical image equals gather+sum on the logical table."""
    rows, dim = 256, 128
    layout, table, ev = _setup(rows, dim, 32, 16, seed)
    cq = compile_queries(layout, ev)
    image = jnp.asarray(layout.build_image(table))
    out = reduce_via_layout(image, cq.tile_ids, cq.bitmaps, tile_rows=layout.tile_rows)
    ref = reduce_dense_oracle(jnp.asarray(table), ev)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)


@given(st.integers(0, 20))
@settings(max_examples=6, deadline=None)
def test_kernel_equals_oracle_random_layouts(seed):
    rows, dim = 200, 128
    layout, table, ev = _setup(rows, dim, 24, 8, seed)
    cq = compile_queries(layout, ev)
    image = jnp.asarray(
        layout.build_image(table).reshape(layout.num_tiles, layout.tile_rows, dim)
    )
    out = crossbar_reduce(image, cq.tile_ids, cq.bitmaps)
    ref = reduce_dense_oracle(jnp.asarray(table), ev)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)


def test_dynamic_switch_does_not_change_values():
    layout, table, ev = _setup(256, 128, 32, 16, 7)
    cq = compile_queries(layout, ev)
    image = jnp.asarray(layout.build_image(table))
    a = reduce_via_layout(image, cq.tile_ids, cq.bitmaps,
                          tile_rows=layout.tile_rows, dynamic_switch=True)
    b = reduce_via_layout(image, cq.tile_ids, cq.bitmaps,
                          tile_rows=layout.tile_rows, dynamic_switch=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_replicas_hold_identical_values():
    """All replica tiles of a group serve the same numerics (any replica
    choice gives the same reduction)."""
    layout, table, ev = _setup(128, 128, 64, 8, 11)
    image = jnp.asarray(layout.build_image(table))
    cq_bal = compile_queries(layout, ev, balance_replicas=True)
    cq_first = compile_queries(layout, ev, balance_replicas=False)
    a = reduce_via_layout(image, cq_bal.tile_ids, cq_bal.bitmaps,
                          tile_rows=layout.tile_rows)
    b = reduce_via_layout(image, cq_first.tile_ids, cq_first.bitmaps,
                          tile_rows=layout.tile_rows)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_empty_and_single_row_queries():
    layout, table, _ = _setup(64, 128, 16, 4, 13)
    ev = [np.array([0]), np.array([5, 5]), np.array([63])]
    cq = compile_queries(layout, ev)
    image = jnp.asarray(layout.build_image(table))
    out = reduce_via_layout(image, cq.tile_ids, cq.bitmaps, tile_rows=layout.tile_rows)
    ref = reduce_dense_oracle(jnp.asarray(table), ev)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ------------------------------------- the flush's host block compile --

Q_BLOCK = 4
# bags per table: ragged (no q_block multiple), one table of empty bags
# and one of no bags at all in the five-table flush
BATCHES = (11, 7, 6, 0, 9)


@functools.lru_cache(maxsize=None)
def _tables(num_tables):
    """``num_tables`` layouts of different row counts, with their plans."""
    out = []
    for t in range(num_tables):
        rows = 96 + 40 * t
        hist = zipf_queries(rows, 60, 3.0, seed=70 + t)
        g = build_cooccurrence(hist, rows)
        grouping = correlation_aware_grouping(g, 16)
        plan = plan_replication(grouping, g.freq, 64)
        out.append((build_layout(grouping, plan, 128), plan,
                    grouping.group_freq(g.freq)))
    return out


def _owner_of_rows(sp, t, layout):
    """Owning shard of every row of table ``t`` (-1: on every shard)."""
    tiles = sp.tables[t].tile_offset + layout.tile_base[layout.group_of]
    return np.asarray(sp.shard_of_tile)[tiles]


def _flush(num_tables, num_shards, subset):
    """A plan and one flush's bags per table, with repeated ids; a subset
    flush keeps only the ids its participants (or every shard) hold."""
    tabs = _tables(num_tables)
    sp = plan_shards([t[0] for t in tabs], [t[1] for t in tabs], num_shards,
                     group_freqs=[t[2] for t in tabs])
    parts = list(range(0, num_shards, 2)) if subset else None
    bags = []
    for t, (layout, _, _) in enumerate(tabs):
        ev = zipf_queries(layout.num_rows, BATCHES[t], 3.0, seed=80 + t)
        ev = [list(q) + list(q[:2]) for q in ev]
        if t == 2:
            ev = [[] for _ in ev]
        if parts is not None:
            keep = np.isin(_owner_of_rows(sp, t, layout), parts + [-1])
            ev = [[r for r in q if keep[r]] for q in ev]
        bags.append(ev)
    return tabs, sp, parts, bags


@pytest.mark.parametrize("subset", [False, True], ids=["full", "subset"])
@pytest.mark.parametrize("num_shards", [1, 4])
@pytest.mark.parametrize("num_tables", [1, 5])
def test_activation_schedule_equals_the_dense_chain(num_tables, num_shards, subset):
    """The flush's host-only block compiler gives the dense chain's
    schedule bit for bit: tile ids, bitmaps (as 0/1), widths, shards and
    spans."""
    tabs, sp, parts, bags = _flush(num_tables, num_shards, subset)
    offsets = [seg.tile_offset for seg in sp.tables]
    cqs = [
        offset_compiled_queries(
            compile_queries(layout, ev, replica_block=Q_BLOCK), off)
        for (layout, _, _), ev, off in zip(tabs, bags, offsets)
    ]
    fused_cq, spans = concat_compiled_queries(cqs, Q_BLOCK)
    want = shard_block_queries(fused_cq, sp, Q_BLOCK, participants=parts)
    fused = fuse_activations(
        [(compile_activations(layout, ev, replica_block=Q_BLOCK), off)
         for (layout, _, _), ev, off in zip(tabs, bags, offsets)],
        Q_BLOCK,
    )
    got = shard_block_activations(fused, sp, Q_BLOCK, participants=parts,
                                  bitmap_dtype=SCHEDULE_BITMAP_DTYPE)

    assert isinstance(got.tile_ids, np.ndarray)
    assert isinstance(got.bitmaps, np.ndarray)
    assert got.bitmaps.dtype == np.int8
    assert set(np.unique(got.bitmaps).tolist()) <= {0, 1}
    np.testing.assert_array_equal(got.tile_ids, np.asarray(want.tile_ids))
    np.testing.assert_array_equal(
        got.bitmaps, np.asarray(want.bitmaps).astype(np.int8))
    np.testing.assert_array_equal(got.shard_widths, want.shard_widths)
    np.testing.assert_array_equal(got.shard_ids, want.shard_ids)
    assert (got.shards is None) == (want.shards is None)
    assert fused.spans == spans
    assert (got.batch, got.q_block) == (want.batch, want.q_block)
    # the flush covers what it should: ragged tables, and on four shards
    # tiles held on every shard
    assert any(b % Q_BLOCK for b in BATCHES[:num_tables])
    if num_shards == 4:
        assert (np.asarray(sp.shard_of_tile)[fused.tile] == -1).any()


@pytest.mark.parametrize("form", ["dense", "sparse"])
@pytest.mark.parametrize("fault", ["cold", "non_participant"])
def test_block_compilers_refuse_cold_tiles_and_outside_owners(fault, form):
    """Both block compilers raise on a cold (host-tier) tile and on a tile
    whose owner is not among the participants."""
    tabs, sp, _, bags = _flush(1, 4, False)
    layout = tabs[0][0]
    acts = compile_activations(layout, bags[0], replica_block=Q_BLOCK)
    owners = np.asarray(sp.shard_of_tile)[acts.act_tile]
    tile = int(acts.act_tile[np.flatnonzero(owners >= 0)[0]])
    owner = int(sp.shard_of_tile[tile])
    plan, parts, match = sp, None, "non-participating"
    if fault == "cold":
        shard_of_tile = np.array(sp.shard_of_tile)
        shard_of_tile[tile] = -2
        plan = types.SimpleNamespace(
            num_shards=sp.num_shards, shard_of_tile=shard_of_tile,
            local_tile_of=sp.local_tile_of, max_local_tiles=sp.max_local_tiles,
        )
        match = "cold"
    else:
        parts = [(owner + 1) % sp.num_shards]
    with pytest.raises(ValueError, match=match):
        if form == "dense":
            cq = compile_queries(layout, bags[0], replica_block=Q_BLOCK)
            shard_block_queries(cq, plan, Q_BLOCK, participants=parts)
        else:
            shard_block_activations(fuse_activations([(acts, 0)], Q_BLOCK),
                                    plan, Q_BLOCK, participants=parts,
                                    bitmap_dtype=SCHEDULE_BITMAP_DTYPE)
