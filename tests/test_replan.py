"""Online replanning (DESIGN.md §6): an incrementally patched plan must
serve BIT-IDENTICAL outputs to a from-scratch ``plan_shards`` rebuild on
the drifted frequencies, while moving only the promoted groups' tiles.

Bit-identity is pinned on integer-valued float tables (every partial sum
exact in f32), so what the tests reject is a wrong, dropped or
double-counted activation after a patch — the failure modes of a broken
ownership edit.  The protocol invariants come straight from DESIGN.md
§6: the patched replicated set equals the fresh Eq.-1 set, the patch
DMAs exactly ``Σ_promoted copies·(S-1)`` tiles (demotions DMA nothing),
and a no-drift serving window stages zero patches.
"""

import numpy as np
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import (
    build_cooccurrence,
    build_layout,
    compile_queries,
    correlation_aware_grouping,
    fused_group_loads,
    plan_replication,
    shard_block_queries,
)
from repro.core.reduction import reduce_dense_oracle
from repro.data import zipf_queries
from repro.dist import (
    apply_plan_patch,
    build_fused_image,
    compute_plan_patch,
    plan_shards,
)
from repro.kernels import crossbar_reduce_sharded, patch_shard_images
from repro.serve.drift import DriftTracker, ReplanConfig

EQ1_BATCH = 64


def _int_table(rows, dim, seed):
    """Integer-valued f32 table: partial sums are exact in float32."""
    return np.random.default_rng(seed).integers(
        -8, 9, size=(rows, dim)
    ).astype(np.float32)


def _pipeline(rows, hist, *, group_size=16, dim=128):
    g = build_cooccurrence(hist, rows)
    grouping = correlation_aware_grouping(g, group_size)
    plan = plan_replication(grouping, g.freq, EQ1_BATCH)
    layout = build_layout(grouping, plan, dim)
    return layout, plan, grouping.group_freq(g.freq)


def _assert_valid_partition(sp):
    """Every tile owned by exactly one shard or resident on all of them."""
    S = sp.num_shards
    for t in range(sp.num_tiles):
        holders = int((sp.local_tile_of[:, t] >= 0).sum())
        if sp.shard_of_tile[t] < 0:
            assert holders == S, (t, holders)
        else:
            assert holders == 1, (t, holders)
            assert sp.local_tile_of[sp.shard_of_tile[t], t] >= 0
    for s in range(S):
        slots = sp.local_tile_of[s][sp.local_tile_of[s] >= 0]
        assert len(set(slots.tolist())) == slots.size, "slot collision"
        assert int((sp.local_tile_of[s] >= 0).sum()) == sp.local_num_tiles[s]


# --------------------------------------------------- patch ≡ rebuild --


@given(st.integers(0, 200), st.sampled_from([1, 2, 4]))
@settings(max_examples=6, deadline=None)
def test_patched_plan_serves_bit_identical_to_fresh_rebuild(seed, num_shards):
    rows, dim = 192, 128
    hist = zipf_queries(rows, 48, 6.0, seed=seed)
    layout, plan, gfreq = _pipeline(rows, hist, dim=dim)
    table = _int_table(rows, dim, seed)
    fused = build_fused_image([layout], [table])
    sp = plan_shards([layout], [plan], num_shards, group_freqs=[gfreq])
    images = jnp.asarray(sp.build_shard_images(fused))

    # drift: the hot set rotates onto formerly-cold groups (reversed
    # hotness is the worst case for a stale plan)
    dload = sp.group_load[::-1].copy()
    patch = compute_plan_patch(
        sp, dload, eq1_batch=EQ1_BATCH, capacity=int(images.shape[1])
    )
    sp_patched = apply_plan_patch(sp, patch)
    images_patched = patch_shard_images(images, patch, fused)
    _assert_valid_partition(sp_patched)

    fresh = plan_shards(
        [layout], [plan], num_shards, group_freqs=[dload], eq1_batch=EQ1_BATCH
    )
    # patched replication classes == what Eq. 1 on the drifted load says
    np.testing.assert_array_equal(sp_patched.replicated_group,
                                  fresh.replicated_group)
    # the patch DMAs exactly the promoted groups' tiles, never the image
    want_dma = sum(
        int(sp.group_copies[g]) * (num_shards - 1) for g in patch.promoted
    )
    assert patch.num_moved_tiles == want_dma
    assert patch.num_moved_tiles < int(fresh.local_num_tiles.sum())

    ev = zipf_queries(rows, 10 + seed % 7, 6.0, seed=seed + 1)
    cq = compile_queries(layout, ev, replica_block=4)
    images_fresh = jnp.asarray(fresh.build_shard_images(fused))
    sbq_p = shard_block_queries(cq, sp_patched, 4)
    sbq_f = shard_block_queries(cq, fresh, 4)
    out_p = np.asarray(crossbar_reduce_sharded(
        images_patched, sbq_p.tile_ids, sbq_p.bitmaps, combine_chunks=2
    ))[: sbq_p.batch]
    out_f = np.asarray(crossbar_reduce_sharded(
        images_fresh, sbq_f.tile_ids, sbq_f.bitmaps, combine_chunks=2
    ))[: sbq_f.batch]
    np.testing.assert_array_equal(out_p, out_f)
    oracle = np.asarray(reduce_dense_oracle(jnp.asarray(table), ev))
    np.testing.assert_array_equal(out_p, oracle)


def test_repeated_patches_stay_consistent():
    """Patch → drift again → patch: slot reuse, growth and re-promotion
    of a previously-demoted group must keep the partition valid and the
    numerics exact."""
    rows, dim, S = 192, 128, 2
    hist = zipf_queries(rows, 48, 6.0, seed=3)
    layout, plan, gfreq = _pipeline(rows, hist, dim=dim)
    table = _int_table(rows, dim, 3)
    fused = build_fused_image([layout], [table])
    sp = plan_shards([layout], [plan], S, group_freqs=[gfreq])
    images = jnp.asarray(sp.build_shard_images(fused))
    ev = zipf_queries(rows, 9, 6.0, seed=4)
    cq = compile_queries(layout, ev, replica_block=4)
    oracle = np.asarray(reduce_dense_oracle(jnp.asarray(table), ev))

    loads = [sp.group_load[::-1].copy(),
             np.roll(sp.group_load, sp.num_groups // 3),
             sp.group_load.copy()]          # back to the original hotness
    for dload in loads:
        patch = compute_plan_patch(
            sp, dload, eq1_batch=EQ1_BATCH, capacity=int(images.shape[1])
        )
        sp = apply_plan_patch(sp, patch)
        images = patch_shard_images(images, patch, fused)
        _assert_valid_partition(sp)
        sbq = shard_block_queries(cq, sp, 4)
        out = np.asarray(crossbar_reduce_sharded(
            images, sbq.tile_ids, sbq.bitmaps
        ))[: sbq.batch]
        np.testing.assert_array_equal(out, oracle)


def test_patch_demotion_moves_no_tiles():
    """A drift that only cools groups (promotes nothing) must DMA zero
    tiles: every shard already holds a replicated group's tiles."""
    rows = 192
    hist = zipf_queries(rows, 48, 6.0, seed=5)
    layout, plan, gfreq = _pipeline(rows, hist)
    sp = plan_shards([layout], [plan], 2, group_freqs=[gfreq])
    if not sp.replicated_group.any():
        return  # nothing replicated at this seed; vacuous
    flat = np.full(sp.num_groups, 1.0)  # uniform: Eq. 1 replicates nothing
    patch = compute_plan_patch(sp, flat, eq1_batch=EQ1_BATCH)
    assert len(patch.promoted) == 0
    assert len(patch.demoted) == int(sp.replicated_group.sum())
    assert patch.num_moved_tiles == 0
    _assert_valid_partition(apply_plan_patch(sp, patch))


def test_rescaled_load_restores_scale_sensitive_promotions():
    """Eq. 1 is not scale-invariant: a decayed serve-time estimate
    (orders below training mass) must be rescaled to the training total
    or hot-set rotations under-promote.  The rescaled tiny observation
    must produce the same replication classes as the full-scale load."""
    from repro.dist import rescale_load_to_plan

    rows = 192
    hist = zipf_queries(rows, 48, 6.0, seed=13)
    layout, plan, gfreq = _pipeline(rows, hist)
    sp = plan_shards([layout], [plan], 2, group_freqs=[gfreq])
    dload_full = sp.group_load[::-1].copy()
    dload_tiny = dload_full / 512.0        # tracker-magnitude estimate
    patch_full = compute_plan_patch(sp, dload_full, eq1_batch=EQ1_BATCH)
    rescaled = rescale_load_to_plan(
        dload_tiny, sp, [sp.group_load.sum()]
    )
    np.testing.assert_allclose(rescaled, dload_full)
    patch_rescaled = compute_plan_patch(sp, rescaled, eq1_batch=EQ1_BATCH)
    assert patch_rescaled.promoted == patch_full.promoted
    assert patch_rescaled.demoted == patch_full.demoted
    # the raw tiny load under-promotes whenever anything is promotable
    patch_raw = compute_plan_patch(sp, dload_tiny, eq1_batch=EQ1_BATCH)
    assert len(patch_raw.promoted) <= len(patch_full.promoted)


def test_build_shard_images_scatters_to_holey_slots():
    """Rebuilding images from a patched plan (checkpoint/restart path)
    must scatter tiles to their allocated local slots, not compact them
    to 0..n-1 — a demote-only patch leaves holes in the numbering."""
    rows, dim, S = 192, 128, 2
    hist = zipf_queries(rows, 48, 6.0, seed=7)
    layout, plan, gfreq = _pipeline(rows, hist, dim=dim)
    table = _int_table(rows, dim, 7)
    fused = build_fused_image([layout], [table])
    sp = plan_shards([layout], [plan], S, group_freqs=[gfreq])
    if not sp.replicated_group.any():
        return  # vacuous at this seed
    flat = np.full(sp.num_groups, 1.0)  # demotes everything replicated
    patch = compute_plan_patch(sp, flat, eq1_batch=EQ1_BATCH)
    sp2 = apply_plan_patch(sp, patch)
    assert any(
        (sp2.local_tile_of[s][sp2.local_tile_of[s] >= 0].max(initial=-1) + 1)
        > sp2.local_num_tiles[s]
        for s in range(S)
    ), "patch left no holes; test needs a demotion"
    rebuilt = sp2.build_shard_images(fused)
    for s in range(S):
        for t in np.nonzero(sp2.local_tile_of[s] >= 0)[0]:
            np.testing.assert_array_equal(
                rebuilt[s, sp2.local_tile_of[s, t]], fused[t]
            )
    # and serving through the rebuilt stack stays exact
    ev = zipf_queries(rows, 9, 6.0, seed=8)
    cq = compile_queries(layout, ev, replica_block=4)
    sbq = shard_block_queries(cq, sp2, 4)
    out = np.asarray(crossbar_reduce_sharded(
        jnp.asarray(rebuilt), sbq.tile_ids, sbq.bitmaps
    ))[: sbq.batch]
    oracle = np.asarray(reduce_dense_oracle(jnp.asarray(table), ev))
    np.testing.assert_array_equal(out, oracle)


def test_noop_patch_rebases_load_only():
    sp_rows = 192
    hist = zipf_queries(sp_rows, 48, 6.0, seed=9)
    layout, plan, gfreq = _pipeline(sp_rows, hist)
    sp = plan_shards([layout], [plan], 2, group_freqs=[gfreq])
    wobble = sp.group_load * 1.5  # same ordering → same Eq.-1 classes
    patch = compute_plan_patch(sp, wobble, eq1_batch=EQ1_BATCH)
    assert patch.is_noop() and patch.num_moved_tiles == 0
    sp2 = apply_plan_patch(sp, patch)
    np.testing.assert_array_equal(sp2.shard_of_tile, sp.shard_of_tile)
    np.testing.assert_array_equal(sp2.local_tile_of, sp.local_tile_of)
    np.testing.assert_array_equal(sp2.group_load, wobble)


# --------------------------------------- demotion target: tile pressure --


def test_cold_demotion_lands_on_least_tile_loaded_shard():
    """A demoted group has usually COOLED to ~zero load, where frequency
    balance says nothing — the owner choice must fall back to per-shard
    tile pressure (cold-tail memory balance), the fresh planner's rule.
    Scenario: shard 0 has few hot tiles but high load, shard 1 one hot
    tile with all the load; the frequency-only rule would dump the cold
    group on the least-loaded shard regardless of its tile count."""
    from repro.dist.shard_plan import ShardPlan, TableSegment

    # g0 replicated (1 tile), g1 (2 tiles, load 1)→s0, g2 (1 tile,
    # load 1)→s0, g3 (1 tile, load 20)→s1: s0 = 3 tiles / load 2,
    # s1 = 1 tile / load 20.
    copies = np.array([1, 2, 1, 1], dtype=np.int64)
    local = np.array([
        [0, 1, 2, 3, -1],
        [0, -1, -1, -1, 1],
    ], dtype=np.int32)
    sp = ShardPlan(
        num_shards=2,
        tables=[TableSegment("t0", 0, 0, 4, 5, 16)],
        replicated_group=np.array([True, False, False, False]),
        shard_of_group=np.array([-1, 0, 0, 1], dtype=np.int32),
        shard_of_tile=np.array([-1, 0, 0, 0, 1], dtype=np.int32),
        local_tile_of=local,
        local_num_tiles=np.array([4, 2], dtype=np.int64),
        group_load=np.array([30.0, 1.0, 1.0, 20.0]),
        group_copies=copies,
    )
    # g0 cools to zero; eq1_batch=2 keeps every other class unchanged
    dload = np.array([0.0, 1.0, 1.0, 20.0])
    patch = compute_plan_patch(sp, dload, eq1_batch=2)
    assert patch.promoted == []
    # least-load would be shard 0 (2 < 20); least-tile is shard 1 (1 < 3)
    assert patch.demoted == [(0, 1)], patch.demoted
    _assert_valid_partition(apply_plan_patch(sp, patch))


def test_loaded_demotion_still_balances_by_frequency():
    """A demoted group that kept real load places on the least-LOADED
    shard (tile pressure only breaks ties) — same rule as plan_shards."""
    from repro.dist.shard_plan import ShardPlan, TableSegment

    copies = np.array([1, 2, 1, 1], dtype=np.int64)
    local = np.array([
        [0, 1, 2, 3, -1],
        [0, -1, -1, -1, 1],
    ], dtype=np.int32)
    sp = ShardPlan(
        num_shards=2,
        tables=[TableSegment("t0", 0, 0, 4, 5, 16)],
        replicated_group=np.array([True, False, False, False]),
        shard_of_group=np.array([-1, 0, 0, 1], dtype=np.int32),
        shard_of_tile=np.array([-1, 0, 0, 0, 1], dtype=np.int32),
        local_tile_of=local,
        local_num_tiles=np.array([4, 2], dtype=np.int64),
        group_load=np.array([30.0, 1.0, 1.0, 20.0]),
        group_copies=copies,
    )
    # g0 keeps real load (5.0) but drops out of the Eq.-1 replicated
    # set: owner = least-loaded shard 0 (load 2 < 20), tiles be damned
    dload = np.array([5.0, 1.0, 1.0, 20.0])
    patch = compute_plan_patch(sp, dload, eq1_batch=2)
    assert patch.demoted == [(0, 0)], patch.demoted


# ----------------------------------------------- slack capacity age-out --


def test_shrink_slack_ages_out_free_capacity():
    """After demotions, shrink_slack compacts the stack down to the
    busiest shard's resident count + headroom: tiles above the new
    depth relocate into freed holes, patch_shard_images slices, and
    serving stays exact through the shrunk stack."""
    rows, dim, S = 192, 128, 2
    hist = zipf_queries(rows, 48, 6.0, seed=3)
    layout, plan, gfreq = _pipeline(rows, hist, dim=dim)
    table = _int_table(rows, dim, 3)
    fused = build_fused_image([layout], [table])
    sp = plan_shards([layout], [plan], S, group_freqs=[gfreq])
    if not sp.replicated_group.any():
        return  # vacuous at this seed
    slack = 8
    images = jnp.asarray(sp.build_shard_images(fused))
    pad = jnp.zeros((S, slack) + images.shape[2:], images.dtype)
    images = jnp.concatenate([images, pad], axis=1)
    capacity = int(images.shape[1])

    flat = np.full(sp.num_groups, 1.0)  # demotes everything replicated
    # without shrink: capacity sticks at the high-water mark
    keep = compute_plan_patch(sp, flat, eq1_batch=EQ1_BATCH, capacity=capacity)
    assert keep.new_capacity == capacity
    assert keep.num_relocated_tiles == 0
    # with shrink: the stack compacts to working set + headroom
    patch = compute_plan_patch(
        sp, flat, eq1_batch=EQ1_BATCH, capacity=capacity, shrink_slack=2
    )
    sp2 = apply_plan_patch(sp, patch)
    assert patch.new_capacity < capacity
    assert patch.new_capacity == int(sp2.local_num_tiles.max()) + 2
    assert sp2.max_local_tiles <= patch.new_capacity
    images2 = patch_shard_images(images, patch, fused)
    assert images2.shape[1] == patch.new_capacity
    _assert_valid_partition(sp2)

    ev = zipf_queries(rows, 9, 6.0, seed=4)
    cq = compile_queries(layout, ev, replica_block=4)
    sbq = shard_block_queries(cq, sp2, 4)
    out = np.asarray(crossbar_reduce_sharded(
        images2, sbq.tile_ids, sbq.bitmaps
    ))[: sbq.batch]
    oracle = np.asarray(reduce_dense_oracle(jnp.asarray(table), ev))
    np.testing.assert_array_equal(out, oracle)


def test_rebase_with_relocations_is_not_noop():
    """A class-unchanged drift computed WITH shrink_slack may still
    relocate resident tiles (compaction).  Such a patch must NOT be
    treated as a load rebase — applying the plan without the image
    update would read zeros from the tiles' new slots."""
    rows, dim, S = 192, 128, 2
    hist = zipf_queries(rows, 48, 6.0, seed=3)
    layout, plan, gfreq = _pipeline(rows, hist, dim=dim)
    table = _int_table(rows, dim, 3)
    fused = build_fused_image([layout], [table])
    sp = plan_shards([layout], [plan], S, group_freqs=[gfreq])
    if not sp.replicated_group.any():
        return  # vacuous at this seed
    images = jnp.asarray(sp.build_shard_images(fused))
    # demote-all first: leaves holes below top-slot residents
    flat = np.full(sp.num_groups, 1.0)
    p1 = compute_plan_patch(sp, flat, eq1_batch=EQ1_BATCH,
                            capacity=int(images.shape[1]))
    sp = apply_plan_patch(sp, p1)
    images = patch_shard_images(images, p1, fused)
    # class-unchanged wobble + shrink: compaction relocates tiles
    p2 = compute_plan_patch(
        sp, flat * 1.5, eq1_batch=EQ1_BATCH,
        capacity=int(images.shape[1]), shrink_slack=0,
    )
    assert not p2.promoted and not p2.demoted
    if not p2.moved:
        return  # nothing above the compaction target; vacuous
    assert not p2.is_noop(), "relocation-carrying patch treated as rebase"
    sp2 = apply_plan_patch(sp, p2)
    images2 = patch_shard_images(images, p2, fused)
    _assert_valid_partition(sp2)
    ev = zipf_queries(rows, 9, 6.0, seed=4)
    cq = compile_queries(layout, ev, replica_block=4)
    sbq = shard_block_queries(cq, sp2, 4)
    out = np.asarray(crossbar_reduce_sharded(
        images2, sbq.tile_ids, sbq.bitmaps
    ))[: sbq.batch]
    oracle = np.asarray(reduce_dense_oracle(jnp.asarray(table), ev))
    np.testing.assert_array_equal(out, oracle)


def test_server_shrink_streak_reclaims_image_capacity():
    """The serving driver's demotion-streak trigger: once the streak
    reaches shrink_streak, the next demotion-only patch also compacts
    the image stack back to working set + slack, and slack_slots
    reports the residual headroom."""
    from repro.serve import ShardedEmbeddingServer

    # 320 rows / 20 groups: uniform traffic gives every group too small
    # a share for Eq. 1 to promote (log f/log f_total · log B < 1), so
    # the drift patch is demotion-only and the streak machinery engages
    rows, dim = 320, 128
    tables = {"a": _int_table(rows, dim, 21)}
    histories = {"a": zipf_queries(rows, 64, 5.0, seed=22)}
    server = ShardedEmbeddingServer(
        tables, histories, num_shards=2, q_block=4, group_size=16,
        batch_size=8,
        replan=ReplanConfig(threshold=0.2, half_life=2.0, min_queries=8,
                            slack_tiles=4, shrink_streak=1),
    )
    if not server.plan.replicated_group.any():
        return  # nothing to demote; vacuous
    cap_before = int(server.shard_images.shape[1])
    server._demote_streak = 1  # as if a demotion-only patch already landed
    rng = np.random.default_rng(99)
    stream = [rng.choice(rows, size=24, replace=False).tolist()
              for _ in range(48)]
    results = []
    for chunk in range(0, len(stream), 8):
        out = server.serve({"a": stream[chunk : chunk + 8]})
        results.append(np.asarray(out["a"]))
    assert server.stats.replans >= 1, server.stats
    assert server.stats.promoted_groups == 0, server.stats
    cap_after = int(server.shard_images.shape[1])
    assert cap_after < cap_before, (cap_before, cap_after)
    rep = server.report()
    assert rep["replan"]["slack_slots"] <= server.replan_cfg.slack_tiles
    # serving through the shrunk stack stays exact
    got = np.concatenate(results)
    want = np.asarray(reduce_dense_oracle(jnp.asarray(tables["a"]), stream))
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------ drift tracker --


def test_drift_tracker_statistic():
    base = np.array([8.0, 4.0, 2.0, 1.0])
    tr = DriftTracker(base, half_life=1.0, min_queries=4)
    assert not tr.ready
    assert tr.drift_from(base) == 0.0
    # identical-distribution observations keep drift at zero
    tr.observe(base * 2, num_queries=4)
    assert tr.ready
    assert abs(tr.drift_from(base)) < 1e-12
    # rotate all mass to the cold tail: drift climbs toward TV distance 1
    for _ in range(12):
        tr.observe(np.array([0.0, 0.0, 0.0, 30.0]), num_queries=4)
    assert tr.drift_from(base) > 0.7
    # zero-mass reference yields no signal
    assert tr.drift_from(np.zeros(4)) == 0.0


def test_fused_group_loads_matches_row_semantics():
    rows = 160
    hist = zipf_queries(rows, 40, 5.0, seed=11)
    layout, plan, gfreq = _pipeline(rows, hist)
    sp = plan_shards([layout], [plan], 2, group_freqs=[gfreq])
    ev = zipf_queries(rows, 12, 5.0, seed=12)
    cq = compile_queries(layout, ev, replica_block=4)
    tile_group = np.repeat(np.arange(sp.num_groups), sp.group_copies)
    got = fused_group_loads(cq, tile_group, sp.num_groups)
    want = np.zeros(sp.num_groups)
    for q in ev:
        rows_u = np.unique(np.asarray(q, dtype=np.int64))
        np.add.at(want, layout.group_of[rows_u], 1.0)
    np.testing.assert_array_equal(got, want)


# ----------------------------------------------------- serving driver --


def _drifting_server(threshold=0.2, **kw):
    from repro.serve import ShardedEmbeddingServer

    rows, dim = 128, 128
    tables = {"a": _int_table(rows, dim, 21)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=22)}
    server = ShardedEmbeddingServer(
        tables, histories, num_shards=2, q_block=4, group_size=16,
        batch_size=8,
        replan=ReplanConfig(threshold=threshold, half_life=1.0,
                            min_queries=8, slack_tiles=4),
        **kw,
    )
    return server, tables, rows


def test_server_replans_under_drift_and_stays_exact():
    server, tables, rows = _drifting_server()
    stream = zipf_queries(rows, 40, 5.0, seed=23)
    perm = np.random.default_rng(24).permutation(rows)
    stream = stream[:16] + [perm[np.asarray(q, np.int64)] for q in stream[16:]]
    results = []
    for q in stream:
        out = server.submit("a", q)
        if out:
            results.append(out["a"])
    tail = server.flush()
    if tail:
        results.append(tail["a"])
    rep = server.report()
    assert rep["serve"]["replans"] + rep["serve"]["rebases"] >= 1, rep["serve"]
    # every flush's outputs — across plan swaps — match the dense oracle
    got = np.concatenate([np.asarray(r) for r in results])
    want = np.asarray(reduce_dense_oracle(jnp.asarray(tables["a"]), stream))
    np.testing.assert_array_equal(got, want)
    # a patch never rewrites the image: DMA'd tiles stay below residency
    assert rep["serve"]["patched_tiles"] < rep["plan"]["stored_tiles"] * max(
        rep["serve"]["replans"], 1
    )


def test_server_no_drift_window_applies_zero_patches():
    """Serving the training distribution itself must never patch."""
    server, tables, rows = _drifting_server(threshold=0.25)
    # replay the history the plan was built from — zero distribution shift
    for q in server_history(server):
        server.submit("a", q)
    server.flush()
    rep = server.report()
    assert rep["serve"]["replans"] == 0
    assert rep["serve"]["patched_tiles"] == 0
    assert rep["replan"]["staged"] is None
    assert rep["replan"]["drift"] < 0.25


def server_history(server):
    # the exact trace the offline pipeline saw (seed 22 above)
    return zipf_queries(128, 48, 5.0, seed=22)


def test_idle_table_registers_no_drift():
    """Multi-table: a table that simply receives no traffic must not
    register as standing drift (its segment's decayed estimate is a
    scaled copy of its reference) — only its own distribution moving
    counts.  Guards against every-flush false rebases."""
    from repro.serve import ShardedEmbeddingServer

    rows, dim = 128, 128
    tables = {"a": _int_table(rows, dim, 31), "b": _int_table(rows, dim, 32)}
    histories = {
        "a": zipf_queries(rows, 48, 5.0, seed=33),
        "b": zipf_queries(rows, 48, 5.0, seed=34),
    }
    server = ShardedEmbeddingServer(
        tables, histories, num_shards=2, q_block=4, group_size=16,
        batch_size=8,
        replan=ReplanConfig(threshold=0.2, half_life=1.0, min_queries=8),
    )
    # replay table a's own training history; table b stays idle
    for q in histories["a"][:32]:
        server.submit("a", q)
    server.flush()
    rep = server.report()
    assert rep["serve"]["replans"] == 0, rep["serve"]
    assert rep["serve"]["rebases"] == 0, rep["serve"]
    assert rep["replan"]["drift"] < 0.2, rep["replan"]


def test_server_report_exposes_replan_state():
    server, _, rows = _drifting_server()
    rep = server.report()
    assert rep["replan"]["drift"] == 0.0
    assert rep["replan"]["ready"] is False
    assert rep["replan"]["staged"] is None
    server.serve({"a": zipf_queries(rows, 4, 5.0, seed=30)})
    assert server.report()["replan"]["observed_queries"] == 4


def test_shard_map_branch_serves_patched_plan_subprocess():
    """The REAL shard_map path must serve a patched plan + patched image
    bit-identically to the emulation path and the fresh rebuild.  Device
    forcing must precede jax init → subprocess with 2 host devices."""
    import os
    import subprocess
    import sys

    script = r"""
import numpy as np
import jax, jax.numpy as jnp
assert len(jax.devices()) >= 2, jax.devices()
import sys
sys.path.insert(0, {src!r})
from repro.core import (build_cooccurrence, build_layout, compile_queries,
                        correlation_aware_grouping, plan_replication,
                        shard_block_queries)
from repro.data import zipf_queries
from repro.dist import (apply_plan_patch, build_fused_image,
                        compute_plan_patch, plan_shards)
from repro.kernels import crossbar_reduce_sharded, patch_shard_images

rows, dim, S = 96, 128, 2
hist = zipf_queries(rows, 32, 5.0, seed=1)
ev = zipf_queries(rows, 9, 5.0, seed=2)
g = build_cooccurrence(hist, rows)
grouping = correlation_aware_grouping(g, 16)
plan = plan_replication(grouping, g.freq, 32)
layout = build_layout(grouping, plan, dim)
gfreq = grouping.group_freq(g.freq)
table = np.random.default_rng(3).integers(-8, 9, size=(rows, dim)).astype(np.float32)
fused = build_fused_image([layout], [table])
sp = plan_shards([layout], [plan], S, group_freqs=[gfreq])
dload = sp.group_load[::-1].copy()
patch = compute_plan_patch(sp, dload, eq1_batch=32)
sp2 = apply_plan_patch(sp, patch)
images2 = patch_shard_images(jnp.asarray(sp.build_shard_images(fused)), patch, fused)
fresh = plan_shards([layout], [plan], S, group_freqs=[dload], eq1_batch=32)
images_f = jnp.asarray(fresh.build_shard_images(fused))
cq = compile_queries(layout, ev, replica_block=4)
sbq2 = shard_block_queries(cq, sp2, 4)
sbqf = shard_block_queries(cq, fresh, 4)
emu = np.asarray(crossbar_reduce_sharded(images2, sbq2.tile_ids, sbq2.bitmaps,
                                         combine_chunks=2))
mesh = jax.make_mesh((1, S), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
for combine in ("psum_scatter", "psum"):
    sm = np.asarray(crossbar_reduce_sharded(
        images2, sbq2.tile_ids, sbq2.bitmaps, mesh=mesh,
        combine=combine, combine_chunks=2))
    np.testing.assert_array_equal(sm, emu)
smf = np.asarray(crossbar_reduce_sharded(
    images_f, sbqf.tile_ids, sbqf.bitmaps, mesh=mesh, combine_chunks=2))
np.testing.assert_array_equal(smf, emu)
print("REPLAN_SHARD_MAP_PARITY_OK")
""".format(src=os.path.join(os.path.dirname(__file__), "..", "src"))

    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=2 "
        + env.get("XLA_FLAGS", "")
    )
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, env=env, timeout=480,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "REPLAN_SHARD_MAP_PARITY_OK" in proc.stdout
