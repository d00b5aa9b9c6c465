"""Tiered host↔device embedding storage (DESIGN.md §9).

The contract: a capacity-bounded server is BIT-IDENTICAL to the
uncapped all-resident oracle — the hot tier changes *where* a reduction
computes (crossbar kernels vs host gather+sum), never *what* it
computes.  Bit-identity is pinned on integer-valued float tables (every
partial sum exact in f32), so the tests reject a wrong, dropped or
double-counted activation at the tier boundary — the failure modes of
a broken residency split or paging patch.

Also pinned here: the capacity-bounded planner's budget/admission
invariants, hysteresis anti-thrash, the paging patch's free-list
bookkeeping through ``patch_shard_images`` edge cases (zero-moved-tile
and evict-only patches, fetch failure under fault injection), the
scheduler's cold-query guard, the drift-observation memo and the
bounded jit-dispatch caches.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    build_cooccurrence,
    build_layout,
    compile_activations,
    compile_queries,
    correlation_aware_grouping,
    fuse_activations,
    plan_replication,
    shard_block_queries,
)
from repro.core.reduction import reduce_dense_oracle
from repro.data import zipf_queries
from repro.dist import (
    COLD,
    PagingPolicy,
    apply_plan_patch,
    build_fused_image,
    compute_plan_patch,
    plan_shards,
)
from repro.dist.replan import PlanPatch
from repro.kernels import crossbar_reduce_sharded, patch_shard_images
from repro.kernels.sharded import (
    DISPATCH_CACHE_MAXSIZE,
    clear_dispatch_caches,
    dispatch_cache_stats,
)
from repro.serve import (
    FlushPolicy,
    FlushScheduler,
    LoadObservationCache,
    ReplanConfig,
    RetryPolicy,
    ShardedEmbeddingServer,
    TierConfig,
)
from repro.serve.faults import FaultPlan
from repro.serve.tiers import HostFetchQueue, ResidencyIndex

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


def _capped_setup(seed, *, rows=192, dim=128, S=2, cap_frac=0.5):
    hist = zipf_queries(rows, 48, 6.0, seed=seed)
    layout, plan, gfreq = _pipeline(rows, hist, dim=dim)
    table = _int_table(rows, dim, seed)
    fused = build_fused_image([layout], [table])
    uncapped = plan_shards([layout], [plan], S, group_freqs=[gfreq])
    cap = max(1, int(uncapped.max_local_tiles * cap_frac))
    sp = plan_shards([layout], [plan], S, group_freqs=[gfreq],
                     capacity_tiles=cap)
    return layout, table, fused, uncapped, sp, cap


def _servers(seed, tiers, **kw):
    """(oracle, capped) server pair over the same tables/stream knobs."""
    rows, dim = kw.pop("rows", 320), kw.pop("dim", 128)
    rng = np.random.default_rng(seed)
    tables = {"a": _int_table(rows, dim, seed),
              "b": _int_table(rows, dim, seed + 1)}
    histories = {n: zipf_queries(rows, 64, 5.0, seed=seed + i)
                 for i, n in enumerate(tables)}
    mk = lambda t: ShardedEmbeddingServer(
        tables, histories, num_shards=2, q_block=4, group_size=16,
        batch_size=16, tiers=t, **kw,
    )
    return mk(None), mk(tiers), tables, rng


# --------------------------------------------- capacity-bounded plan --


def test_capacity_plan_respects_budget_and_keeps_hottest():
    layout, _table, _fused, uncapped, sp, cap = _capped_setup(3)
    assert sp.capacity_tiles == cap
    # budget respected on every shard
    assert int(sp.local_num_tiles.max()) <= cap
    assert sp.max_local_tiles <= cap
    # something had to go cold at half capacity
    assert sp.cold_tiles > 0 and sp.cold_groups.size > 0
    # cold groups' tiles are held by NO shard; resident tiles behave
    # exactly as before (owned once or replicated everywhere)
    for t in range(sp.num_tiles):
        holders = int((sp.local_tile_of[:, t] >= 0).sum())
        if sp.shard_of_tile[t] == COLD:
            assert holders == 0, (t, holders)
        elif sp.shard_of_tile[t] == -1:
            assert holders == sp.num_shards
        else:
            assert holders == 1
    # greedy admission is hottest-first: every cold group's load is <=
    # the minimum load over resident SHARDED groups of the same table
    # (replicated groups may degrade to sharded, so compare like kinds)
    res_sharded = (sp.shard_of_group >= 0) & ~sp.replicated_group
    if res_sharded.any():
        assert sp.group_load[sp.cold_groups].max() <= (
            sp.group_load[res_sharded].max()
        )
    summary = sp.memory_summary()
    assert summary["cold_tiles"] == sp.cold_tiles
    assert summary["capacity_tiles"] == cap
    assert 0.0 < summary["resident_tile_fraction"] < 1.0


def test_huge_capacity_matches_uncapped_plan():
    # a budget the working set never touches must not change placement
    hist = zipf_queries(192, 48, 6.0, seed=5)
    layout2, plan2, gfreq2 = _pipeline(192, hist)
    a = plan_shards([layout2], [plan2], 2, group_freqs=[gfreq2])
    b = plan_shards([layout2], [plan2], 2, group_freqs=[gfreq2],
                    capacity_tiles=10_000)
    np.testing.assert_array_equal(a.shard_of_group, b.shard_of_group)
    np.testing.assert_array_equal(a.replicated_group, b.replicated_group)
    np.testing.assert_array_equal(a.local_tile_of, b.local_tile_of)
    assert b.cold_tiles == 0


def test_tier_config_validation():
    with pytest.raises(ValueError):
        TierConfig()                                  # neither knob
    with pytest.raises(ValueError):
        TierConfig(capacity_tiles=8, capacity_frac=0.5)   # both
    with pytest.raises(ValueError):
        TierConfig(capacity_frac=1.5)
    with pytest.raises(ValueError):
        TierConfig(capacity_tiles=8, hysteresis=0.9)
    tc = TierConfig(capacity_frac=0.25)
    assert tc.resolve_capacity(40) == 10
    assert tc.resolve_capacity(2) == 1                # floor >= 1
    assert TierConfig(capacity_tiles=7).resolve_capacity(40) == 7
    pol = tc.paging_policy(10)
    assert isinstance(pol, PagingPolicy) and pol.capacity_tiles == 10


# ------------------------------------------- capped ≡ oracle serving --


@pytest.mark.parametrize("policy,threaded", [
    ("global", False), ("deadline", False), ("owner-set", True),
])
def test_capped_server_bit_identical_to_uncapped_oracle(policy, threaded):
    oracle, capped, tables, rng = _servers(
        11, TierConfig(capacity_frac=0.5),
        flush_policy=policy, threaded=threaded,
    )
    assert capped.plan.cold_groups.size > 0, "cap did not bite; resize test"
    rows = tables["a"].shape[0]
    stream = [("a" if i % 2 else "b",
               rng.integers(0, rows, size=rng.integers(1, 6)).tolist())
              for i in range(180)]
    if policy == "global":
        by = {"a": [q for n, q in stream if n == "a"],
              "b": [q for n, q in stream if n == "b"]}
        got = capped.serve(by)
        want = oracle.serve(by)
        for n in by:
            np.testing.assert_array_equal(
                np.asarray(got[n]), np.asarray(want[n]))
    else:
        for n, q in stream:
            capped.submit(n, q)
            oracle.submit(n, q)
        got, want = capped.drain(), oracle.drain()
        capped.close(), oracle.close()
        assert set(got) == set(want)
        for n in got:
            np.testing.assert_array_equal(
                np.asarray(got[n]), np.asarray(want[n]))
    ts = capped.stats.tier_summary()
    assert ts["host_queries"] > 0, "cap never exercised the host path"
    assert ts["hot_queries"] + ts["host_queries"] == len(stream)
    assert oracle.stats.host_queries == 0


def test_paging_replay_fetches_evicts_and_stays_exact():
    """Skewed traffic onto initially-cold groups must page them in
    (fetch), displace colder residents (evict), and keep every drained
    row bit-identical to the uncapped oracle throughout."""
    oracle, capped, tables, rng = _servers(
        7, TierConfig(capacity_frac=0.5, hysteresis=1.1),
        flush_policy="deadline",
        replan=ReplanConfig(threshold=0.2, half_life=4, min_queries=32),
    )
    cold = capped.plan.cold_groups
    assert cold.size > 0
    gof = capped._residency._fused_group_of_row["a"]
    cold_rows = np.nonzero(np.isin(gof, cold))[0]
    assert cold_rows.size > 0
    rows = tables["a"].shape[0]
    got_chunks, want_chunks = [], []
    for i in range(480):
        if i % 3:
            q = rng.choice(cold_rows[:40], size=rng.integers(1, 5)).tolist()
        else:
            q = rng.integers(0, rows, size=rng.integers(1, 5)).tolist()
        capped.submit("a", q)
        oracle.submit("a", q)
        if (i + 1) % 96 == 0:
            g, w = capped.drain(), oracle.drain()
            got_chunks.append(np.asarray(g["a"]))
            want_chunks.append(np.asarray(w["a"]))
    g, w = capped.drain(), oracle.drain()
    if "a" in g:
        got_chunks.append(np.asarray(g["a"]))
        want_chunks.append(np.asarray(w["a"]))
    got = np.concatenate(got_chunks)
    want = np.concatenate(want_chunks)
    np.testing.assert_array_equal(got, want)
    ts = capped.stats.tier_summary()
    assert ts["fetched_tiles"] > 0, ts
    assert ts["evicted_tiles"] > 0, ts
    assert ts["paging_bytes"] == ts["fetched_tiles"] * capped._tile_bytes
    # budget held through every patch
    assert int(capped.plan.local_num_tiles.max()) <= capped._capacity_tiles
    assert int(capped.shard_images.shape[1]) == capped._capacity_tiles
    rep = capped.report()
    assert rep["tiers"]["capacity_tiles"] == capped._capacity_tiles
    assert rep["serve"]["tiers"]["fetched_tiles"] == ts["fetched_tiles"]


# ----------------------------------------------- hysteresis anti-thrash --


def _paging_scenario(seed=3):
    """A capped single-shard plan with zero free slots, plus maps."""
    rows = 192
    hist = zipf_queries(rows, 48, 6.0, seed=seed)
    layout, plan, gfreq = _pipeline(rows, hist)
    uncapped = plan_shards([layout], [plan], 1, group_freqs=[gfreq])
    cap = max(2, uncapped.max_local_tiles // 2)
    sp = plan_shards([layout], [plan], 1, group_freqs=[gfreq],
                     capacity_tiles=cap)
    # shave capacity down to exactly the occupied slot count so a fetch
    # MUST evict (no free slots to absorb it)
    sp = plan_shards([layout], [plan], 1, group_freqs=[gfreq],
                     capacity_tiles=int(sp.local_num_tiles[0]))
    assert int(sp.local_num_tiles[0]) == sp.capacity_tiles
    return sp


def test_hysteresis_blocks_marginal_swap_and_allows_hot_one():
    sp = _paging_scenario()
    cold = sp.cold_groups
    assert cold.size > 0
    resident = np.nonzero((sp.shard_of_group >= 0)
                          & ~sp.replicated_group)[0]
    assert resident.size >= 2
    # synthetic drifted loads with a unique, known min-load victim:
    # residents at 2.0, one victim at 1.0, replicated kept clearly hot
    # so Eq.1 churn stays out of the picture, all cold traffic zero
    # except the group under test
    victim = int(resident[0])
    singles = cold[np.asarray(sp.group_copies)[cold] == 1]
    assert singles.size > 0, "need a 1-copy cold group"
    g = int(singles[0])
    h = 2.0
    pol = PagingPolicy(capacity_tiles=sp.capacity_tiles, hysteresis=h)
    base = np.zeros(sp.num_groups, dtype=np.float64)
    base[resident] = 2.0
    base[victim] = 1.0
    base[np.asarray(sp.replicated_group)] = 50.0
    vload = 1.0

    below = base.copy()
    below[g] = 0.95 * h * vload
    p = compute_plan_patch(sp, below, eq1_batch=EQ1_BATCH, paging=pol)
    assert g not in [f[0] for f in p.fetched]
    assert victim not in p.evicted

    above = base.copy()
    above[g] = 1.5 * h * vload
    p = compute_plan_patch(sp, above, eq1_batch=EQ1_BATCH, paging=pol)
    assert g in [f[0] for f in p.fetched], p.summary()
    assert p.evicted, p.summary()
    sp2 = apply_plan_patch(sp, p)
    assert sp2.shard_of_group[g] >= 0
    assert all(sp2.shard_of_group[e] == COLD for e in p.evicted)
    # no immediate reverse swap: recomputing on the SAME loads must not
    # page the fresh evictee back in (it would need to out-load the
    # just-fetched group by the hysteresis factor — impossible)
    p2 = compute_plan_patch(sp2, above, eq1_batch=EQ1_BATCH, paging=pol)
    assert not any(f[0] in p.evicted for f in p2.fetched)
    assert g not in p2.evicted


def test_paging_patch_never_shrinks_or_grows_capacity():
    sp = _paging_scenario(seed=9)
    pol = PagingPolicy(capacity_tiles=sp.capacity_tiles, hysteresis=1.2)
    hot = sp.group_load.copy()
    if sp.cold_groups.size:
        hot[sp.cold_groups] = hot.max() * 3
    # shrink_slack is ignored under paging (fixed budget)
    p = compute_plan_patch(sp, hot, eq1_batch=EQ1_BATCH,
                           shrink_slack=0, paging=pol)
    assert p.new_capacity == sp.capacity_tiles
    assert not p.moved
    sp2 = apply_plan_patch(sp, p)
    assert int(sp2.local_num_tiles.max()) <= sp.capacity_tiles


def test_max_fetch_tiles_bounds_the_paging_dma():
    sp = _paging_scenario()
    cold = sp.cold_groups
    resident = np.nonzero((sp.shard_of_group >= 0)
                          & ~sp.replicated_group)[0]
    assert cold.size >= 2 and resident.size >= 2
    # every cold group screams, every evictable victim whispers: the
    # unbounded patch swaps as many as the free-list allows
    hot = np.zeros(sp.num_groups, dtype=np.float64)
    hot[resident] = 1.0
    hot[np.asarray(sp.replicated_group)] = 100.0
    hot[cold] = 50.0
    unbounded = compute_plan_patch(
        sp, hot, eq1_batch=EQ1_BATCH,
        paging=PagingPolicy(capacity_tiles=sp.capacity_tiles,
                            hysteresis=1.1))
    assert len(unbounded.fetch_dma) >= 2, unbounded.summary()
    bound = max(1, len(unbounded.fetch_dma) // 2)
    p = compute_plan_patch(
        sp, hot, eq1_batch=EQ1_BATCH,
        paging=PagingPolicy(capacity_tiles=sp.capacity_tiles,
                            hysteresis=1.1, max_fetch_tiles=bound))
    assert len(p.fetch_dma) <= bound < len(unbounded.fetch_dma)


# ------------------------------------- patch_shard_images edge cases --


def test_patch_images_zero_moved_tiles_is_identity():
    """An evict-only patch moves no data: the image array must come
    back byte-identical (evicted slots just stop being addressed)."""
    for seed in (13, 5, 3, 7, 11):      # need a sharded-once resident
        layout, table, fused, _unc, sp, _cap = _capped_setup(seed)
        resident = np.nonzero((sp.shard_of_group >= 0)
                              & ~sp.replicated_group)[0]
        if resident.size:
            break
    assert resident.size > 0
    images = jnp.asarray(sp.build_shard_images(fused))
    g = int(resident[np.argmin(sp.group_load[resident])])
    o = int(sp.shard_of_group[g])
    base = np.zeros(sp.num_groups, dtype=np.int64)
    np.cumsum(sp.group_copies[:-1], out=base[1:])
    tiles = range(int(base[g]), int(base[g] + sp.group_copies[g]))
    patch = PlanPatch(
        promoted=[], demoted=[], dma=[],
        freed=[(o, int(sp.local_tile_of[o, t])) for t in tiles],
        new_capacity=int(images.shape[1]),
        drifted_load=sp.group_load.copy(),
        evicted=[g], evicted_tiles=int(sp.group_copies[g]),
    )
    assert not patch.is_noop()          # residency changed, image didn't
    images2 = patch_shard_images(images, patch, fused)
    np.testing.assert_array_equal(np.asarray(images2), np.asarray(images))
    sp2 = apply_plan_patch(sp, patch)
    assert sp2.shard_of_group[g] == COLD
    assert sp2.cold_tiles == sp.cold_tiles + int(sp.group_copies[g])
    assert int(sp2.local_num_tiles[o]) == int(sp.local_num_tiles[o]) - len(
        list(tiles))
    # serving queries that avoid the evicted group stays exact
    rows = table.shape[0]
    gof = np.asarray(layout.group_of, dtype=np.int64)
    ok_rows = np.nonzero(~np.isin(gof, np.asarray(sp2.cold_groups)))[0]
    ev = [np.random.default_rng(i).choice(ok_rows, size=5).tolist()
          for i in range(8)]
    cq = compile_queries(layout, ev, replica_block=4)
    sbq = shard_block_queries(cq, sp2, 4)
    out = np.asarray(crossbar_reduce_sharded(
        images2, sbq.tile_ids, sbq.bitmaps))[: sbq.batch]
    want = np.asarray(reduce_dense_oracle(jnp.asarray(table), ev))
    np.testing.assert_array_equal(out, want)


def test_fetch_dma_scatters_from_master_image():
    """A paging patch's fetch_dma writes must land the master image's
    bytes in the fetched slots (and nothing else may change)."""
    layout, table, fused, _unc, sp, cap = _capped_setup(17)
    assert sp.cold_groups.size > 0
    # give the hot tier free headroom so fetches land in empty slots
    # (the victimless page-in path; eviction swaps are covered above)
    cap2 = cap + 4
    images = jnp.asarray(sp.build_shard_images(fused))
    pad = jnp.zeros((sp.num_shards, cap2 - images.shape[1])
                    + images.shape[2:], images.dtype)
    images = jnp.concatenate([images, pad], axis=1)
    hot = sp.group_load.copy()
    hot[sp.cold_groups] = hot.max() * 3
    p = compute_plan_patch(
        sp, hot, eq1_batch=EQ1_BATCH,
        paging=PagingPolicy(capacity_tiles=cap2, hysteresis=1.1))
    assert p.fetch_dma, p.summary()
    images2 = patch_shard_images(images, p, fused)
    touched = set()
    for s, slot, t in list(p.dma) + list(p.fetch_dma):
        np.testing.assert_array_equal(
            np.asarray(images2[s, slot]), fused[t])
        touched.add((s, slot))
    for s in range(sp.num_shards):
        for slot in range(images.shape[1]):
            if (s, slot) not in touched:
                np.testing.assert_array_equal(
                    np.asarray(images2[s, slot]), np.asarray(images[s, slot]))


def test_fetch_failure_degrades_to_host_path_and_drain_survives():
    """An injected patch-apply fault (the paging DMA seam) must leave
    the group cold — its queries keep taking the host path — and the
    drain still returns every row, bit-identical."""
    faults = FaultPlan([], seed=10).add("patch", times=100)
    oracle, capped, tables, rng = _servers(
        19, TierConfig(capacity_frac=0.5, hysteresis=1.1),
        flush_policy="deadline",
        replan=ReplanConfig(threshold=0.2, half_life=4, min_queries=32),
    )
    # rebuild capped WITH the fault plan (same everything else)
    capped2 = ShardedEmbeddingServer(
        {n: t for n, t in tables.items()},
        {n: zipf_queries(t.shape[0], 64, 5.0, seed=19 + i)
         for i, (n, t) in enumerate(tables.items())},
        num_shards=2, q_block=4, group_size=16, batch_size=16,
        tiers=TierConfig(capacity_frac=0.5, hysteresis=1.1),
        flush_policy="deadline",
        replan=ReplanConfig(threshold=0.2, half_life=4, min_queries=32),
        retry=RetryPolicy(patch_retries=1, backoff_base=0.0, jitter=0.0),
        faults=faults,
    )
    cold = capped2.plan.cold_groups
    gof = capped2._residency._fused_group_of_row["a"]
    cold_rows = np.nonzero(np.isin(gof, cold))[0]
    rows = tables["a"].shape[0]
    got, want = [], []
    for i in range(300):
        if i % 3:
            q = rng.choice(cold_rows[:40], size=rng.integers(1, 5)).tolist()
        else:
            q = rng.integers(0, rows, size=rng.integers(1, 5)).tolist()
        capped2.submit("a", q)
        oracle.submit("a", q)
        if (i + 1) % 100 == 0:
            g, w = capped2.drain(), oracle.drain()
            got.append(np.asarray(g["a"]))
            want.append(np.asarray(w["a"]))
    np.testing.assert_array_equal(np.concatenate(got), np.concatenate(want))
    # every patch apply failed: nothing ever paged in, the ledger shows
    # the failures, and the hot tier never changed shape
    assert capped2.stats.ledger.patch_failures > 0
    assert capped2.stats.fetched_tiles == 0
    assert np.array_equal(capped2.plan.cold_groups, cold)


# ------------------------------------------------- routing + scheduler --


def test_scheduler_raises_on_cold_query():
    layout, _table, _fused, _unc, sp, _cap = _capped_setup(23)
    assert sp.cold_groups.size > 0
    sched = FlushScheduler(
        sp, [layout], ["t0"], 4,
        FlushPolicy.parse("per-shard", batch_size=8))
    gof = np.asarray(layout.group_of, dtype=np.int64)
    cold_rows = np.nonzero(np.isin(gof, np.asarray(sp.cold_groups)))[0]
    assert cold_rows.size > 0
    with pytest.raises(ValueError, match="cold"):
        sched.push("t0", 0, cold_rows[:3].tolist())
    hot_rows = np.nonzero(~np.isin(gof, np.asarray(sp.cold_groups)))[0]
    sched.push("t0", 0, hot_rows[:3].tolist())  # hot queries still route


def test_resident_query_survives_patch_barrier_during_routing():
    """Regression (stale-residency race): a query judged resident whose
    own routing's host flush hits a patch barrier — which evicts that
    query's group — must detour to the host path under the post-patch
    residency, not land in the scheduler and raise on the cold group."""
    oracle, capped, tables, rng = _servers(
        47, TierConfig(capacity_frac=0.5, host_batch=64, host_deadline=8),
        flush_policy="per-shard",
    )
    plan = capped.plan
    cold = plan.cold_groups
    assert cold.size > 0
    resident = np.nonzero((plan.shard_of_group >= 0)
                          & ~plan.replicated_group)[0]
    gof = capped._residency._fused_group_of_row["a"]
    in_a = resident[np.isin(resident, gof)]
    assert in_a.size > 0
    # craft the paging patch the barrier will apply: every cold group
    # screams, the victim (a resident group with rows in "a") whispers,
    # replicated groups stay clearly hot — the victim must be evicted
    victim = int(in_a[0])
    loads = np.zeros(plan.num_groups, dtype=np.float64)
    loads[resident] = 2.0
    loads[np.asarray(plan.replicated_group)] = 50.0
    loads[cold] = 50.0
    loads[victim] = 0.01
    pol = PagingPolicy(capacity_tiles=capped._capacity_tiles,
                       hysteresis=1.1)
    patch = compute_plan_patch(plan, loads, eq1_batch=EQ1_BATCH,
                               paging=pol)
    assert victim in patch.evicted, patch.summary()
    victim_rows = np.nonzero(gof == victim)[0]
    cold_rows = np.nonzero(np.isin(gof, cold))[0]
    # one queued cold query aged past its deadline + a staged patch:
    # the NEXT submission's routing fires the host flush → barrier
    q0 = cold_rows[:2].tolist()
    capped.submit("a", q0)
    oracle.submit("a", q0)
    capped._tick += 100
    capped._staged = patch
    q1 = victim_rows[:3].tolist()
    capped.submit("a", q1)      # pre-fix: ValueError('… cold …')
    oracle.submit("a", q1)
    # the barrier ran mid-routing and the in-hand query went cold
    assert capped.stats.barrier_flushes >= 1
    assert not capped._residency.is_resident(
        "a", np.asarray(q1, dtype=np.int64))
    assert capped.stats.host_queries >= 2
    got, want = capped.drain(), oracle.drain()
    np.testing.assert_array_equal(
        np.asarray(got["a"]), np.asarray(want["a"]))
    capped.close(), oracle.close()


def test_residency_index_and_host_queue():
    layout, _t, _f, _unc, sp, _cap = _capped_setup(29)
    gof = np.asarray(layout.group_of, dtype=np.int64)
    idx = ResidencyIndex(sp, {"t": gof})
    assert idx.any_cold
    cold_rows = np.nonzero(np.isin(gof, np.asarray(sp.cold_groups)))[0]
    hot_rows = np.nonzero(~np.isin(gof, np.asarray(sp.cold_groups)))[0]
    assert not idx.is_resident("t", cold_rows[:2])
    assert idx.is_resident("t", hot_rows[:2])
    # host loads count DISTINCT rows per query per group
    r = int(cold_rows[0])
    loads = idx.host_group_loads([("t", 0, np.asarray([r, r, r]))])
    assert loads.sum() == 1.0 and loads[gof[r]] == 1.0

    q = HostFetchQueue(batch=2, deadline=10)
    assert q.due(0) is None
    q.push("t", 0, np.asarray([1]), 5)
    assert q.due(5) is None
    assert q.due(15) == "deadline"      # oldest aged out
    q.push("t", 1, np.asarray([2]), 6)
    assert q.due(6) == "batch"          # batch trigger wins
    assert len(q.take()) == 2 and q.due(99) is None


def test_host_queue_deadline_forces_flush_in_hot_stream():
    """One cold query in a hot-dominated stream must still be served
    within the host deadline (ticks advance on every submission)."""
    oracle, capped, tables, rng = _servers(
        31, TierConfig(capacity_frac=0.5, host_batch=64, host_deadline=20),
        flush_policy="deadline",
    )
    cold = capped.plan.cold_groups
    gof = capped._residency._fused_group_of_row["a"]
    cold_rows = np.nonzero(np.isin(gof, cold))[0]
    hot_rows = np.nonzero(~np.isin(gof, cold))[0]
    capped.submit("a", cold_rows[:2].tolist())
    for i in range(30):
        capped.submit("a", rng.choice(hot_rows, size=3).tolist())
    assert capped.stats.host_deadline_flushes >= 1
    assert len(capped._host_queue) == 0
    capped.drain()
    capped.close()


# --------------------------------------- observation + dispatch caches --


@pytest.mark.parametrize("maxsize", [1, 4])
def test_load_observation_cache_is_content_keyed(maxsize):
    """Keyed on the flush's content: a fresh but equal batch hits, other
    queries miss, and the memo holds at most ``maxsize`` batches."""
    rows = 192
    hist = zipf_queries(rows, 48, 6.0, seed=2)
    layout, plan, gfreq = _pipeline(rows, hist)
    sp = plan_shards([layout], [plan], 2, group_freqs=[gfreq])
    tile_group = np.repeat(np.arange(sp.num_groups), sp.group_copies)

    def batch(ev):
        return fuse_activations(
            [(compile_activations(layout, ev, replica_block=4), 0)], 4)

    ev1 = zipf_queries(rows, 8, 6.0, seed=3)
    ev2 = zipf_queries(rows, 8, 6.0, seed=4)
    cache = LoadObservationCache(maxsize=maxsize)
    a = cache.loads(batch(ev1), tile_group, sp.num_groups)
    b = cache.loads(batch(ev1), tile_group, sp.num_groups)  # identical content
    c = cache.loads(batch(ev2), tile_group, sp.num_groups)  # different queries
    assert cache.hits == 1 and cache.misses == 2
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c) or ev1 == ev2
    # eviction bound holds
    for seed in range(5, 12):
        ev = zipf_queries(rows, 8, 6.0, seed=seed)
        cache.loads(batch(ev), tile_group, sp.num_groups)
    assert len(cache._memo) <= maxsize


def test_server_memoizes_repeated_flush_observation():
    """Replaying the SAME batch through the server must hit the memo."""
    rows, dim = 256, 128
    tables = {"a": _int_table(rows, dim, 41)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=42)}
    server = ShardedEmbeddingServer(
        tables, histories, num_shards=2, q_block=4, group_size=16,
        batch_size=8,
        replan=ReplanConfig(threshold=0.9, half_life=4, min_queries=10**9),
    )
    batch = [list(range(5 * i, 5 * i + 5)) for i in range(8)]
    server.serve({"a": batch})
    server.serve({"a": batch})
    server.serve({"a": batch})
    assert server.stats.load_obs_misses == 1
    assert server.stats.load_obs_hits == 2
    s = server.stats.summary()["tiers"]
    assert s["load_obs_hits"] == 2 and s["load_obs_misses"] == 1


def test_dispatch_caches_bounded_and_reported():
    clear_dispatch_caches()
    stats = dispatch_cache_stats()
    assert set(stats) >= {"emulated", "mesh", "mesh_subset",
                          "mesh_single", "total"}
    for k in ("emulated", "mesh", "mesh_subset", "mesh_single"):
        assert stats[k]["maxsize"] == DISPATCH_CACHE_MAXSIZE
        assert stats[k]["currsize"] == 0
    # two emulated dispatches with identical signatures: 1 miss + 1 hit
    layout, table, fused, _unc, sp, _cap = _capped_setup(37, cap_frac=1.0)
    images = jnp.asarray(sp.build_shard_images(fused))
    ev = zipf_queries(192, 6, 6.0, seed=38)
    cq = compile_queries(layout, ev, replica_block=4)
    sbq = shard_block_queries(cq, sp, 4)
    crossbar_reduce_sharded(images, sbq.tile_ids, sbq.bitmaps)
    crossbar_reduce_sharded(images, sbq.tile_ids, sbq.bitmaps)
    stats = dispatch_cache_stats()
    assert stats["emulated"]["misses"] >= 1
    assert stats["emulated"]["hits"] >= 1
    assert stats["total"]["hits"] >= 1
    # the server surfaces the same counters
    tables = {"a": _int_table(256, 128, 43)}
    histories = {"a": zipf_queries(256, 48, 5.0, seed=44)}
    server = ShardedEmbeddingServer(tables, histories, num_shards=2,
                                    group_size=16, batch_size=8)
    rep = server.report()
    assert "dispatch_cache" in rep
    assert rep["dispatch_cache"]["emulated"]["maxsize"] == (
        DISPATCH_CACHE_MAXSIZE)
