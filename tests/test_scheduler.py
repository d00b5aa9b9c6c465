"""Shard-aware async serving (DESIGN.md §7): independent per-shard
flushes with double-buffered host-compile / device-execute pipelining
must serve BIT-IDENTICAL outputs to the synchronous global path (and the
dense oracle), and a PlanPatch staged during in-flight flushes must
apply atomically at the next barrier — never mid-pipeline.

Bit-identity is pinned on integer-valued float tables (every partial sum
exact in f32), so what the tests reject is a dropped, duplicated or
mis-routed query after the engine reorders flushes — the failure modes
of broken routing/ownership.  The patch-barrier invariants come from
DESIGN.md §7.3: pending work flushes under the plan it was submitted
against, the pipeline drains, and only then do placement arrays swap.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    BlockUnionTracker,
    build_cooccurrence,
    build_layout,
    compile_queries,
    correlation_aware_grouping,
    plan_replication,
    shard_block_queries,
)
from repro.core.reduction import reduce_dense_oracle
from repro.data import zipf_queries
from repro.dist import build_fused_image, plan_shards
from repro.kernels import crossbar_reduce_sharded
from repro.serve import FlushPolicy, RetryPolicy, ShardedEmbeddingServer
from repro.serve.drift import ReplanConfig

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


# ------------------------------------------------ subset block compile --


def test_subset_compile_owns_each_activation_once():
    """participants= restricts the stack to the subset; every activation
    lands on exactly one participating shard, replicated-tile ownership
    round-robins over the participants, and summing the subset kernels
    over a partition of the batch reproduces the oracle exactly."""
    rows, dim, S = 192, 128, 2
    hist = zipf_queries(rows, 48, 6.0, seed=0)
    layout, plan, gfreq = _pipeline(rows, hist, dim=dim)
    table = _int_table(rows, dim, 0)
    fused = build_fused_image([layout], [table])
    sp = plan_shards([layout], [plan], S, group_freqs=[gfreq])
    images = jnp.asarray(sp.build_shard_images(fused))
    ev = zipf_queries(rows, 12, 6.0, seed=1)

    # route queries by owner set (the scheduler's rule)
    owner_of_row = sp.shard_of_group[layout.group_of]
    by_home = {0: [], 1: [], None: []}
    for q in ev:
        owners = {int(o) for o in
                  np.unique(owner_of_row[np.unique(np.asarray(q, np.int64))])
                  if o >= 0}
        if len(owners) <= 1:
            by_home[owners.pop() if owners else 0].append(q)
        else:
            by_home[None].append(q)

    outs, queries = [], []
    for home in (0, 1):
        if not by_home[home]:
            continue
        cq = compile_queries(layout, by_home[home], replica_block=4)
        sbq = shard_block_queries(cq, sp, 4, participants=[home])
        assert sbq.tile_ids.shape[0] == 1
        assert sbq.shard_ids.tolist() == [home]
        # every bitmap row lives in the single participant's stack slot
        out = np.asarray(crossbar_reduce_sharded(
            images, sbq.tile_ids, sbq.bitmaps, shard_ids=sbq.shards
        ))[: sbq.batch]
        outs.append(out)
        queries.extend(by_home[home])
    if by_home[None]:
        cq = compile_queries(layout, by_home[None], replica_block=4)
        sbq = shard_block_queries(cq, sp, 4)
        outs.append(np.asarray(crossbar_reduce_sharded(
            images, sbq.tile_ids, sbq.bitmaps
        ))[: sbq.batch])
        queries.extend(by_home[None])
    got = np.concatenate(outs)
    want = np.asarray(reduce_dense_oracle(jnp.asarray(table), queries))
    np.testing.assert_array_equal(got, want)


def test_subset_compile_rejects_foreign_owners():
    """A query whose sharded-once groups live outside the participants
    must fail the compile loudly, not silently drop activations."""
    rows = 192
    hist = zipf_queries(rows, 48, 6.0, seed=2)
    layout, plan, gfreq = _pipeline(rows, hist)
    sp = plan_shards([layout], [plan], 2, group_freqs=[gfreq])
    owner_of_row = sp.shard_of_group[layout.group_of]
    ev = zipf_queries(rows, 24, 6.0, seed=3)
    multi = [q for q in ev if len({
        int(o) for o in np.unique(owner_of_row[np.unique(np.asarray(q, np.int64))])
        if o >= 0
    }) > 1]
    if not multi:
        return  # vacuous at this seed
    cq = compile_queries(layout, multi[:1], replica_block=4)
    with pytest.raises(ValueError, match="non-participating"):
        shard_block_queries(cq, sp, 4, participants=[0])


def test_subset_dispatch_matches_full_under_shard_ids():
    """crossbar_reduce_sharded with shard_ids= must equal the same
    batch compiled/dispatched through the full-stack path."""
    rows, dim, S = 192, 128, 4
    hist = zipf_queries(rows, 48, 6.0, seed=4)
    layout, plan, gfreq = _pipeline(rows, hist, dim=dim)
    table = _int_table(rows, dim, 4)
    fused = build_fused_image([layout], [table])
    sp = plan_shards([layout], [plan], S, group_freqs=[gfreq])
    images = jnp.asarray(sp.build_shard_images(fused))
    ev = zipf_queries(rows, 9, 6.0, seed=5)
    cq = compile_queries(layout, ev, replica_block=4)
    full = np.asarray(crossbar_reduce_sharded(
        images, *(lambda s: (s.tile_ids, s.bitmaps))(
            shard_block_queries(cq, sp, 4))
    ))[: len(ev)]
    sub = shard_block_queries(cq, sp, 4, participants=list(range(S)))
    got = np.asarray(crossbar_reduce_sharded(
        images, sub.tile_ids, sub.bitmaps, shard_ids=sub.shards
    ))[: len(ev)]
    np.testing.assert_array_equal(got, full)


# -------------------------------------------------- union-fill tracker --


def test_union_tracker_matches_compiled_grid():
    """The incremental fill accounting must agree with what
    shard_block_queries actually compiles for a single-shard stream."""
    rows = 192
    hist = zipf_queries(rows, 48, 6.0, seed=6)
    layout, plan, gfreq = _pipeline(rows, hist)
    sp = plan_shards([layout], [plan], 1, group_freqs=[gfreq])
    ev = zipf_queries(rows, 13, 6.0, seed=7)
    tr = BlockUnionTracker(4)
    groups = [np.unique(layout.group_of[np.unique(np.asarray(q, np.int64))])
              for q in ev]
    tr.extend(np.concatenate(groups), [g.size for g in groups])
    cq = compile_queries(layout, ev, replica_block=4)
    sbq = shard_block_queries(cq, sp, 4, participants=[0])
    assert tr.pending == len(ev)
    assert tr.grid_cells() == sbq.grid_cells_per_shard()
    tr.reset()
    assert tr.fill == 0 and tr.grid_cells() == 0


@pytest.mark.parametrize("q_block", [1, 4, 8])
def test_union_tracker_extend_matches_per_query_adds(q_block):
    """extend() over runs of any length (empty queries, duplicate ids,
    runs that open, close and straddle blocks) keeps the same fill,
    grid and open block as the per-query set-union oracle."""
    rng = np.random.default_rng(q_block)
    fast, ref = BlockUnionTracker(q_block), BlockUnionTracker(q_block)
    for _ in range(40):
        run = [rng.integers(0, 30, int(rng.integers(0, 6))).tolist()
               for _ in range(int(rng.integers(0, 2 * q_block + 3)))]
        fast.extend([g for q in run for g in q], [len(q) for q in run])
        for q in run:
            ref._reference_add(q)
        assert (fast.pending, fast.fill, fast.grid_cells(), fast._block) == (
            ref.pending, ref.fill, ref.grid_cells(), ref._block)


def test_flush_policy_validation():
    with pytest.raises(ValueError, match="unknown flush policy"):
        FlushPolicy(kind="sometimes")
    with pytest.raises(ValueError, match="max_in_flight"):
        FlushPolicy(kind="per-shard", max_in_flight=0)
    p = FlushPolicy.parse("deadline", batch_size=32)
    assert p.batch_size == 32 and p.deadline == 128 and p.is_async
    assert not FlushPolicy.parse("global", batch_size=8).is_async


# -------------------------------------------- async ≡ sync bit-identity --


@pytest.mark.parametrize("num_shards", [1, 2, 4])
@pytest.mark.parametrize("policy", ["per-shard", "deadline"])
def test_async_serving_bit_identical_to_sync(num_shards, policy):
    rows, dim = 160, 128
    rng = np.random.default_rng(10)
    tables = {"a": _int_table(rows, dim, 11), "b": _int_table(rows, dim, 12)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=13),
                 "b": zipf_queries(rows, 48, 5.0, seed=14)}
    streams = {"a": zipf_queries(rows, 30, 5.0, seed=15),
               "b": zipf_queries(rows, 17, 5.0, seed=16)}
    # skewed interleave: a arrives ~2x as often as b
    replay, ia, ib = [], 0, 0
    for i in range(len(streams["a"]) + len(streams["b"])):
        if (i % 3 < 2 and ia < len(streams["a"])) or ib >= len(streams["b"]):
            replay.append(("a", streams["a"][ia])); ia += 1
        else:
            replay.append(("b", streams["b"][ib])); ib += 1

    def run(policy, **kw):
        srv = ShardedEmbeddingServer(
            tables, histories, num_shards=num_shards, q_block=4,
            group_size=16, batch_size=8, flush_policy=policy, **kw,
        )
        outs = {n: [] for n in tables}
        for name, q in replay:
            for n, o in srv.submit(name, q).items():
                outs[n].append(np.asarray(o))
        for n, o in srv.flush().items():
            outs[n].append(np.asarray(o))
        return srv, {n: np.concatenate(v) for n, v in outs.items() if v}

    srv_g, outs_g = run("global")
    srv_a, outs_a = run(policy, max_in_flight=2, flush_deadline=20)
    for n in tables:
        np.testing.assert_array_equal(outs_a[n], outs_g[n])
        want = np.asarray(reduce_dense_oracle(
            jnp.asarray(tables[n]), streams[n]))
        np.testing.assert_array_equal(outs_a[n], want)
    st = srv_a.stats.summary()
    assert st["flush_policy"] == policy
    assert st["batches"] >= 1
    assert st["in_flight_peak"] >= 1
    if policy == "deadline" and num_shards > 1:
        # the skewed slow table must never wait unboundedly
        assert st["batches"] >= srv_g.stats.summary()["batches"]


def test_async_drain_orders_rows_by_submission():
    """drain() must return rows in per-table submission order even when
    homes flush out of order."""
    rows, dim = 160, 128
    tables = {"a": _int_table(rows, dim, 20)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=21)}
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=2, q_block=4, group_size=16,
        batch_size=4, flush_policy="per-shard",
    )
    stream = zipf_queries(rows, 23, 5.0, seed=22)
    for q in stream:
        srv.submit("a", q)
    out = srv.drain()
    want = np.asarray(reduce_dense_oracle(jnp.asarray(tables["a"]), stream))
    np.testing.assert_array_equal(np.asarray(out["a"]), want)
    # second drain with no traffic returns nothing
    assert srv.drain() == {}


def test_failed_async_flush_requeues_batch():
    """A failed flush must not drop its batch: a malformed query is
    rejected at routing time (nothing enqueued), and a dispatch-time
    failure requeues the whole batch for retry — the async analogue of
    the sync flush's leave-buffered-on-failure contract.  Pinned on
    ``RetryPolicy.legacy()``: the default self-healing policy retries
    in place instead of requeue-and-re-raise (test_faults.py)."""
    rows, dim = 160, 128
    tables = {"a": _int_table(rows, dim, 40)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=41)}
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=1, q_block=4, group_size=16,
        batch_size=8, flush_policy="per-shard", retry=RetryPolicy.legacy(),
    )
    good = zipf_queries(rows, 7, 5.0, seed=42)
    for q in good:
        srv.submit("a", q)
    # malformed query: rejected at the door, buffered work untouched
    with pytest.raises(IndexError):
        srv.submit("a", [rows + 5])
    assert srv.scheduler.pending_total() == 7
    # transient dispatch failure at the flush trigger: batch requeues
    calls = {"n": 0}
    orig = srv._compile_and_dispatch

    def flaky(entries, participants):
        if calls["n"] == 0:
            calls["n"] += 1
            raise RuntimeError("transient device error")
        return orig(entries, participants)

    srv._compile_and_dispatch = flaky
    last = zipf_queries(rows, 1, 5.0, seed=43)[0]
    with pytest.raises(RuntimeError):
        srv.submit("a", last)  # trips batch_size → flush → fails
    assert srv.scheduler.pending_total() == 8, "failed flush dropped queries"
    # retry (drain) succeeds and rows stay in submission order
    out = srv.drain()
    stream = list(good) + [last]
    want = np.asarray(reduce_dense_oracle(jnp.asarray(tables["a"]), stream))
    np.testing.assert_array_equal(np.asarray(out["a"]), want)


# --------------------------------------------- engine accounting fixes --


def test_device_busy_ignores_unknown_array_types():
    """hidden_compile_s promises a conservative LOWER bound: an output
    without is_ready (e.g. a materialized NumPy array from a stubbed
    dispatch) must count as idle, not busy — the old AttributeError
    branch overcounted hidden compile exactly where it mattered."""
    from repro.serve.sharded import _InFlight

    rows, dim = 160, 128
    tables = {"a": _int_table(rows, dim, 50)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=51)}
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=1, q_block=4, group_size=16,
        batch_size=8, flush_policy="per-shard",
    )
    stub = _InFlight(outs=[np.zeros((4, dim), np.float32)], sbq=None,
                     served=["a"], seqs={}, t0=0.0, n_queries=1)
    srv._in_flight.append(stub)
    assert srv._device_busy() is False, (
        "array without is_ready treated as busy — overcounts overlap"
    )
    assert srv._entry_ready(stub)

    class _NotReady:
        def is_ready(self):
            return False

    srv._in_flight.append(_InFlight(
        outs=[_NotReady()], sbq=None, served=["a"], seqs={}, t0=0.0,
        n_queries=1,
    ))
    assert srv._device_busy() is True
    srv._in_flight.clear()


def test_in_flight_peak_sampled_at_append():
    """The queue transiently holds max_in_flight + 1 entries before the
    retire loop trims it; the peak stat must report that transient, not
    the post-trim depth (which can never exceed the bound)."""
    rows, dim = 160, 128
    tables = {"a": _int_table(rows, dim, 52)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=53)}
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=1, q_block=4, group_size=16,
        batch_size=4, flush_policy="per-shard", max_in_flight=1,
    )
    stream = zipf_queries(rows, 12, 5.0, seed=54)  # >= 3 flushes
    for q in stream:
        srv.submit("a", q)
    out = srv.drain()
    assert srv.stats.batches >= 2
    assert srv.stats.in_flight_peak == 2, (
        f"peak {srv.stats.in_flight_peak} != max_in_flight + 1 — "
        "sampled after the retire loop trimmed the queue"
    )
    want = np.asarray(reduce_dense_oracle(jnp.asarray(tables["a"]), stream))
    np.testing.assert_array_equal(np.asarray(out["a"]), want)


@pytest.mark.parametrize("policy", ["global", "per-shard"])
def test_submit_validates_ids_before_enqueue(policy):
    """Malformed queries are rejected at the door: no buffer entry, no
    scheduler entry, and — crucially — no sequence id consumed, so the
    pending stream stays retryable without a removal API."""
    rows, dim = 160, 128
    tables = {"a": _int_table(rows, dim, 55)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=56)}
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=2, q_block=4, group_size=16,
        batch_size=64, flush_policy=policy,
    )
    good = zipf_queries(rows, 5, 5.0, seed=57)
    for q in good:
        srv.submit("a", q)
    for bad in ([rows], [rows + 5], [-1], [0, rows + 2]):
        with pytest.raises(IndexError, match="out of range"):
            srv.submit("a", bad)
    if srv.scheduler is not None:
        assert srv.scheduler.pending_total() == len(good)
        assert srv.next_seq("a") == len(good), "rejected query consumed a seq"
    else:
        assert srv._buffered == len(good)
    out = srv.flush()
    want = np.asarray(reduce_dense_oracle(jnp.asarray(tables["a"]), good))
    np.testing.assert_array_equal(np.asarray(out["a"]), want)


def test_seq_reset_guarded_by_requeued_entries():
    """drain() restarts sequence ids ONLY when nothing requeued is still
    carrying the old ones — a reset with a failed flush's entries alive
    would hand new submissions colliding seqs and scramble the argsort
    row order of the next drain.  Pinned on ``RetryPolicy.legacy()``:
    only the legacy policy requeues (healing retries in place)."""
    rows, dim = 160, 128
    tables = {"a": _int_table(rows, dim, 58)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=59)}
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=1, q_block=4, group_size=16,
        batch_size=8, flush_policy="per-shard", retry=RetryPolicy.legacy(),
    )
    good = zipf_queries(rows, 7, 5.0, seed=60)
    for q in good:
        srv.submit("a", q)
    orig = srv._compile_and_dispatch

    def broken(entries, participants):
        raise RuntimeError("persistent device error")

    srv._compile_and_dispatch = broken
    last = zipf_queries(rows, 1, 5.0, seed=61)[0]
    with pytest.raises(RuntimeError):
        srv.submit("a", last)  # trips the flush → fails → requeues
    assert srv.scheduler.pending_total() == 8
    assert srv.next_seq("a") == 8
    # a barrier that hands back without flushing (the partial-recovery
    # hazard) must not let drain() reset seqs over live requeued work
    orig_barrier = srv._barrier
    srv._barrier = lambda: None
    assert srv.drain() == {}
    assert srv.next_seq("a") == 8, "seq reset while requeued entries alive"
    srv._barrier = orig_barrier
    srv._compile_and_dispatch = orig
    more = zipf_queries(rows, 3, 5.0, seed=62)
    for q in more:
        srv.submit("a", q)
    out = srv.drain()
    stream = list(good) + [last] + list(more)
    want = np.asarray(reduce_dense_oracle(jnp.asarray(tables["a"]), stream))
    np.testing.assert_array_equal(np.asarray(out["a"]), want)
    assert srv.next_seq("a") == 0  # clean drain: seqs restart


def test_route_is_a_peek():
    """route() must not consume round-robin state: inspecting a query's
    home twice returns the same answer, and only push() advances."""
    rows, dim = 160, 128
    tables = {"a": _int_table(rows, dim, 45)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=46)}
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=2, q_block=4, group_size=16,
        batch_size=64, batch_size_for_eq1=512, flush_policy="per-shard",
    )
    sched = srv.scheduler
    owner = sched._owner_of_row["a"]
    repl_rows = np.nonzero(owner < 0)[0]
    if repl_rows.size == 0:
        return  # no replicated groups at this seed; vacuous
    q = [int(repl_rows[0])]
    h1, _ = sched.route("a", q)
    h2, _ = sched.route("a", q)
    assert h1 == h2, "route() consumed round-robin state"
    assert sched.push("a", 0, q) == h1
    # after the push the round robin advanced: next replicated-only
    # query routes to the other shard
    h3, _ = sched.route("a", q)
    assert h3 == (h1 + 1) % 2


# ------------------------------------------------- owner-set routing --


def _owner_rows(sched, table):
    """{owner shard: [row ids]} of the sharded-once rows of a table."""
    owner = sched._owner_of_row[table]
    out = {}
    for r, o in enumerate(owner):
        if o >= 0:
            out.setdefault(int(o), []).append(r)
    return out


def test_owner_set_scheduler_routes_by_frozen_owner_set():
    """Under owner-set routing each distinct multi-owner set is its own
    home (a sorted tuple) and take() returns exactly that set as flush
    participants — the full stack only when the set covers the mesh."""
    rows, dim, S = 160, 128, 4
    tables = {"a": _int_table(rows, dim, 63)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=64)}
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=S, q_block=4, group_size=16,
        batch_size=1024, flush_policy="owner-set",
    )
    sched = srv.scheduler
    by_owner = _owner_rows(sched, "a")
    if len(by_owner) < 2:
        return  # vacuous at this seed
    owners = sorted(by_owner)
    a, b = owners[0], owners[1]
    q2 = [by_owner[a][0], by_owner[b][0]]
    home, _ = sched.route("a", q2)
    assert home == (a, b)
    assert sched.push("a", 0, q2) == (a, b)
    entries, participants = sched.take((a, b))
    assert [e[2] for e in entries] == [q2]
    assert participants == [a, b]
    # single-owner queries still route to int homes
    h1, _ = sched.route("a", [by_owner[a][0]])
    assert h1 == a
    if len(by_owner) == S:
        qall = [by_owner[o][0] for o in owners]
        homeall, _ = sched.route("a", qall)
        assert homeall == tuple(owners)
        sched.push("a", 1, qall)
        _, parts = sched.take(tuple(owners))
        assert parts is None  # covers the mesh → full stack


def test_owner_set_max_pools_wide_sets():
    """Owner sets larger than owner_set_max collapse into the POOL home
    (flushed over their owner union) while sets within the cap keep
    their own — the fragmentation guard for near-mesh traffic."""
    from repro.serve.scheduler import POOL

    rows, dim, S = 160, 128, 4
    tables = {"a": _int_table(rows, dim, 73)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=74)}
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=S, q_block=4, group_size=16,
        batch_size=1024, flush_policy="owner-set", owner_set_max=2,
    )
    assert srv.policy.owner_set_max == 2
    sched = srv.scheduler
    by_owner = _owner_rows(sched, "a")
    if len(by_owner) < 3:
        return  # vacuous at this seed
    owners = sorted(by_owner)
    a, b, c = owners[:3]
    home2, _ = sched.route("a", [by_owner[a][0], by_owner[b][0]])
    assert home2 == (a, b)  # within the cap: keyed home
    home3, _ = sched.route("a", [by_owner[o][0] for o in (a, b, c)])
    assert home3 == POOL    # beyond the cap: pooled
    sched.push("a", 0, [by_owner[o][0] for o in (a, b, c)])
    _, parts = sched.take(POOL)
    assert parts == [a, b, c]  # pool still flushes over the owner union
    with pytest.raises(ValueError, match="owner_set_max"):
        FlushPolicy(kind="owner-set", owner_set_max=1)


@pytest.mark.parametrize("num_shards", [1, 2, 4])
@pytest.mark.parametrize("threaded", [False, True])
def test_owner_set_serving_bit_identical_to_sync(num_shards, threaded):
    """Owner-set homes (and the thread driver on top of them) must serve
    bit-identically to the synchronous global path and the oracle."""
    rows, dim = 160, 128
    tables = {"a": _int_table(rows, dim, 11), "b": _int_table(rows, dim, 12)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=13),
                 "b": zipf_queries(rows, 48, 5.0, seed=14)}
    streams = {"a": zipf_queries(rows, 30, 5.0, seed=15),
               "b": zipf_queries(rows, 17, 5.0, seed=16)}
    replay, ia, ib = [], 0, 0
    for i in range(len(streams["a"]) + len(streams["b"])):
        if (i % 3 < 2 and ia < len(streams["a"])) or ib >= len(streams["b"]):
            replay.append(("a", streams["a"][ia])); ia += 1
        else:
            replay.append(("b", streams["b"][ib])); ib += 1

    def run(policy, **kw):
        srv = ShardedEmbeddingServer(
            tables, histories, num_shards=num_shards, q_block=4,
            group_size=16, batch_size=8, flush_policy=policy, **kw,
        )
        outs = {n: [] for n in tables}
        for name, q in replay:
            for n, o in srv.submit(name, q).items():
                outs[n].append(np.asarray(o))
        for n, o in srv.flush().items():
            outs[n].append(np.asarray(o))
        srv.close()
        return srv, {n: np.concatenate(v) for n, v in outs.items() if v}

    srv_g, outs_g = run("global")
    srv_o, outs_o = run("owner-set", threaded=threaded, max_in_flight=2)
    for n in tables:
        np.testing.assert_array_equal(outs_o[n], outs_g[n])
        want = np.asarray(reduce_dense_oracle(
            jnp.asarray(tables[n]), streams[n]))
        np.testing.assert_array_equal(outs_o[n], want)
    st = srv_o.stats.summary()
    assert st["flush_policy"] == "owner-set"
    assert st["batches"] >= 1
    if num_shards > 1:
        # no flush may stack more schedules than the mesh has shards
        assert max(int(k) for k in st["participant_sizes"]) <= num_shards


def test_two_owner_traffic_flushes_two_participants():
    """The acceptance contract of owner-set routing: 2-owner traffic on
    a 4-shard mesh flushes with participant sets of size two — never
    the near-mesh-wide pool the PR-4 scheduler collapsed it into."""
    rows, dim, S = 160, 128, 4
    tables = {"a": _int_table(rows, dim, 65)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=66)}
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=S, q_block=4, group_size=16,
        batch_size=8, flush_policy="owner-set",
    )
    by_owner = _owner_rows(srv.scheduler, "a")
    if len(by_owner) < 2:
        return  # vacuous at this seed
    owners = sorted(by_owner)
    a, b = owners[0], owners[1]
    stream = [
        [by_owner[a][i % len(by_owner[a])], by_owner[b][i % len(by_owner[b])]]
        for i in range(24)
    ]
    for q in stream:
        srv.submit("a", q)
    out = srv.drain()
    sizes = {int(k) for k in srv.stats.summary()["participant_sizes"]}
    assert sizes == {2}, (
        f"2-owner traffic flushed with participant sizes {sizes}"
    )
    want = np.asarray(reduce_dense_oracle(jnp.asarray(tables["a"]), stream))
    np.testing.assert_array_equal(np.asarray(out["a"]), want)


# ------------------------------------------------------- thread driver --


def test_thread_driver_submit_is_enqueue_only():
    """Under the thread driver submit() never dispatches inline: the
    driver owns compile/dispatch/retire, results arrive at drain(), and
    submit-side latency samples are recorded for every call."""
    rows, dim = 160, 128
    tables = {"a": _int_table(rows, dim, 67)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=68)}
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=2, q_block=4, group_size=16,
        batch_size=4, flush_policy="per-shard", threaded=True,
        max_in_flight=1,
    )
    stream = zipf_queries(rows, 23, 5.0, seed=69)
    for q in stream:
        assert srv.submit("a", q) == {}
    out = srv.drain()
    srv.close()
    want = np.asarray(reduce_dense_oracle(jnp.asarray(tables["a"]), stream))
    np.testing.assert_array_equal(np.asarray(out["a"]), want)
    assert len(srv.stats.submit_wall) == len(stream)
    assert len(srv.stats.flush_wall) == srv.stats.batches
    st = srv.stats.summary()
    assert st["submit_latency_s"]["p50"] <= st["submit_latency_s"]["p95"]
    assert st["submit_latency_s"]["p95"] <= st["submit_latency_s"]["p99"]
    # a second drain with no traffic returns nothing and is harmless
    assert srv.drain() == {}


def test_thread_driver_surfaces_failures_and_retries():
    """A flush failure on the driver thread requeues its batch and
    surfaces at the next submit()/drain(); a later drain retries the
    requeued work and returns every row in submission order.  Pinned on
    ``RetryPolicy.legacy()`` — the default policy heals on the driver
    thread without surfacing (test_faults.py)."""
    import time as _time

    rows, dim = 160, 128
    tables = {"a": _int_table(rows, dim, 70)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=71)}
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=1, q_block=4, group_size=16,
        batch_size=8, flush_policy="per-shard", threaded=True,
        retry=RetryPolicy.legacy(),
    )
    calls = {"n": 0}
    orig = srv._compile_and_dispatch

    def flaky(entries, participants):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient device error")
        return orig(entries, participants)

    srv._compile_and_dispatch = flaky
    stream = zipf_queries(rows, 9, 5.0, seed=72)
    for q in stream[:8]:
        srv.submit("a", q)  # 8th trips the flush on the driver → fails
    deadline = _time.monotonic() + 10.0
    while not srv._driver_errors and _time.monotonic() < deadline:
        _time.sleep(0.005)
    assert srv._driver_errors, "driver never recorded the failure"
    with pytest.raises(RuntimeError, match="transient device error"):
        srv.drain()
    out = srv.drain()  # retry: the requeued batch flushes cleanly now
    for q in stream[8:]:
        srv.submit("a", q)
    out2 = srv.drain()
    srv.close()
    got = np.concatenate([np.asarray(out["a"]), np.asarray(out2["a"])])
    want = np.asarray(reduce_dense_oracle(jnp.asarray(tables["a"]), stream))
    np.testing.assert_array_equal(got, want)


def test_close_preserves_handoff_backlog():
    """close() must never drop submitted queries: whatever the driver
    had not yet popped from the hand-off queue is pushed back into the
    scheduler, and a later (inline) drain serves every row in
    submission order."""
    rows, dim = 160, 128
    tables = {"a": _int_table(rows, dim, 75)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=76)}
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=2, q_block=4, group_size=16,
        batch_size=64, flush_policy="per-shard", threaded=True,
    )
    stream = zipf_queries(rows, 9, 5.0, seed=77)
    for q in stream:
        srv.submit("a", q)
    srv.close()  # races the driver: any undispatched backlog must survive
    assert srv._driver is None
    out = srv.drain()  # driver stopped → inline barrier
    want = np.asarray(reduce_dense_oracle(jnp.asarray(tables["a"]), stream))
    np.testing.assert_array_equal(np.asarray(out["a"]), want)


def test_latency_percentiles_sanity():
    from repro.serve.sharded import _latency_percentiles

    assert _latency_percentiles([]) == {"p50": 0.0, "p95": 0.0, "p99": 0.0}
    pct = _latency_percentiles([1.0, 2.0, 3.0, 4.0])
    assert pct["p50"] <= pct["p95"] <= pct["p99"] <= 4.0
    assert pct["p50"] == 2.5


# ------------------------------------- PlanPatch × async-flush barrier --


def _drifting_async_server(rows=128, dim=128, **kw):
    tables = {"a": _int_table(rows, dim, 31)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=32)}
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=2, q_block=4, group_size=16,
        batch_size=8, flush_policy="per-shard",
        replan=ReplanConfig(threshold=0.2, half_life=1.0, min_queries=8,
                            slack_tiles=4),
        **kw,
    )
    return srv, tables


@pytest.mark.parametrize("num_shards", [1, 2, 4])
def test_patch_staged_mid_pipeline_applies_at_barrier_only(num_shards):
    """A patch staged while flushes are in flight must wait for the
    barrier: placement arrays never swap with work in the pipeline, and
    the drained outputs stay exact across the plan transition."""
    rows, dim = 128, 128
    tables = {"a": _int_table(rows, dim, 31)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=32)}
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=num_shards, q_block=4, group_size=16,
        # eq1_batch large enough that Eq. 1 replicates groups even at 4
        # shards — otherwise every drift event is a rebase and nothing
        # ever stages
        batch_size=8, batch_size_for_eq1=512,
        flush_policy="per-shard", max_in_flight=4,
        replan=ReplanConfig(threshold=0.15, half_life=1.0, min_queries=8,
                            slack_tiles=8),
    )
    applied_with_in_flight = []
    orig_apply = srv._apply_staged_patch

    def spy_apply():
        if srv._staged is not None:
            applied_with_in_flight.append(len(srv._in_flight))
        orig_apply()

    srv._apply_staged_patch = spy_apply

    stream = zipf_queries(rows, 48, 5.0, seed=33)
    perm = np.random.default_rng(34).permutation(rows)
    stream = stream[:16] + [perm[np.asarray(q, np.int64)] for q in stream[16:]]
    saw_staged_mid_pipeline = False
    for q in stream:
        srv.submit("a", q)
        if srv._staged is not None and srv._in_flight:
            saw_staged_mid_pipeline = True
    out = srv.drain()
    assert saw_staged_mid_pipeline, "drift never staged while in flight"
    assert applied_with_in_flight, "no patch was ever applied"
    assert all(n == 0 for n in applied_with_in_flight), (
        "patch applied with flushes in flight"
    )
    assert srv.stats.replans + srv.stats.rebases >= 1
    assert srv.stats.barrier_flushes >= 1
    want = np.asarray(reduce_dense_oracle(jnp.asarray(tables["a"]), stream))
    np.testing.assert_array_equal(np.asarray(out["a"]), want)


def test_patch_applies_at_barrier_only_under_thread_driver():
    """The §7.3 barrier rule must survive the thread driver: a patch
    staged by driver-side flushes applies only with the pipeline empty
    (spied on the driver thread), and the drained outputs stay exact
    across the plan transition."""
    rows, dim = 128, 128
    tables = {"a": _int_table(rows, dim, 31)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=32)}
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=2, q_block=4, group_size=16,
        batch_size=8, batch_size_for_eq1=512,
        flush_policy="per-shard", max_in_flight=4, threaded=True,
        replan=ReplanConfig(threshold=0.15, half_life=1.0, min_queries=8,
                            slack_tiles=8),
    )
    applied_with_in_flight = []
    orig_apply = srv._apply_staged_patch

    def spy_apply():
        if srv._staged is not None:
            applied_with_in_flight.append(len(srv._in_flight))
        orig_apply()

    srv._apply_staged_patch = spy_apply
    stream = zipf_queries(rows, 48, 5.0, seed=33)
    perm = np.random.default_rng(34).permutation(rows)
    stream = stream[:16] + [perm[np.asarray(q, np.int64)] for q in stream[16:]]
    for q in stream:
        srv.submit("a", q)
    out = srv.drain()
    srv.close()
    assert applied_with_in_flight, "no patch was ever applied"
    assert all(n == 0 for n in applied_with_in_flight), (
        "patch applied with flushes in flight"
    )
    assert srv.stats.replans + srv.stats.rebases >= 1
    assert srv.stats.barrier_flushes >= 1
    want = np.asarray(reduce_dense_oracle(jnp.asarray(tables["a"]), stream))
    np.testing.assert_array_equal(np.asarray(out["a"]), want)


def test_sync_serve_barriers_pending_async_queries():
    """A synchronous serve() call on an async server is a barrier: the
    pending (not yet flushed) queries must flush under the plan they
    were routed against BEFORE a staged patch applies — stale routing
    would compile them onto shards that no longer own their groups."""
    rows, dim = 128, 128
    tables = {"a": _int_table(rows, dim, 31)}
    histories = {"a": zipf_queries(rows, 48, 5.0, seed=32)}
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=2, q_block=4, group_size=16,
        batch_size=8, batch_size_for_eq1=512,
        flush_policy="per-shard", max_in_flight=4,
        replan=ReplanConfig(threshold=0.15, half_life=1.0, min_queries=8,
                            slack_tiles=8),
    )
    stream = zipf_queries(rows, 44, 5.0, seed=33)
    perm = np.random.default_rng(34).permutation(rows)
    stream = stream[:16] + [perm[np.asarray(q, np.int64)] for q in stream[16:]]
    probe = zipf_queries(rows, 5, 5.0, seed=36)
    served = []
    for i, q in enumerate(stream):
        srv.submit("a", q)
        if i == len(stream) - 3:
            # mid-replay sync serve: pending queries + (likely) a
            # staged patch are both outstanding right now
            served.append(("probe", np.asarray(srv.serve({"a": probe})["a"])))
    out = srv.drain()
    np.testing.assert_array_equal(
        served[0][1],
        np.asarray(reduce_dense_oracle(jnp.asarray(tables["a"]), probe)),
    )
    want = np.asarray(reduce_dense_oracle(jnp.asarray(tables["a"]), stream))
    np.testing.assert_array_equal(np.asarray(out["a"]), want)
    assert srv.stats.replans >= 1  # the patch really applied en route


def test_patched_async_server_matches_fresh_rebuild():
    """After the async replay's patches, the live plan must serve a
    probe bit-identically to a from-scratch plan_shards rebuild on the
    plan's (drifted) load snapshot — the §6 invariant holding through
    the §7 engine."""
    rows, dim, S = 128, 128, 2
    hist = zipf_queries(rows, 48, 5.0, seed=32)
    layout, plan, gfreq = _pipeline(rows, hist, dim=dim)
    tables = {"a": _int_table(rows, dim, 31)}
    srv = ShardedEmbeddingServer(
        tables, {"a": hist}, num_shards=S, q_block=4, group_size=16,
        batch_size=8, flush_policy="per-shard",
        replan=ReplanConfig(threshold=0.2, half_life=1.0, min_queries=8,
                            slack_tiles=4),
    )
    stream = zipf_queries(rows, 48, 5.0, seed=33)
    perm = np.random.default_rng(34).permutation(rows)
    stream = stream[:16] + [perm[np.asarray(q, np.int64)] for q in stream[16:]]
    for q in stream:
        srv.submit("a", q)
    srv.drain()
    if srv.stats.replans == 0:
        return  # no class change at this seed; vacuous
    # the patched plan's group_load IS the drifted snapshot Eq. 1 saw
    fresh = plan_shards(
        [layout], [plan], S,
        group_freqs=[srv.plan.group_load], eq1_batch=srv._eq1_batch,
    )
    np.testing.assert_array_equal(
        srv.plan.replicated_group, fresh.replicated_group
    )
    probe = zipf_queries(rows, 11, 5.0, seed=35)
    out_srv = srv.serve({"a": probe})["a"]
    fused = build_fused_image([layout], [tables["a"]])
    images_f = jnp.asarray(fresh.build_shard_images(fused))
    cq = compile_queries(layout, probe, replica_block=4)
    sbq = shard_block_queries(cq, fresh, 4)
    out_f = np.asarray(crossbar_reduce_sharded(
        images_f, sbq.tile_ids, sbq.bitmaps
    ))[: sbq.batch]
    np.testing.assert_array_equal(np.asarray(out_srv), out_f)


def test_shard_map_async_serving_subprocess():
    """The REAL shard_map path must run the async engine — subset
    flushes scattered into the full device stack — bit-identically to
    the global policy.  Device forcing must precede jax init →
    subprocess with 2 host devices."""
    import os
    import subprocess
    import sys

    script = r"""
import numpy as np
import jax, jax.numpy as jnp
assert len(jax.devices()) >= 2, jax.devices()
import sys
sys.path.insert(0, {src!r})
from repro.data import zipf_queries
from repro.serve import ShardedEmbeddingServer
from repro.serve.drift import ReplanConfig
from repro.core.reduction import reduce_dense_oracle

rows, dim, S = 96, 128, 2
tables = {{"a": np.random.default_rng(3).integers(
    -8, 9, size=(rows, dim)).astype(np.float32)}}
histories = {{"a": zipf_queries(rows, 32, 5.0, seed=1)}}
stream = zipf_queries(rows, 30, 5.0, seed=2)
perm = np.random.default_rng(4).permutation(rows)
stream = stream[:10] + [perm[np.asarray(q, np.int64)] for q in stream[10:]]
mesh = jax.make_mesh((1, S), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

def run(policy, mesh, **kw):
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=S, mesh=mesh, q_block=4,
        group_size=16, batch_size=8, flush_policy=policy,
        replan=ReplanConfig(threshold=0.2, half_life=1.0, min_queries=8,
                            slack_tiles=4),
        **kw)
    outs = []
    for q in stream:
        for _, o in srv.submit("a", q).items():
            outs.append(np.asarray(o))
    for _, o in srv.flush().items():
        outs.append(np.asarray(o))
    return srv, np.concatenate(outs)

srv_sm, out_sm = run("per-shard", mesh)
srv_emu, out_emu = run("per-shard", None)
srv_g, out_g = run("global", mesh)
np.testing.assert_array_equal(out_sm, out_emu)
np.testing.assert_array_equal(out_sm, out_g)
oracle = np.asarray(reduce_dense_oracle(jnp.asarray(tables["a"]), stream))
np.testing.assert_array_equal(out_sm, oracle)
assert srv_sm.stats.batches >= 2
print("SCHEDULER_SHARD_MAP_PARITY_OK")
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
    assert "SCHEDULER_SHARD_MAP_PARITY_OK" in proc.stdout


def test_owner_set_thread_driver_shard_map_subprocess():
    """Owner-set homes + the thread driver on the REAL shard_map path
    (4 forced host devices): 2-owner flushes dispatch the grouped-psum
    subset combine and everything stays bit-identical to emulation, the
    global policy, and the oracle.  Device forcing must precede jax
    init → subprocess."""
    import os
    import subprocess
    import sys

    script = r"""
import numpy as np
import jax, jax.numpy as jnp
assert len(jax.devices()) >= 4, jax.devices()
import sys
sys.path.insert(0, {src!r})
from repro.data import zipf_queries
from repro.serve import ShardedEmbeddingServer
from repro.core.reduction import reduce_dense_oracle

rows, dim, S = 96, 128, 4
tables = {{"a": np.random.default_rng(3).integers(
    -8, 9, size=(rows, dim)).astype(np.float32)}}
histories = {{"a": zipf_queries(rows, 32, 5.0, seed=1)}}
mesh = jax.make_mesh((1, S), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

# owner map for crafting 2-owner queries (read off a probe server)
probe = ShardedEmbeddingServer(
    tables, histories, num_shards=S, q_block=4, group_size=16,
    batch_size=8, flush_policy="owner-set")
owner = probe.scheduler._owner_of_row["a"]
by_owner = {{}}
for r, o in enumerate(owner):
    if o >= 0:
        by_owner.setdefault(int(o), []).append(r)
owners = sorted(by_owner)
assert len(owners) >= 2, owners
a, b = owners[0], owners[1]
stream = list(zipf_queries(rows, 18, 5.0, seed=2))
stream += [
    [by_owner[a][i % len(by_owner[a])], by_owner[b][i % len(by_owner[b])]]
    for i in range(10)
]

def run(policy, mesh, **kw):
    srv = ShardedEmbeddingServer(
        tables, histories, num_shards=S, mesh=mesh, q_block=4,
        group_size=16, batch_size=8, flush_policy=policy, **kw)
    outs = []
    for q in stream:
        for _, o in srv.submit("a", q).items():
            outs.append(np.asarray(o))
    for _, o in srv.flush().items():
        outs.append(np.asarray(o))
    srv.close()
    return srv, np.concatenate(outs)

srv_sm, out_sm = run("owner-set", mesh, threaded=True)
srv_emu, out_emu = run("owner-set", None, threaded=True)
srv_g, out_g = run("global", mesh)
np.testing.assert_array_equal(out_sm, out_emu)
np.testing.assert_array_equal(out_sm, out_g)
oracle = np.asarray(reduce_dense_oracle(jnp.asarray(tables["a"]), stream))
np.testing.assert_array_equal(out_sm, oracle)
sizes = {{int(k) for k in srv_sm.stats.summary()["participant_sizes"]}}
assert 2 in sizes, sizes   # the grouped-psum subset combine really ran
assert len(srv_sm.stats.submit_wall) == len(stream)
print("OWNER_SET_THREAD_DRIVER_SHARD_MAP_OK")
""".format(src=os.path.join(os.path.dirname(__file__), "..", "src"))

    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=4 "
        + env.get("XLA_FLAGS", "")
    )
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, env=env, timeout=480,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OWNER_SET_THREAD_DRIVER_SHARD_MAP_OK" in proc.stdout


# ------------------------------------ chunked routing ≡ per-query push --


def _routing_server(num_shards):
    """A two-table plan (tables of different sizes) for routing tests."""
    tables = {"a": _int_table(160, 128, 81), "b": _int_table(96, 128, 82)}
    histories = {"a": zipf_queries(160, 48, 5.0, seed=83),
                 "b": zipf_queries(96, 48, 5.0, seed=84)}
    return ShardedEmbeddingServer(
        tables, histories, num_shards=num_shards, q_block=4,
        group_size=16, batch_size=16, batch_size_for_eq1=512,
        flush_policy="per-shard",
    )


def _mixed_stream(sched, n, seed):
    """Seeded ``(table, seq, query)`` entries mixing replicated-only,
    single-owner and multi-owner bags over both tables, with empty bags
    and duplicate ids."""
    rng = np.random.default_rng(seed)
    by_owner = {t: {} for t in ("a", "b")}
    for t in by_owner:
        for r, o in enumerate(sched._owner_of_row[t]):
            by_owner[t].setdefault(int(o), []).append(r)
    out = []
    for i in range(n):
        t = "ab"[int(rng.integers(0, 2))]
        kind = int(rng.integers(0, 5))
        pools = by_owner[t]
        owners = sorted(o for o in pools if o >= 0)
        if kind == 0:
            q = []
        elif kind == 1 and -1 in pools:
            q = rng.choice(pools[-1], int(rng.integers(1, 6))).tolist()
        elif kind == 2 and owners:
            o = owners[int(rng.integers(0, len(owners)))]
            q = rng.choice(pools[o], int(rng.integers(1, 6))).tolist()
            if -1 in pools:
                q += rng.choice(pools[-1], 2).tolist()
        else:
            picked = rng.choice(owners, min(len(owners), int(rng.integers(2, 5))),
                                replace=False) if owners else []
            q = [int(rng.choice(pools[int(o)])) for o in picked]
        if q and rng.random() < 0.3:
            q.append(q[0])  # a duplicate id
        out.append((t, i, [int(x) for x in q]))
    return out


def _route_state(sched):
    return {
        "pending": [(h, list(q)) for h, q in sched._pending.items()],
        "rr": sched._rr, "tick": sched._tick,
        "first_tick": dict(sched._first_tick),
        "pool_owners": set(sched._pool_owners),
        "trackers": {(h, t): (tr.pending, tr.fill, tr.grid_cells(), tr._block)
                     for h, d in sched._trackers.items()
                     for t, tr in d.items()},
        "pushed": dict(sched.pushed_by_producer),
    }


_ROUTING_POLICIES = {
    "per-shard": dict(kind="per-shard", batch_size=16),
    "deadline": dict(kind="deadline", batch_size=16, deadline=6),
    "owner-set": dict(kind="owner-set", batch_size=16),
    "owner-set-max2": dict(kind="owner-set", batch_size=16, owner_set_max=2),
    "union-budget": dict(kind="per-shard", batch_size=16, union_budget=12),
}


@pytest.mark.parametrize("num_shards", [1, 2, 4])
@pytest.mark.parametrize("policy", sorted(_ROUTING_POLICIES))
def test_push_many_matches_per_query_push(num_shards, policy):
    """push_many over runs of any length gives every bag the home,
    every home the pending order, round robin, ticks, union trackers
    and pool owners, and every due check the homes and batches, that
    one _reference_push and one due check per bag give."""
    from repro.serve.scheduler import FlushScheduler

    srv = _routing_server(num_shards)
    pol = FlushPolicy.parse(FlushPolicy(**_ROUTING_POLICIES[policy]),
                            batch_size=16)
    ref, fast = (FlushScheduler(srv.plan, srv.layouts, srv.names, 4, pol)
                 for _ in range(2))
    stream = _mixed_stream(ref, 300, seed=num_shards)

    def due_point(sched, log):
        due = [(h, sched.due_reason(h)) for h in sched.due_homes()]
        if due:
            log.append((sched._tick, due, [sched.take(h) for h, _ in due]))

    ref_homes, ref_due = [], []
    for table, seq, query in stream:
        ref_homes.append(ref._reference_push(table, seq, query))
        due_point(ref, ref_due)
    fast_homes, fast_due = [], []
    rng, i = np.random.default_rng(7), 0
    while i < len(stream):
        k = int(rng.integers(1, 48))
        fast_homes += fast.push_many(stream[i:i + k],
                                     flush=lambda: due_point(fast, fast_due))
        i += k
    assert fast_homes == ref_homes
    assert fast_due == ref_due
    assert ref_due, "no due point: the stream exercises nothing"
    assert _route_state(fast) == _route_state(ref)


def test_push_many_cuts_runs_at_due_points():
    """A run is pushed up to each due point, flushed there, and the rest
    stays pending; without a flush callback the whole run is pushed."""
    from repro.serve.scheduler import FlushScheduler

    srv = _routing_server(1)
    pol = FlushPolicy.parse("per-shard", batch_size=16)
    sched = FlushScheduler(srv.plan, srv.layouts, srv.names, 4, pol)
    stream = [("a", i, [i % 160, (7 * i) % 160]) for i in range(40)]
    seen = []

    def flush():
        for h in sched.due_homes():
            seen.append((sched._tick, [e[1] for e in sched.take(h)[0]]))

    sched.push_many(stream, flush=flush)
    assert seen == [(16, list(range(16))), (32, list(range(16, 32)))]
    assert [e[1] for e in sched._pending[0]] == list(range(32, 40))
    assert sched._first_tick == {0: 32}
    back = FlushScheduler(srv.plan, srv.layouts, srv.names, 4, pol)
    back.push_many(stream)
    assert back.pending_total() == 40 and back.due_homes() == [0]


def test_push_many_stops_wherever_due_reason_says(monkeypatch):
    """push_many asks the policy's own due check after every bag, so a
    trigger it does not know of (here: every seventh tick) still cuts
    the run exactly where per-bag routing would flush."""
    from repro.serve.scheduler import FlushScheduler

    srv = _routing_server(2)
    pol = FlushPolicy.parse("per-shard", batch_size=16)
    sched = FlushScheduler(srv.plan, srv.layouts, srv.names, 4, pol)

    def every_seventh(self, home):
        return "tick" if self._pending[home] and self._tick % 7 == 0 else None

    monkeypatch.setattr(FlushScheduler, "due_reason", every_seventh)
    seen = []

    def flush():
        for h in sched.due_homes():
            seen.append(sched._tick)
            sched.take(h)

    sched.push_many(_mixed_stream(sched, 30, seed=5), flush=flush)
    assert sorted(set(seen)) == [7, 14, 21, 28]


def test_push_many_raises_on_a_cold_bag_after_the_bags_before_it():
    """A bag touching a cold (host-tier) group raises as per-query
    routing does: the bags before it are pushed, it and the rest not."""
    import types

    from repro.serve.scheduler import FlushScheduler

    srv = _routing_server(2)
    sog = np.asarray(srv.plan.shard_of_group).copy()
    gof = np.asarray(srv.layouts[srv.names.index("a")].group_of)
    seg = next(t for t in srv.plan.tables if t.name == "a")
    cold_row = 5
    sog[gof[cold_row] + seg.group_offset] = -2
    plan = types.SimpleNamespace(num_shards=2, shard_of_group=sog,
                                 tables=srv.plan.tables)
    pol = FlushPolicy.parse("per-shard", batch_size=16)
    warm = [r for r in range(160) if gof[r] != gof[cold_row]]
    stream = [("a", 0, warm[:3]), ("b", 1, [1, 2]),
              ("a", 2, [warm[3], cold_row]), ("a", 3, warm[4:6])]
    fast = FlushScheduler(plan, srv.layouts, srv.names, 4, pol)
    with pytest.raises(ValueError, match="cold"):
        fast.push_many(stream)
    ref = FlushScheduler(plan, srv.layouts, srv.names, 4, pol)
    for e in stream[:2]:
        ref._reference_push(*e)
    with pytest.raises(ValueError, match="cold"):
        ref._reference_push(*stream[2])
    assert _route_state(fast) == _route_state(ref)
    assert fast.pending_total() == 2
    with pytest.raises(ValueError, match="cold"):
        fast.route("a", [cold_row])
