"""Spans, counters and keyed completion stamps of the async engine
(DESIGN.md §7.2): what the front door and the driver thread add up, the
stamps a reader takes per ``(producer, table, local seq)``, the plan
build's stage timings, and the spans each flush opens on the engine."""

import threading
import time

import jax
import numpy as np
import pytest

from repro.data import zipf_queries
from repro.serve import FaultPlan, RetryPolicy, ShardedEmbeddingServer
from repro.serve.producers import SEQ_STRIDE
from repro.serve.stamps import CompletionStamps

ROWS, DIM = 160, 128
TABLES = {n: np.random.default_rng(s).integers(-8, 9, (ROWS, DIM)).astype(np.float32)
          for n, s in (("a", 21), ("b", 22))}
HISTORIES = {"a": zipf_queries(ROWS, 48, 5.0, seed=23),
             "b": zipf_queries(ROWS, 48, 5.0, seed=24)}


def _server(**kw):
    kw.setdefault("threaded", True)
    kw.setdefault("batch_size", 8)
    return ShardedEmbeddingServer(
        TABLES, HISTORIES, num_shards=2, q_block=4, group_size=16,
        flush_policy="per-shard", **kw)


def _submit(srv, n_producers, n_submits):
    """Each producer submits its own stream from its own thread,
    alternating tables; returns ``{(label, table): bags}``."""
    streams = [list(zipf_queries(ROWS, n_submits, 5.0, seed=300 + p))
               for p in range(n_producers)]
    labels = [f"p{p}" for p in range(n_producers)]
    for label in labels:
        srv.register_producer(label)
    sent = {}
    for p, label in enumerate(labels):
        for i, q in enumerate(streams[p]):
            sent.setdefault((label, "ab"[i % 2]), []).append(q)
    errs = []

    def body(p):
        try:
            for i, q in enumerate(streams[p]):
                srv.submit("ab"[i % 2], q, producer=labels[p])
        except Exception as e:  # pragma: no cover - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=body, args=(p,)) for p in range(n_producers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    return sent


@pytest.mark.parametrize("n_producers", [1, 4])
def test_front_door_and_engine_counters_add_up(n_producers):
    srv = _server()
    try:
        n = 40
        _submit(srv, n_producers, n)
        srv.drain()
        st = srv.stats
        assert st.submits == n_producers * n == len(st.submit_wall)
        assert st.routed == n_producers * n
        assert 0.0 <= st.handoff_full_s <= st.submit_s
        assert st.submit_s <= sum(st.submit_wall)
        for k in ("submit_s", "handoff_full_s", "engine_wait_s", "route_s"):
            assert getattr(st, k) >= 0.0, k
        assert st.engine_wait_s > 0.0     # the driver idles between items
    finally:
        srv.close()


def test_a_full_handoff_queue_is_timed_as_blocked():
    """With a one-slot hand-off and a slow engine, a producer's submits
    block on the full queue, and that time is counted apart."""
    srv = _server(batch_size=4)
    srv.policy.handoff_depth = 1
    real = srv._ingest_many

    def slow(*args, **kw):
        threading.Event().wait(0.002)
        real(*args, **kw)

    srv._ingest_many = slow
    try:
        _submit(srv, 1, 24)
        srv.drain()
        st = srv.stats
        assert 0.0 < st.handoff_full_s <= st.submit_s
    finally:
        srv.close()


def test_route_time_leaves_out_the_flushes_it_triggers():
    """Inline, every flush a submit triggers runs inside that submit:
    made 10 ms slower each, they lengthen submit_s and not route_s."""
    srv = _server(threaded=False)
    real = srv._flush_home
    calls = []

    def slow(*args, **kw):
        calls.append(1)
        threading.Event().wait(0.01)
        real(*args, **kw)

    srv._flush_home = slow
    try:
        _submit(srv, 1, 64)
        st = srv.stats
        assert st.routed == 64 and len(calls) > 0
        assert 0.0 < st.route_s <= st.submit_s - 0.01 * len(calls)
        srv.drain()
    finally:
        srv.close()


@pytest.mark.parametrize("n_producers", [1, 4])
def test_every_drained_bag_has_one_keyed_record(n_producers):
    srv = _server()
    try:
        sent = _submit(srv, n_producers, 30)
        out = srv.drain()
        assert sum(np.asarray(v).shape[0] for v in out.values()) == 30 * n_producers
        records = srv.take_completion_stamps()
    finally:
        srv.close()
    keys = [(r.producer, r.table, int(s)) for r in records for s in r.local_seq]
    assert len(keys) == len(set(keys))
    assert sorted(keys) == sorted(
        (label, table, i) for (label, table), bags in sent.items()
        for i in range(len(bags)))
    for r in records:
        assert r.epoch == 0
        assert np.all(r.completed >= r.submitted)
        assert np.all(np.diff(r.local_seq) > 0)
    assert srv.take_completion_stamps() == []


def test_quarantined_bags_have_no_record():
    plan = FaultPlan([], seed=5).add("poison", table="a", seq=3)
    srv = _server(retry=RetryPolicy(max_retries=1, backoff_base=1e-4,
                                    backoff_max=1e-3), faults=plan)
    try:
        for i, q in enumerate(zipf_queries(ROWS, 20, 5.0, seed=31)):
            srv.submit("ab"[i % 2], q)
        srv.drain()
        records = srv.take_completion_stamps()
    finally:
        srv.close()
    assert srv.stats.ledger.quarantined_keys() == [("a", 3)]
    seqs = {r.table: r.local_seq.tolist() for r in records}
    assert seqs == {"a": [0, 1, 2, 4, 5, 6, 7, 8, 9], "b": list(range(10))}


def test_records_of_two_epochs_are_never_merged():
    """A quiesced drain restarts local seqs at 0: the records of the
    bags before it and after it come back apart, each under its epoch."""
    srv = _server()
    try:
        _submit(srv, 1, 8)
        srv.drain()
        _submit(srv, 1, 12)
        srv.drain()
        assert srv.stats.summary()["e2e_latency_s"]["p50"] > 0.0
        records = srv.take_completion_stamps()
        assert srv.stats.summary()["e2e_latency_s"]["p50"] == 0.0
    finally:
        srv.close()
    by_epoch = {}
    for r in records:
        assert r.producer == "p0"
        by_epoch.setdefault(r.epoch, {})[r.table] = r
    assert sorted(by_epoch) == [0, 1]
    assert by_epoch[0]["a"].local_seq.tolist() == [0, 1, 2, 3]
    assert by_epoch[1]["a"].local_seq.tolist() == list(range(6))
    assert by_epoch[1]["a"].submitted.min() > by_epoch[0]["a"].completed.max()


def test_take_keeps_what_is_pending():
    stamps = CompletionStamps()

    def gseqs(local):                      # producer 1's packed ids
        return np.asarray(local) * SEQ_STRIDE + 1

    for local in range(3000):              # past the first capacity
        stamps.submitted("t", int(gseqs(local)), float(local))
    stamps.completed("t", gseqs(np.arange(1000)), 5000.0)
    stamps.completed("t", gseqs([1500]), 6000.0)
    stamps.dropped("t", int(gseqs(1000)))
    first = stamps.take()
    assert [(r.producer, r.table, r.local_seq.size) for r in first] == [(1, "t", 1001)]
    assert first[0].local_seq[-1] == 1500
    assert first[0].submitted[-1] == 1500.0 and first[0].completed[-1] == 6000.0
    # what is left starts at the first pending bag, 1001
    rest = np.asarray([s for s in range(1001, 3000) if s != 1500])
    stamps.completed("t", gseqs(rest), 7000.0)
    second = stamps.take()
    assert second[0].local_seq.tolist() == rest.tolist()
    assert np.all(second[0].completed == 7000.0)
    assert np.array_equal(second[0].submitted, rest.astype(float))
    assert stamps.take() == [] and stamps.latencies().size == 0


def test_setup_timings_split_the_plan_build():
    import time

    t0 = time.perf_counter()
    srv = _server()
    built = time.perf_counter() - t0
    srv.close()
    t = srv.setup_timings
    assert set(t) == {"cooccurrence", "grouping", "placement"}
    assert all(v > 0.0 for v in t.values())
    assert sum(t.values()) <= built


def test_each_flush_opens_its_spans_on_the_engine(tmp_path):
    import jax
    from jax.profiler import ProfileData

    srv = _server()
    try:
        _submit(srv, 1, 16)               # warm: compile the flush programs
        srv.drain()
        b0 = srv.stats.batches
        jax.profiler.start_trace(str(tmp_path))
        try:
            _submit(srv, 1, 48)
            srv.drain()
        finally:
            jax.profiler.stop_trace()
        flushes = srv.stats.batches - b0
    finally:
        srv.close()
    path = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("recross."):
                    events.setdefault(e.name, []).append(
                        ((plane.name, k), dict(e.stats)["flush"]))
    assert flushes > 0
    for name in ("recross.compile", "recross.dispatch", "recross.retire"):
        assert len(events[name]) == flushes, name
        assert sorted(f for _, f in events[name]) == list(range(b0, b0 + flushes))
    assert len(events["recross.barrier"]) >= 1
    # all on one thread's line: the driver's
    assert len({line for evs in events.values() for line, _ in evs}) == 1


# ---------------------------------------------- chunked routing (§7.2) --


def _held(srv):
    """Holds the driver inside its first run until the returned event is
    set, so the producer fills the hand-off queue meanwhile."""
    release = threading.Event()
    real = srv._ingest_many

    def held(*args, **kw):
        release.wait(10.0)
        real(*args, **kw)

    srv._ingest_many = held
    return release


def _stream(n, seed=400):
    return [("ab"[i % 3 % 2], q) for i, q in
            enumerate(zipf_queries(ROWS, n, 5.0, seed=seed))]


def test_full_handoff_routes_in_chunks_like_the_inline_engine():
    """A driver that finds a full hand-off routes runs of bags at once,
    and serves the same rows, flushes and per-home flush counts as the
    inline engine routing bag by bag."""
    stream = _stream(120)
    inline = _server(threaded=False, batch_size=16)
    threaded = _server(batch_size=16)
    release = _held(threaded)
    try:
        for srv in (inline, threaded):
            for table, q in stream:
                srv.submit(table, q)
        release.set()
        want, got = inline.drain(), threaded.drain()
        assert sorted(got) == sorted(want)
        for table in want:
            np.testing.assert_array_equal(np.asarray(got[table]),
                                          np.asarray(want[table]))
        assert threaded.stats.batches == inline.stats.batches
        assert threaded.stats.shard_flushes == inline.stats.shard_flushes
        st = threaded.stats.summary()
        assert st["routed"] == len(stream)
        assert st["route_chunks"] < st["routed"]
        assert st["routed"] / st["route_chunks"] > 1.0
        assert inline.stats.route_chunks == inline.stats.routed == len(stream)
    finally:
        inline.close()
        threaded.close()


def test_chunk_route_time_leaves_out_the_flushes_it_triggers():
    """Flushes triggered inside a run, made 20 ms slower each, stay out
    of route_s on the driver thread too."""
    srv = _server(batch_size=8)
    release = _held(srv)
    real = srv._flush_home
    calls = []

    def slow(*args, **kw):
        calls.append(1)
        threading.Event().wait(0.02)
        real(*args, **kw)

    srv._flush_home = slow
    try:
        for table, q in _stream(60):
            srv.submit(table, q)
        release.set()
        srv.drain()
        st = srv.stats
        assert st.routed == 60 and st.route_chunks < 60 and len(calls) > 2
        assert 0.0 < st.route_s < 0.02 * len(calls)
    finally:
        srv.close()


def test_barrier_token_inside_a_run_keeps_its_fifo_place():
    """A drain() posted between two runs of queued bags serves exactly
    the bags before it; the bags after it wait for the next drain."""
    from repro.core.reduction import reduce_dense_oracle

    srv = _server(batch_size=16)
    release = _held(srv)
    first, second = _stream(40), _stream(30, seed=401)
    try:
        for table, q in first:
            srv.submit(table, q)
        out = {}
        drainer = threading.Thread(target=lambda: out.update(srv.drain()))
        drainer.start()
        deadline = time.monotonic() + 10.0
        while not any(item[0] == "barrier"  # the token is queued
                      for item in list(srv._handoff.queue)):
            assert time.monotonic() < deadline
            threading.Event().wait(0.001)
        for table, q in second:
            srv.submit(table, q)
        release.set()
        drainer.join(10.0)
        assert not drainer.is_alive()
        for part, rows in ((first, out), (second, srv.drain())):
            for table in "ab":
                qs = [q for t, q in part if t == table]
                np.testing.assert_array_equal(
                    np.asarray(rows[table]),
                    np.asarray(reduce_dense_oracle(TABLES[table], qs)))
        assert srv._handoff.unfinished_tasks == 0
    finally:
        srv.close()


def test_a_compile_fault_inside_a_run_is_requeued_and_surfaced():
    """Under the legacy policy a compile failure on the driver, inside a
    run, requeues its batch, routes the rest of the run, marks every
    item done and surfaces at the next drain, which then serves all."""
    from repro.core.reduction import reduce_dense_oracle

    srv = _server(batch_size=8, retry=RetryPolicy.legacy())
    release = _held(srv)
    real = srv._compile_and_dispatch
    calls = {"n": 0}

    def flaky(entries, participants):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("transient compile error")
        return real(entries, participants)

    srv._compile_and_dispatch = flaky
    stream = _stream(48)
    try:
        for table, q in stream:
            srv.submit(table, q)
        release.set()
        with pytest.raises(RuntimeError, match="transient compile error"):
            srv.drain()
        assert srv._handoff.unfinished_tasks == 0
        assert srv.stats.routed == len(stream)
        rows = srv.drain()
        for table in "ab":
            qs = [q for t, q in stream if t == table]
            np.testing.assert_array_equal(
                np.asarray(rows[table]),
                np.asarray(reduce_dense_oracle(TABLES[table], qs)))
    finally:
        srv.close()


def test_flush_compile_makes_no_transfer(monkeypatch):
    """The host block compile stays on the host: with device→host pulls
    disallowed and every way of making a device array made to raise, it
    returns NumPy arrays only.  (On the CPU the guard alone lets
    zero-copy pulls and ``jnp.asarray`` through.)"""
    srv = _server(threaded=False)

    def no_device_array(*args, **kw):
        raise AssertionError("the host compile made a device array")

    try:
        bags = {n: list(zipf_queries(ROWS, 11, 5.0, seed=s))
                for n, s in (("a", 501), ("b", 502))}
        with monkeypatch.context() as m:
            for mod, name in ((jax, "device_put"), (jax.numpy, "asarray"),
                              (jax.numpy, "array")):
                m.setattr(mod, name, no_device_array)
            with jax.transfer_guard_device_to_host("disallow"):
                fused, sbq = srv._compile_batch(["a", "b"], bags)
                subset = srv._compile_batch(
                    ["a"], {"a": bags["a"]}, participants=[0, 1])[1]
        for arr in (sbq.tile_ids, sbq.bitmaps, subset.tile_ids,
                    subset.bitmaps, fused.tile, fused.rows):
            assert isinstance(arr, np.ndarray)
        assert sbq.bitmaps.dtype == np.int8
        assert fused.spans == [(0, 11), (12, 11)]
    finally:
        srv.close()


def test_each_flush_puts_its_schedule_once(monkeypatch):
    """A threaded server puts each flush's schedule in one transfer,
    counted in ``schedule_puts`` / ``schedule_bytes``, and still serves
    the reference sums."""
    import repro.serve.sharded as sharded

    put = []
    real = sharded.put_schedule

    def counted(images, tile_ids, bitmaps, **kw):
        out = real(images, tile_ids, bitmaps, **kw)
        put.append(sum(int(a.nbytes) for a in out))
        assert bitmaps.dtype == np.int8
        return out

    monkeypatch.setattr(sharded, "put_schedule", counted)
    srv = _server()
    try:
        bags = {n: list(zipf_queries(ROWS, 40, 5.0, seed=s))
                for n, s in (("a", 511), ("b", 512))}
        for i in range(40):
            for n in ("a", "b"):
                srv.submit(n, bags[n][i])
        out = srv.drain()
        st = srv.stats
        assert st.batches > 1
        assert st.schedule_puts == st.batches == len(put)
        assert st.schedule_bytes == sum(put)
        assert srv.report()["serve"]["schedule_puts"] == st.batches
        for n in ("a", "b"):
            want = np.stack([TABLES[n][np.unique(q)].sum(axis=0)
                             for q in bags[n]])
            np.testing.assert_array_equal(np.asarray(out[n]), want)
    finally:
        srv.close()
