"""Fault injection + self-healing flush pipeline (DESIGN.md §8).

The invariant under test: for any injected fault schedule whose faults
are retriable (transient compile/device faults, hangs), ``drain()``
returns rows BIT-IDENTICAL to the fault-free oracle — the engine heals,
it does not drop, duplicate or reorder.  Non-retriable faults (poisoned
queries) are bisected down to the single offender and quarantined with
their error; every other row still matches the oracle.  Bit-identity is
pinned on integer-valued float tables exactly as in test_scheduler.py.

The legacy requeue-and-re-raise contract (``RetryPolicy.legacy()``)
is pinned here too, via the injector, under both inline and threaded
drivers for shards {1, 2, 4} — the driver fault branches that were
previously uncoverable.
"""

import time

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.reduction import reduce_dense_oracle
from repro.data import zipf_queries
from repro.serve import (
    FaultInjector,
    FaultPlan,
    FaultSpec,
    FlushTimeout,
    InjectedFault,
    PoisonedQueryError,
    RetryPolicy,
    ShardedEmbeddingServer,
)

ROWS, DIM = 160, 128


def _int_table(seed):
    """Integer-valued f32 table: partial sums are exact in float32."""
    return np.random.default_rng(seed).integers(
        -8, 9, size=(ROWS, DIM)
    ).astype(np.float32)


TABLES = {"a": _int_table(11), "b": _int_table(12)}
HISTORIES = {"a": zipf_queries(ROWS, 48, 5.0, seed=13),
             "b": zipf_queries(ROWS, 48, 5.0, seed=14)}
STREAMS = {"a": zipf_queries(ROWS, 20, 5.0, seed=15),
           "b": zipf_queries(ROWS, 12, 5.0, seed=16)}
REPLAY = ([("a", q) for q in STREAMS["a"]]
          + [("b", q) for q in STREAMS["b"]])
#: fast-backoff policy so healing tests don't sleep for real
FAST = dict(backoff_base=1e-4, backoff_max=1e-3)


def _serve(replay=REPLAY, *, num_shards=2, batch_size=4, **kw):
    srv = ShardedEmbeddingServer(
        TABLES, HISTORIES, num_shards=num_shards, q_block=4,
        group_size=16, batch_size=batch_size, flush_policy="per-shard",
        **kw,
    )
    for name, q in replay:
        srv.submit(name, q)
    out = srv.drain()
    srv.close()
    return srv, out


def _oracle():
    return {n: np.asarray(reduce_dense_oracle(jnp.asarray(TABLES[n]),
                                              STREAMS[n]))
            for n in TABLES}


ORACLE = _oracle()


# ------------------------------------------------- plan / policy units --


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec("meteor")
    with pytest.raises(ValueError, match="table= and seq="):
        FaultSpec("poison")
    with pytest.raises(ValueError, match="times"):
        FaultSpec("compile", times=0)
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan.random(0, {"meteor": 1})
    with pytest.raises(ValueError, match="tables="):
        FaultPlan.random(0, {"poison": 1})
    with pytest.raises(TypeError):
        FaultInjector.parse("chaos")
    with pytest.raises(TypeError):
        RetryPolicy.parse("retry hard")


def test_fault_plan_random_is_seed_deterministic():
    counts = {"compile": 2, "device": 1, "poison": 2, "hang": 1}
    mk = lambda s: FaultPlan.random(
        s, counts, horizon=8, tables=("a", "b"), max_seq=20, hang_s=9.0)
    p1, p2, p3 = mk(5), mk(5), mk(6)
    assert p1.specs == p2.specs  # FaultSpec is frozen → value equality
    assert p1.specs != p3.specs
    assert p1.poisoned() == p2.poisoned()
    assert p1.summary()["faults"] == counts


def test_injector_attempt_windows():
    """tick=t, times=k fails attempts t..t+k-1 at that seam only."""
    plan = FaultPlan([], seed=0).add("compile", tick=1, times=2)
    inj = FaultInjector(plan)
    inj.on_compile([("a", 0, [1])])  # attempt 0: healthy
    for _ in range(2):               # attempts 1, 2: injected
        with pytest.raises(InjectedFault):
            inj.on_compile([("a", 0, [1])])
    inj.on_compile([("a", 0, [1])])  # attempt 3: healed
    assert inj.injected["compile"] == 2
    # the poison set fires regardless of attempt index, forever
    inj2 = FaultInjector(FaultPlan([], seed=0).add("poison", table="a", seq=3))
    for _ in range(3):
        with pytest.raises(PoisonedQueryError):
            inj2.on_compile([("a", 3, [1]), ("a", 4, [2])])
    inj2.on_compile([("a", 4, [2])])  # offender absent: healthy


def test_retry_policy_backoff_and_legacy():
    with pytest.raises(ValueError, match="max_retries"):
        RetryPolicy(max_retries=-1)
    with pytest.raises(ValueError, match="jitter"):
        RetryPolicy(jitter=1.5)
    with pytest.raises(ValueError, match="watchdog_s"):
        RetryPolicy(watchdog_s=0.0)
    p = RetryPolicy(backoff_base=0.01, backoff_mult=2.0, backoff_max=0.05,
                    jitter=0.0)
    rng = np.random.default_rng(0)
    waits = [p.backoff_s(a, rng) for a in range(5)]
    assert waits[:3] == [0.01, 0.02, 0.04]
    assert waits[3] == waits[4] == 0.05  # capped
    pj = RetryPolicy(backoff_base=0.01, jitter=0.25)
    for a in range(4):
        w = pj.backoff_s(a, rng)
        base = min(0.01 * 2.0 ** a, pj.backoff_max)
        assert 0.75 * base <= w <= 1.25 * base
    leg = RetryPolicy.legacy()
    assert leg.max_retries == 0 and not leg.bisect and not leg.quarantine
    assert RetryPolicy.parse(None) == RetryPolicy()
    assert RetryPolicy.parse(leg) is leg


# ------------------------- legacy driver fault branches (satellite 3) --


@pytest.mark.parametrize("num_shards", [1, 2, 4])
@pytest.mark.parametrize("threaded", [False, True])
@pytest.mark.parametrize("kind", ["compile", "device"])
def test_legacy_requeue_and_reraise_branches(num_shards, threaded, kind):
    """The pre-§8 contract, provoked by the injector instead of
    monkeypatching: a dispatch-time fault requeues the batch, the error
    surfaces (inline: at submit; threaded: at the next submit or
    drain), and a
    later drain retries the requeued work — every row served, in
    order, bit-identical to the oracle."""
    plan = FaultPlan([], seed=1).add(kind, tick=0, times=1)
    srv = ShardedEmbeddingServer(
        TABLES, HISTORIES, num_shards=num_shards, q_block=4,
        group_size=16, batch_size=4, flush_policy="per-shard",
        threaded=threaded, retry=RetryPolicy.legacy(), faults=plan,
    )
    raised = None
    for name, q in REPLAY:
        try:
            srv.submit(name, q)
        except InjectedFault as e:
            raised = e
            if threaded:
                # a failure stashed by the driver surfaces at the top
                # of the next submit(), which then accepts nothing
                srv.submit(name, q)
    if threaded and raised is None:
        # the failure happened on the driver thread; it surfaces at the
        # next submit()/drain() instead of the submit that tripped it —
        # here the replay's submits all ran before it was stashed
        with pytest.raises(InjectedFault):
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                srv.drain()
                time.sleep(0.005)
            # a single slow drain (cold compiles under suite-wide
            # load) can eat the whole budget AFTER the driver stashed
            # the error; drain once more so the stash still surfaces
            srv.drain()
            raise AssertionError("driver never surfaced the failure")
    else:
        assert raised is not None, "inline legacy must re-raise at submit"
    assert srv.scheduler.requeues >= 1
    out = srv.drain()  # retry: the fault was transient (times=1)
    got = {n: np.asarray(out[n]) for n in out}
    # rows served across the failed attempt + retry must total the
    # oracle, in submission order
    for n in TABLES:
        np.testing.assert_array_equal(got[n], ORACLE[n])
    led = srv.stats.ledger
    assert not led.quarantined and led.retries == 0  # legacy never heals
    srv.close()


@pytest.mark.parametrize("threaded", [False, True])
def test_legacy_late_device_fault_requeues_at_retire(threaded):
    """A device fault surfacing only at retire (outputs lost) requeues
    the already-dispatched batch under the legacy policy and re-raises;
    the next drain re-dispatches it."""
    plan = FaultPlan([], seed=2).add("device-late", tick=0, times=1)
    srv = ShardedEmbeddingServer(
        TABLES, HISTORIES, num_shards=2, q_block=4, group_size=16,
        batch_size=4, flush_policy="per-shard", threaded=threaded,
        retry=RetryPolicy.legacy(), faults=plan,
    )
    # inline: the fault can surface at a submit that trims the pipeline;
    # threaded: it is stashed and surfaces at a drain.  Either way the
    # batch requeues and a later drain must serve EVERY row exactly once.
    raised = False
    for name, q in REPLAY:
        try:
            srv.submit(name, q)
        except InjectedFault:
            raised = True
    outs = []
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        try:
            out = srv.drain()
        except InjectedFault:
            raised = True
            continue
        outs.append(out)
        if raised and srv.scheduler.pending_total() == 0:
            break
    assert raised, "retire fault never surfaced"
    assert srv.scheduler.requeues >= 1
    got = {}
    for out in outs:
        for n, rows in out.items():
            got.setdefault(n, []).append(np.asarray(rows))
    for n in TABLES:
        served = np.concatenate(got[n]) if n in got else np.empty((0, DIM))
        # all rows served exactly once; cross-drain order may interleave
        # (the requeued batch retries behind later flushes), so compare
        # as multisets of rows via lexicographic sort
        assert served.shape == ORACLE[n].shape
        np.testing.assert_array_equal(
            served[np.lexsort(served.T)], ORACLE[n][np.lexsort(ORACLE[n].T)]
        )
    srv.close()


# ------------------------------------------- self-healing bit-identity --


@pytest.mark.parametrize("num_shards", [1, 2, 4])
def test_healing_transient_faults_bit_identical(num_shards):
    """Transient compile + device + late-device faults: the default
    policy retries in place, nothing surfaces to the caller, and
    drain() is bit-identical to the fault-free oracle."""
    plan = (FaultPlan([], seed=3)
            .add("compile", tick=0, times=2)
            .add("device", tick=2, times=1)
            .add("device-late", tick=1, times=1))
    srv, out = _serve(num_shards=num_shards,
                      retry=RetryPolicy(max_retries=3, **FAST),
                      faults=plan)
    for n in TABLES:
        np.testing.assert_array_equal(np.asarray(out[n]), ORACLE[n])
    led = srv.stats.ledger
    assert led.retries >= 3
    assert led.backoff_s > 0
    assert not led.quarantined
    assert led.recovery_s, "healed transients must record recovery latency"
    summ = srv.stats.summary()["faults"]
    assert summ["recoveries"] == len(led.recovery_s)
    assert summ["recovery_latency_s"]["p50"] > 0


@pytest.mark.parametrize("threaded", [False, True])
def test_poison_bisected_and_quarantined(threaded):
    """One poisoned query fails every batch containing it without
    naming itself; bisection isolates it, quarantines it with its
    error, and every OTHER row still matches the oracle."""
    plan = FaultPlan([], seed=5).add("poison", table="a", seq=3)
    srv, out = _serve(threaded=threaded,
                      retry=RetryPolicy(max_retries=1, **FAST),
                      faults=plan)
    led = srv.stats.ledger
    assert led.quarantined_keys() == [("a", 3)]
    assert "PoisonedQueryError" in led.quarantined[0][2]
    assert led.bisections >= 1
    assert srv.scheduler.quarantined == 1
    assert srv.scheduler.state()["quarantined"] == 1
    keep = np.asarray([i for i in range(len(STREAMS["a"])) if i != 3])
    np.testing.assert_array_equal(np.asarray(out["a"]), ORACLE["a"][keep])
    np.testing.assert_array_equal(np.asarray(out["b"]), ORACLE["b"])


def test_quarantine_without_bisection_drops_whole_batch():
    """bisect=False still unwedges the home — the whole failing batch
    quarantines (every entry recorded), the rest of the replay serves."""
    plan = FaultPlan([], seed=6).add("poison", table="b", seq=0)
    srv, out = _serve(retry=RetryPolicy(max_retries=0, bisect=False, **FAST),
                      faults=plan)
    led = srv.stats.ledger
    assert led.bisections == 0
    assert ("b", 0) in led.quarantined_keys()
    assert len(led.quarantined) >= 1
    # without bisection the whole mixed batch drops — possibly entries
    # of BOTH tables; the survivors must still match the oracle rows
    for n in TABLES:
        dropped = {s for t, s in led.quarantined_keys() if t == n}
        keep = np.asarray([i for i in range(len(STREAMS[n]))
                           if i not in dropped])
        np.testing.assert_array_equal(np.asarray(out[n]), ORACLE[n][keep])


# ------------------------------------------------- watchdog / degrade --


@pytest.mark.parametrize("threaded", [False, True])
def test_watchdog_degrades_hung_flush(threaded):
    """An (effectively) infinite hang trips the watchdog: the flush is
    served via the inline host path instead of blocking drain()
    forever, and the rows are STILL bit-identical to the oracle."""
    plan = FaultPlan([], seed=7).add("hang", tick=1, hang_s=999.0)
    t0 = time.monotonic()
    srv, out = _serve(threaded=threaded,
                      retry=RetryPolicy(max_retries=1, watchdog_s=0.2,
                                        **FAST),
                      faults=plan)
    assert time.monotonic() - t0 < 60.0, "watchdog failed to bound drain"
    led = srv.stats.ledger
    assert led.timed_out_flushes >= 1
    assert led.degraded_flushes >= 1
    for n in TABLES:
        np.testing.assert_array_equal(np.asarray(out[n]), ORACLE[n])


def test_infinite_hang_without_watchdog_still_degrades():
    """hang_s=None simulates a device that never reports ready; with no
    watchdog configured the engine must still degrade (an injected
    infinite hang may never wedge drain())."""
    plan = FaultPlan([], seed=8).add("hang", tick=0)
    srv, out = _serve(retry=RetryPolicy(max_retries=0, **FAST), faults=plan)
    assert srv.stats.ledger.degraded_flushes >= 1
    for n in TABLES:
        np.testing.assert_array_equal(np.asarray(out[n]), ORACLE[n])


def test_short_hang_recovers_without_degrade():
    """A hang shorter than the watchdog deadline just waits it out —
    no timeout, no degrade, device outputs used."""
    plan = FaultPlan([], seed=9).add("hang", tick=0, hang_s=0.05)
    srv, out = _serve(retry=RetryPolicy(watchdog_s=5.0, **FAST),
                      faults=plan)
    led = srv.stats.ledger
    assert led.timed_out_flushes == 0 and led.degraded_flushes == 0
    for n in TABLES:
        np.testing.assert_array_equal(np.asarray(out[n]), ORACLE[n])


# ------------------------------------------------------- patch seam --


def _patch_barrier(srv):
    srv._staged = object()  # sentinel: dropped/kept, never applied
    srv._apply_staged_patch()


def test_patch_fault_retries_then_drops():
    """A failing staged patch is retried at the next barriers, then
    dropped (recorded) — the server keeps serving under the live plan.
    The sentinel staged object must never reach the real apply path."""
    plan = FaultPlan([], seed=10).add("patch", tick=0, times=3)
    srv = ShardedEmbeddingServer(
        TABLES, HISTORIES, num_shards=2, q_block=4, group_size=16,
        batch_size=4, flush_policy="per-shard",
        retry=RetryPolicy(patch_retries=1, **FAST), faults=plan,
    )
    staged = object()
    srv._staged = staged
    srv._apply_staged_patch()                 # failure 1: kept staged
    assert srv._staged is staged
    srv._apply_staged_patch()                 # failure 2 > patch_retries
    assert srv._staged is None
    led = srv.stats.ledger
    assert led.patch_failures == 2 and led.patches_dropped == 1
    # legacy policy: the patch failure re-raises instead
    srv2 = ShardedEmbeddingServer(
        TABLES, HISTORIES, num_shards=2, q_block=4, group_size=16,
        batch_size=4, flush_policy="per-shard",
        retry=RetryPolicy.legacy(),
        faults=FaultPlan([], seed=11).add("patch", tick=0),
    )
    srv2._staged = object()
    with pytest.raises(InjectedFault):
        srv2._apply_staged_patch()


# ---------------------------------- error stashing + close (sat. 1/2) --


def test_driver_error_stash_is_bounded_and_ordered():
    """A burst of driver failures: the FIRST surfaces first with the
    count of the rest; the deque is bounded and overflow is counted,
    never silently dropped; later calls surface the rest in order."""
    srv = ShardedEmbeddingServer(
        TABLES, HISTORIES, num_shards=1, q_block=4, group_size=16,
        batch_size=4, flush_policy="per-shard",
    )
    for i in range(12):
        srv._stash_driver_error(RuntimeError(f"boom {i}"))
    assert len(srv._driver_errors) == 8
    assert srv._suppressed_errors == 4
    assert srv.stats.ledger.driver_errors_suppressed == 4
    with pytest.raises(RuntimeError, match=r"boom 0.*\+11 more.*4 suppressed"):
        srv._raise_driver_error()
    with pytest.raises(RuntimeError, match=r"boom 1.*\+10 more"):
        srv._raise_driver_error()
    for i in range(2, 8):
        with pytest.raises(RuntimeError, match=f"boom {i}"):
            srv._raise_driver_error()
    srv._raise_driver_error()  # empty: no-op
    assert srv.stats.summary()["faults"]["driver_errors_suppressed"] == 4


def test_close_is_idempotent_and_reports_lost_work():
    """close() with work still queued: bounded, idempotent, and the
    unserved work is summarized into the ledger instead of silently
    discarded — a later drain() still serves every row inline."""
    srv = ShardedEmbeddingServer(
        TABLES, HISTORIES, num_shards=2, q_block=4, group_size=16,
        batch_size=10_000, flush_policy="per-shard", threaded=True,
    )
    for name, q in REPLAY:
        srv.submit(name, q)
    t0 = time.monotonic()
    srv.close()
    srv.close()  # idempotent
    assert time.monotonic() - t0 < ShardedEmbeddingServer._CLOSE_JOIN_S
    assert srv._driver is None and srv._handoff is None
    lost = srv.stats.ledger.lost_work
    assert lost is not None
    assert lost["requeued"] + lost["handoff_pushed_back"] >= len(REPLAY) \
        or srv.scheduler.pending_total() == len(REPLAY)
    assert lost["driver_leaked"] == 0
    assert srv.report()["serve"]["faults"]["lost_work"] == lost
    # nothing was dropped: the inline drain serves the whole backlog
    out = srv.drain()
    for n in TABLES:
        np.testing.assert_array_equal(np.asarray(out[n]), ORACLE[n])
    # close on a never-threaded server is a clean no-op
    srv2 = ShardedEmbeddingServer(
        TABLES, HISTORIES, num_shards=1, q_block=4, group_size=16,
        batch_size=4, flush_policy="per-shard",
    )
    srv2.close()
    srv2.close()
    assert srv2.stats.ledger.lost_work is None


# ------------------------------------------------ acceptance scenario --


def test_chaos_replay_threaded_acceptance():
    """ISSUE 6 acceptance: >= 3 fault kinds (transient device fault,
    compile failure, poisoned query) + a hung flush, on the THREADED
    driver.  drain() completes bit-identical to the fault-free oracle
    minus exactly the injected offender; the ledger shows nonzero
    retries and exactly the offenders quarantined; the hang degrades
    via the watchdog instead of blocking drain() forever."""
    plan = (FaultPlan([], seed=3)
            .add("compile", tick=0, times=2)
            .add("device", tick=2, times=1)
            .add("poison", table="a", seq=5)
            .add("hang", tick=4, hang_s=999.0))
    t0 = time.monotonic()
    srv, out = _serve(threaded=True,
                      retry=RetryPolicy(max_retries=3, watchdog_s=0.2,
                                        **FAST),
                      faults=plan)
    assert time.monotonic() - t0 < 120.0
    led = srv.stats.ledger
    assert led.retries > 0
    assert led.quarantined_keys() == plan.poisoned() == [("a", 5)]
    assert led.timed_out_flushes >= 1 and led.degraded_flushes >= 1
    keep = np.asarray([i for i in range(len(STREAMS["a"])) if i != 5])
    np.testing.assert_array_equal(np.asarray(out["a"]), ORACLE["a"][keep])
    np.testing.assert_array_equal(np.asarray(out["b"]), ORACLE["b"])
    rep = srv.report()
    assert rep["retry"]["max_retries"] == 3
    inj = rep["faults"]["injected"]
    assert inj["compile"] >= 2 and inj["device"] >= 1
    assert inj["poison"] >= 1 and inj["hang"] >= 1
    assert rep["serve"]["faults"]["quarantined"] == [["a", 5,
        led.quarantined[0][2]]]


# ---------------------------------------------- multi-producer chaos --


def test_poison_quarantines_only_offending_producer():
    """Multi-producer chaos replay (DESIGN.md §10): producers A and B
    submit the SAME stream concurrently, and a poison spec keyed to
    producer A's (table, local seq) must quarantine only A's offender —
    B's copy of the very same query serves, and B's drained stream
    stays bit-identical to the fault-free oracle."""
    import threading

    plan = FaultPlan([], seed=9).add("poison", table="a", seq=3,
                                     producer="A")
    assert plan.poisoned_by_producer() == [("A", "a", 3)]
    srv = ShardedEmbeddingServer(
        TABLES, HISTORIES, num_shards=2, q_block=4, group_size=16,
        batch_size=4, flush_policy="per-shard", threaded=True,
        retry=RetryPolicy(max_retries=1, **FAST), faults=plan,
    )
    for lab in ("A", "B"):
        srv.register_producer(lab)
    errs = []

    def body(lab):
        try:
            for q in STREAMS["a"]:
                srv.submit("a", q, producer=lab)
        except Exception as e:
            errs.append(e)

    threads = [threading.Thread(target=body, args=(lab,), daemon=True)
               for lab in ("A", "B")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "producer thread wedged"
    assert not errs, errs
    out = {lab: srv.drain(producer=lab) for lab in ("A", "B")}
    srv.close()
    led = srv.stats.ledger
    assert led.quarantined_keys_by_producer() == [("A", "a", 3)]
    assert "PoisonedQueryError" in led.quarantined[0][2]
    keep = np.asarray([i for i in range(len(STREAMS["a"])) if i != 3])
    np.testing.assert_array_equal(np.asarray(out["A"]["a"]),
                                  ORACLE["a"][keep])
    np.testing.assert_array_equal(np.asarray(out["B"]["a"]), ORACLE["a"])
