"""Sharded multi-table serving launcher (real shard_map over the devices).

Builds a ``(1, num_shards)`` (data, model) mesh over the visible devices
(on the CPU platform, ``JAX_PLATFORMS=cpu``, it first forces the host to
present ``--shards`` devices), stands up a
:class:`~repro.serve.sharded.ShardedEmbeddingServer` over synthetic
Zipf-weighted tables, and drives a continuous stream of per-table
queries through the batched flush path.  Prints the per-shard grid
cells / combine bytes / wall time report.

Usage::

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve_sharded \
        --shards 4 --tables 2                 # 4 forced host devices
    PYTHONPATH=src python -m repro.launch.serve_sharded --emulate   # no mesh
    PYTHONPATH=src python -m repro.launch.serve_sharded --emulate --drift
    PYTHONPATH=src python -m repro.launch.serve_sharded --emulate \
        --flush-policy deadline --skew 3   # async per-shard pipelining
    PYTHONPATH=src python -m repro.launch.serve_sharded --shards 4 \
        --flush-policy owner-set --threaded   # owner-set homes + driver
                                              # thread (non-blocking submit)
    PYTHONPATH=src python -m repro.launch.serve_sharded --emulate \
        --flush-policy per-shard --threaded --producers 4
                                              # 4 concurrent producer
                                              # threads, per-producer
                                              # sequence spaces (§10)
    PYTHONPATH=src python -m repro.launch.serve_sharded --emulate \
        --flush-policy per-shard --threaded \
        --inject compile:2,device:1,poison:1,hang:1 \
        --inject-seed 0 --watchdog 2.0        # seeded chaos replay: the
                                              # engine heals (DESIGN.md §8)
    PYTHONPATH=src python -m repro.launch.serve_sharded --emulate \
        --flush-policy deadline --capacity-frac 0.25 --drift
                                              # tiered hot/cold storage:
                                              # device holds 1/4 of the
                                              # working set, drift pages
                                              # groups in/out (§9)

``--drift`` enables the drifting-workload replay (DESIGN.md §6): after
``--drift-at`` of the request stream, row ids are remapped through a
fixed permutation — the hot set rotates onto previously-cold rows — and
the server's online replanner (enabled with the ``--replan-*`` knobs)
incrementally promotes/demotes groups instead of rebuilding the plan.
The report then includes the replan counters (patches applied, tiles
DMA'd, residual drift).

The module is import-safe: args are parsed and ``XLA_FLAGS`` is set only
when run as ``__main__`` (the device-count flag must land before the
first jax import, so :func:`main` defers its jax-touching imports).
:func:`main` keeps compiled programs in the persistent compilation cache
(:mod:`repro.launch.compile_cache`).
"""

from __future__ import annotations

import argparse
import json
import os


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--tables", type=int, default=2)
    ap.add_argument("--rows", type=int, default=2048)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--history", type=int, default=2048)
    ap.add_argument("--requests", type=int, default=512)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--q-block", type=int, default=8)
    ap.add_argument("--group-size", type=int, default=64)
    ap.add_argument("--mean-bag", type=float, default=12.0)
    ap.add_argument("--combine", choices=["psum_scatter", "psum"],
                    default="psum_scatter")
    ap.add_argument("--combine-chunks", type=int, default=2)
    ap.add_argument("--flush-policy",
                    choices=["global", "per-shard", "deadline", "owner-set"],
                    default="global",
                    help="global: synchronous fused flushes (PR-2 path); "
                         "per-shard/deadline: shards flush independently "
                         "as their block unions fill, host compile "
                         "pipelined against device execution; owner-set: "
                         "multi-owner queries additionally key their home "
                         "by the frozen owner set, so a 2-owner flush "
                         "compiles (and combines over) exactly 2 shards "
                         "(DESIGN.md §7)")
    ap.add_argument("--owner-set-max", type=int, default=None,
                    help="owner-set policy: sets larger than this pool "
                         "up instead of getting their own home (None: "
                         "every multi-owner set is keyed; 2-3 keeps the "
                         "high-value small-set homes and avoids "
                         "fragmenting near-mesh traffic)")
    ap.add_argument("--producers", type=int, default=1,
                    help="concurrent producer threads sharing the server "
                         "(DESIGN.md §10): the request stream splits "
                         "round-robin, each thread submits under its own "
                         "producer label (its own sequence space), and "
                         "the final drain merges the streams in the "
                         "deterministic (local_seq, producer_id) order. "
                         "> 1 requires an async --flush-policy; pair "
                         "with --threaded for the non-blocking front "
                         "door")
    ap.add_argument("--threaded", action="store_true",
                    help="run the async engine on a driver thread: "
                         "submit() only validates + enqueues (bounded "
                         "hand-off queue) and never blocks on a full "
                         "in-flight pipeline; submit-side p50/p95/p99 "
                         "land in the report (DESIGN.md §7.2)")
    ap.add_argument("--union-budget", type=int, default=None,
                    help="per-shard block-union fill that triggers an "
                         "independent flush (None: batch-size/deadline "
                         "triggers only)")
    ap.add_argument("--flush-deadline", type=int, default=None,
                    help="max submissions a pending query waits before a "
                         "forced flush (deadline policy; default 4x "
                         "batch-size)")
    ap.add_argument("--max-in-flight", type=int, default=2,
                    help="bound on dispatched-but-unretired async flushes")
    ap.add_argument("--skew", type=float, default=1.0,
                    help="per-table arrival skew: table i receives "
                         "weight skew^-i of the request stream (1.0 = "
                         "uniform); skewed arrivals are where per-shard "
                         "flushing beats the global policy")
    ap.add_argument("--emulate", action="store_true",
                    help="single-device shard loop instead of shard_map")
    ap.add_argument("--drift", action="store_true",
                    help="drifting-workload replay: rotate the hot set "
                         "mid-stream and replan online")
    ap.add_argument("--drift-at", type=float, default=0.5,
                    help="fraction of the stream after which rows remap")
    ap.add_argument("--drift-seed", type=int, default=7)
    ap.add_argument("--replan-threshold", type=float, default=0.2)
    ap.add_argument("--replan-half-life", type=float, default=4.0)
    ap.add_argument("--replan-min-queries", type=int, default=64)
    ap.add_argument("--slack-tiles", type=int, default=8,
                    help="per-shard zero-tile image headroom for promotions")
    ap.add_argument("--capacity-frac", type=float, default=None,
                    help="tiered storage (DESIGN.md §9): cap the per-shard "
                         "hot-tier image at this fraction of what an "
                         "uncapped plan would need — 0.25 means the device "
                         "holds a quarter of the working set; cold queries "
                         "serve via the host gather+sum path and drift-"
                         "driven paging swaps groups in/out at flush "
                         "barriers (None: untiered, everything resident)")
    ap.add_argument("--capacity-tiles", type=int, default=None,
                    help="absolute per-shard hot-tier budget in tiles "
                         "(alternative to --capacity-frac)")
    ap.add_argument("--tier-hysteresis", type=float, default=1.5,
                    help="load ratio a cold group must beat over its "
                         "eviction victim to page in (anti-thrash; >= 1)")
    ap.add_argument("--host-batch", type=int, default=None,
                    help="cold queries buffered before a host-path flush "
                         "(default: --batch-size)")
    ap.add_argument("--host-deadline", type=int, default=None,
                    help="max submissions a queued cold query waits before "
                         "a forced host flush (default: 4x host batch)")
    ap.add_argument("--inject", default=None, metavar="KIND:N[,KIND:N...]",
                    help="chaos replay (DESIGN.md §8): inject a seeded, "
                         "deterministic fault schedule, e.g. "
                         "'compile:2,device:1,poison:2,hang:1'.  Kinds: "
                         "compile (transient host-compile failure), "
                         "device (fault at dispatch), device-late (fault "
                         "at retire), hang (flush never reports ready — "
                         "pair with --watchdog), poison (a (table, seq) "
                         "query that fails every containing batch until "
                         "bisection quarantines it), patch (staged plan "
                         "patch fails to apply).  The self-healing "
                         "policy retries/bisects/degrades; the report's "
                         "'faults' section shows the ledger")
    ap.add_argument("--inject-seed", type=int, default=0,
                    help="fault-plan draw + retry-jitter seed (same seed "
                         "+ same replay = same faults: replayable chaos)")
    ap.add_argument("--inject-hang-s", type=float, default=None,
                    help="simulated hang duration for injected 'hang' "
                         "faults (default: forever — the watchdog's job)")
    ap.add_argument("--watchdog", type=float, default=None,
                    help="per-flush watchdog deadline in seconds: a "
                         "flush not ready by then is timed out and "
                         "served degraded via the inline host path "
                         "(None: no watchdog)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="in-place re-dispatch attempts per failed flush "
                         "before bisection/quarantine (0 + the other "
                         "defaults still bisects; see RetryPolicy)")
    return ap.parse_args(argv)


def build_fault_plan(args, table_names, requests):
    """``--inject 'compile:2,poison:1'`` → a seeded FaultPlan (None when
    no injection was requested)."""
    if not args.inject:
        return None
    from repro.serve.faults import FaultPlan

    counts = {}
    for part in args.inject.split(","):
        part = part.strip()
        if not part:
            continue
        kind, _, n = part.partition(":")
        counts[kind.strip()] = int(n) if n else 1
    per_table = max(1, requests // max(1, len(table_names)))
    producers = (
        tuple(f"p{i}" for i in range(args.producers))
        if args.producers > 1 else ()
    )
    return FaultPlan.random(
        args.inject_seed, counts,
        horizon=max(4, requests // max(1, args.batch_size)),
        tables=tuple(table_names),
        max_seq=max(1, per_table // max(1, args.producers)),
        hang_s=args.inject_hang_s,
        producers=producers,
    )


def main(args) -> None:
    # deferred: jax must initialize AFTER the XLA_FLAGS device forcing
    import numpy as np
    import jax

    from repro.data import zipf_queries
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve.sharded import ShardedEmbeddingServer

    enable_compile_cache()
    rng = np.random.default_rng(0)
    tables = {
        f"t{i}": rng.normal(size=(args.rows, args.dim)).astype(np.float32)
        for i in range(args.tables)
    }
    histories = {
        name: zipf_queries(args.rows, args.history, args.mean_bag, seed=i)
        for i, name in enumerate(tables)
    }

    mesh = None
    if not args.emulate:
        devices = jax.devices()
        if len(devices) < args.shards:
            hint = (
                "run with JAX_PLATFORMS=cpu to force host devices"
                if devices[0].platform == "cpu" else
                f"this host has {len(devices)} {devices[0].platform} "
                f"device(s)"
            )
            raise SystemExit(
                f"--shards {args.shards} needs {args.shards} devices, "
                f"found {len(devices)}: {hint}, or pass --emulate"
            )
        mesh = jax.make_mesh(
            (1, args.shards), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2,
        )

    replan_cfg = None
    if args.drift:
        from repro.serve.drift import ReplanConfig

        replan_cfg = ReplanConfig(
            threshold=args.replan_threshold,
            half_life=args.replan_half_life,
            min_queries=args.replan_min_queries,
            slack_tiles=args.slack_tiles,
        )
    from repro.serve.faults import RetryPolicy

    tiers_cfg = None
    if args.capacity_frac is not None or args.capacity_tiles is not None:
        from repro.serve.tiers import TierConfig

        tiers_cfg = TierConfig(
            capacity_tiles=args.capacity_tiles,
            capacity_frac=args.capacity_frac,
            hysteresis=args.tier_hysteresis,
            host_batch=args.host_batch,
            host_deadline=args.host_deadline,
        )
    fault_plan = build_fault_plan(args, list(tables), args.requests)
    server = ShardedEmbeddingServer(
        tables, histories,
        num_shards=args.shards, mesh=mesh,
        q_block=args.q_block, group_size=args.group_size,
        batch_size=args.batch_size,
        combine=args.combine, combine_chunks=args.combine_chunks,
        replan=replan_cfg,
        flush_policy=args.flush_policy,
        union_budget=args.union_budget,
        flush_deadline=args.flush_deadline,
        owner_set_max=args.owner_set_max,
        max_in_flight=args.max_in_flight,
        threaded=args.threaded,
        retry=RetryPolicy(max_retries=args.max_retries,
                          watchdog_s=args.watchdog,
                          seed=args.inject_seed),
        faults=fault_plan,
        tiers=tiers_cfg,
    )

    stream = zipf_queries(args.rows, args.requests, args.mean_bag, seed=1234)
    if args.drift:
        # hot-set rotation: remap every row id through a fixed permutation
        # for the tail of the stream (serve-time drift the offline plan
        # never saw; the replanner must chase it incrementally)
        cut = int(len(stream) * args.drift_at)
        perm = np.random.default_rng(args.drift_seed).permutation(args.rows)
        stream = stream[:cut] + [
            perm[np.asarray(q, dtype=np.int64)] for q in stream[cut:]
        ]
    names = list(tables)
    # per-table arrival replay: uniform round robin at skew 1, weighted
    # choice otherwise (table i's arrival rate ∝ skew^-i) — tables fill
    # at different rates, so per-shard unions fill at different rates
    if args.skew != 1.0:
        w = np.power(float(args.skew), -np.arange(len(names)))
        pick = np.random.default_rng(5).choice(
            len(names), size=len(stream), p=w / w.sum()
        )
    else:
        pick = np.arange(len(stream)) % len(names)
    flushed = 0
    import time
    if args.producers > 1:
        # multi-producer front door (DESIGN.md §10): the stream splits
        # round-robin, each producer thread submits under its own label
        # (= its own sequence space) and the full drain at the end
        # merges the streams deterministically
        if args.flush_policy == "global":
            raise SystemExit("--producers > 1 requires an async "
                             "--flush-policy (per-shard/deadline/"
                             "owner-set)")
        import threading

        labels = [f"p{i}" for i in range(args.producers)]
        slices = {
            lab: [(names[int(pick[i])], stream[i])
                  for i in range(len(stream))
                  if i % args.producers == p]
            for p, lab in enumerate(labels)
        }
        # registration order pins producer ids (the merge tiebreak)
        # independently of which thread wins the first stamp
        for lab in labels:
            server.register_producer(lab)

        def run(lab):
            for name, q in slices[lab]:
                server.submit(name, q, producer=lab)

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=run, args=(lab,), name=lab)
            for lab in labels
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if server.drain():
            flushed += 1
        wall = time.perf_counter() - t0
    else:
        t0 = time.perf_counter()
        for i, q in enumerate(stream):
            out = server.submit(names[int(pick[i])], q)
            if out:
                flushed += 1
        if server.flush():
            flushed += 1
        wall = time.perf_counter() - t0

    server.close()
    report = server.report()
    report["flushes"] = flushed
    report["replay_wall_s"] = wall
    report["producers"] = args.producers
    print(json.dumps(report, indent=1, default=str))


if __name__ == "__main__":
    _args = parse_args()
    if not _args.emulate and "cpu" in os.environ.get("JAX_PLATFORMS", ""):
        # must precede the first jax import (inside main)
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={max(_args.shards, 1)} "
            + os.environ.get("XLA_FLAGS", "")
        )
    main(_args)
