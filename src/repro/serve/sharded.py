"""Sharded multi-table embedding serving driver (DESIGN.md §4, §6).

Glues the offline pipeline to the sharded online path for a *set* of
DLRM embedding tables:

  per table: history → co-occurrence → grouping (Alg. 1) → Eq.-1
  log-scaled replication (``num_copies(g) = floor(log f_g / log f_total
  · log batch)``) → layout, then one :class:`~repro.dist.shard_plan.
  ShardPlan` over the fused tile space decides replicated-everywhere vs
  sharded-once tiles and one stacked shard image feeds the kernel.

Serving batches per-shard queries: requests accumulate per table in the
driver's buffer; a flush compiles each table's batch (block-granular
replica choice), rebases into the fused tile space, block-compiles one
:class:`~repro.core.reduction.ShardedBlockedQueries` per flush, and runs
:func:`repro.kernels.crossbar_reduce_tables` — emulation on one device,
``shard_map`` when a mesh is installed.  Every flush records the
observability contract of the sharded path: per-shard grid cells,
per-shard union widths, and cross-shard combine bytes.

**Online replanning** (opt-in via ``replan=``, DESIGN.md §6): each flush
also feeds the compiled batch's per-group loads to a
:class:`~repro.serve.drift.DriftTracker`.  When the decayed observation
drifts past the configured total-variation threshold, the server stages
an incremental :class:`~repro.dist.replan.PlanPatch` — computed on the
host *while the flush's kernel executes on device* — and applies it at
the start of the next flush: placement arrays swap, and only the moved
tiles DMA into the image stack
(:func:`repro.kernels.sharded.patch_shard_images`).  The full
``plan_shards`` + ``build_fused_image`` rebuild never reruns.

**Async flush scheduling** (opt-in via ``flush_policy=``, DESIGN.md §7):
under ``"per-shard"`` / ``"deadline"`` / ``"owner-set"`` the synchronous
loop above becomes a pipelined engine.  Queries route to homes
(:class:`~repro.serve.scheduler.FlushScheduler`) — one per shard, plus
(owner-set routing) one per distinct frozen owner set — homes flush
independently as their block unions fill, subset flushes compile with
``participants=`` exactly the home's shards (a single-shard flush
combines nothing; a 2-owner flush rings 2 shards via grouped psum), and
each dispatch is non-blocking: the host compiles flush *n+1* while
flush *n* executes on device, ``block_until_ready`` runs only at result
hand-off (bounded in-flight queue /
:meth:`ShardedEmbeddingServer.drain`).  A staged plan patch then
applies only at a pipeline **barrier** — never between in-flight
flushes.

**Thread driver** (opt-in via ``threaded=``, DESIGN.md §7.2): the
engine's dispatch/retire loop moves to a dedicated driver thread.
``submit()`` then only validates the query, stamps its sequence id and
enqueues onto a bounded hand-off queue — it never blocks on a full
in-flight pipeline (the ``max_in_flight`` hand-off block happens on the
driver).  ``drain()``/``flush()``/``serve()`` post a barrier token and
join the driver at it; plan patches still apply only at such barriers.
A flush failure on the driver requeues its batch (same retry contract)
and surfaces at the next ``submit()``/``drain()``.

**Multi-producer front door** (DESIGN.md §10): ``submit()`` is safe
under N concurrent producer threads.  Each producer (the ``producer=``
label, lazily registered) owns a per-table **sequence space**; a stamp
packs ``(local_seq, producer_id)`` into the one int64 sequence id the
whole engine already carries (:mod:`repro.serve.producers`), so
per-producer FIFO is preserved end to end and a full :meth:`drain`
merges streams in the deterministic ``(local_seq, producer_id)``
order — a pure function of what was submitted, never of thread
scheduling.  ``drain(producer=...)`` hands back only that producer's
rows (no cross-producer head-of-line mixing); :meth:`close` racing
concurrent submits gives late submitters a clean ``RuntimeError`` and
lands drained work in the ledger's ``lost_work``.

**Self-healing failure policy** (DESIGN.md §8, default on via
``retry=``): a failed compile/dispatch retries in place with bounded
exponential backoff + seeded jitter; a batch that keeps failing is
**bisected** so a single poisoned query is quarantined with its error
(recorded in the :class:`~repro.serve.faults.ErrorLedger`) instead of
wedging its home; a flush that exceeds the ``watchdog_s`` deadline is
timed out and **degraded** to the inline host/reference path, so
``drain()`` never blocks forever on hung device work.
``RetryPolicy.legacy()`` restores the requeue-and-re-raise contract.
The ``faults=`` hook accepts a :class:`~repro.serve.faults.FaultPlan`
— a deterministic, seeded fault-injection layer wrapping the compile,
dispatch, retire and patch-apply seams (chaos replay, CI smoke).

**Tiered host↔device storage** (opt-in via ``tiers=``, DESIGN.md §9):
a :class:`~repro.serve.tiers.TierConfig` caps the per-shard image
depth — the device images become a **hot tier** over the host-resident
master image, planned capacity-bounded so only the hottest groups are
resident and the cold tail lives host-side only
(``shard_of_group == COLD``).  Every query routes by residency at
submit time: resident queries flow through the crossbar kernels
unchanged, cold queries detour into a deadline-batched host queue
served by the same gather+sum the degrade path uses (bit-identical on
integer tables).  Cold traffic feeds the drift tracker too, so when a
cold group warms past the hysteresis-gated paging policy the next
patch barrier **fetches** its tiles into free slots (DMA from the host
master) and **evicts** colder victims (slots reclaimed through the
free-list, no data movement — the host master stays authoritative).
Residency snapshots refresh only at those barriers, so routing is
always consistent with the images a flush executes against.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import queue
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import (
    build_cooccurrence,
    build_layout,
    compile_activations,
    correlation_aware_grouping,
    fuse_activations,
    plan_replication,
    shard_block_activations,
)
from repro.dist.replan import (
    PlanPatch,
    apply_plan_patch,
    compute_plan_patch,
    rescale_load_to_plan,
)
from repro.dist.shard_plan import ShardPlan, build_fused_image, plan_shards
from repro.kernels.crossbar_reduce import SCHEDULE_BITMAP_DTYPE
from repro.kernels.sharded import (
    combine_bytes_per_batch,
    crossbar_reduce_tables,
    dispatch_cache_stats,
    lower_sharded,
    patch_shard_images,
    put_schedule,
)
from repro.serve.drift import DriftTracker, LoadObservationCache, ReplanConfig
from repro.serve.faults import (
    ErrorLedger,
    FaultInjector,
    FlushTimeout,
    RetryPolicy,
    latency_percentiles as _latency_percentiles,
)
from repro.serve.producers import ProducerRegistry
from repro.serve.scheduler import POOL, FlushPolicy, FlushScheduler
from repro.serve.stamps import CompletionStamps, StampRecords
from repro.serve.tiers import HostFetchQueue, ResidencyIndex, TierConfig


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unretired flush (DESIGN.md §7.2)."""

    outs: List[jax.Array]                  # lazy per-table kernel outputs
    sbq: object                            # the flush's ShardedBlockedQueries
    served: List[str]                      # table names, outs order
    seqs: Dict[str, np.ndarray]            # per-table submission sequence ids
    t0: float                              # host compile start (perf_counter)
    n_queries: int
    fused: object = None                   # the flush's FusedActivations
    # ---- healing metadata (DESIGN.md §8): the raw batch so a retire-
    # time fault can re-dispatch it and a watchdog timeout can degrade
    # it to the host path ----
    home: object = None
    entries: Optional[List[tuple]] = None  # raw (table, seq, query) triples
    participants: Optional[List[int]] = None
    t_dispatch: float = 0.0                # kernel dispatch (perf_counter)
    hang_s: Optional[float] = None         # injected hang (None = healthy)
    flush: int = 0                         # dispatch sequence number


#: bound of the driver-failure stash (first-in surfaces first; overflow
#: is counted, never silently dropped) — see _stash_driver_error
_MAX_STASHED_ERRORS = 8


@dataclasses.dataclass
class ShardedServeStats:
    """Accumulated per-flush accounting of the sharded datapath.

    Under an async flush policy (DESIGN.md §7) ``wall_s`` is the sum of
    per-flush residence times, from the start of the flush's host
    compile to its retire; flushes OVERLAP, so the sum is not wall
    clock — end-to-end wall clock is what the scheduler bench measures;
    the pipelining gain shows up here as ``hidden_compile_s`` (host
    compile time that ran while a previous flush executed on device)
    over ``host_compile_s``.  Latency samples are kept raw (one float
    per flush / per submit) so ``summary()`` can report percentiles; at
    serving-bench scales this is a few KB — a reservoir is not worth the
    accounting distortion.

    The front-door and engine counters (``submit_s`` … ``routed``) are
    ``perf_counter`` sums, each written by one thread or under the lock
    already held where it is written.  Wall-clock time on either thread
    includes the time it waited for the GIL.  ``stamps`` holds the
    keyed submit and completion stamps of the async paths
    (:class:`~repro.serve.stamps.CompletionStamps`).
    """

    num_shards: int
    q_block: int
    policy: str = "global"
    batches: int = 0
    queries: int = 0
    blocks: int = 0
    grid_cells_per_shard: int = 0          # Σ over flushes of nb × max_tiles
    max_grid_cells_per_flush: int = 0
    max_shard_width: int = 0               # widest per-shard block union seen
    combine_bytes: int = 0
    wall_s: float = 0.0
    # ---- async flush scheduling (DESIGN.md §7) ----
    shard_flushes: Dict[object, int] = dataclasses.field(default_factory=dict)
    participant_sizes: Dict[int, int] = dataclasses.field(default_factory=dict)
    barrier_flushes: int = 0               # pipeline drains (patch/explicit)
    deadline_flushes: int = 0              # flushes forced by query age
    host_compile_s: float = 0.0            # Σ per-flush host compile time
    hidden_compile_s: float = 0.0          # … of which overlapped device exec
    in_flight_peak: int = 0                # deepest dispatch queue seen
    # ---- the schedule's host→device transfer, one put per flush ----
    schedule_puts: int = 0
    schedule_bytes: int = 0                # Σ tile-id + bitmap bytes put
    # compile start → retire, one sample per flush
    flush_wall: List[float] = dataclasses.field(default_factory=list)
    submit_wall: List[float] = dataclasses.field(default_factory=list)
    # ---- front door and engine thread (DESIGN.md §7.2) ----
    submit_s: float = 0.0                  # Σ time inside accepted submit()s
    submits: int = 0                       # accepted submit() calls
    handoff_full_s: float = 0.0            # … of which blocked on a full hand-off
    engine_wait_s: float = 0.0             # driver blocked on an empty hand-off
    route_s: float = 0.0                   # ingest time outside flush work
    routed: int = 0                        # bags ingested by the engine
    route_chunks: int = 0                  # routing passes over those bags
    # submit and completion stamps keyed by (producer, table, local seq);
    # quarantined queries never complete, so they never get a record
    stamps: CompletionStamps = dataclasses.field(
        default_factory=CompletionStamps)
    # ---- online replanning (DESIGN.md §6) ----
    replans: int = 0                       # patches applied (moves > 0)
    rebases: int = 0                       # no-op patches (load reanchor only)
    patched_tiles: int = 0                 # Σ tiles DMA'd by applied patches
    promoted_groups: int = 0
    demoted_groups: int = 0
    # ---- tiered host/device storage (DESIGN.md §9) ----
    hot_queries: int = 0                   # routed through the crossbar path
    host_queries: int = 0                  # routed to the host (cold) path
    host_flushes: int = 0                  # host-queue batches served
    host_deadline_flushes: int = 0         # … of which forced by query age
    sync_cold_batches: int = 0             # sync serve()'s inline cold splits
    fetched_tiles: int = 0                 # Σ tiles paged INTO the hot tier
    evicted_tiles: int = 0                 # Σ tiles paged OUT (slots freed)
    paging_bytes: int = 0                  # Σ host→device bytes of fetches
    load_obs_hits: int = 0                 # drift-observation memo hits
    load_obs_misses: int = 0
    # ---- failure/recovery accounting (DESIGN.md §8) ----
    ledger: ErrorLedger = dataclasses.field(default_factory=ErrorLedger)

    def record(self, sbq, dim: int, wall_s: float, queries: int) -> None:
        """Accounts one served batch: grid cells, widths, combine
        traffic (scaled to the flush's participant set), and its
        residence ``wall_s`` (compile start → retire on the async
        paths, the whole ``serve()`` call on the sync one)."""
        cells = sbq.grid_cells_per_shard()
        self.batches += 1
        self.queries += queries
        self.blocks += sbq.num_blocks
        self.grid_cells_per_shard += cells
        self.max_grid_cells_per_flush = max(self.max_grid_cells_per_flush, cells)
        self.max_shard_width = max(
            self.max_shard_width, int(np.max(sbq.shard_widths, initial=0))
        )
        # combine traffic scales with the flush's PARTICIPANTS, not the
        # mesh: a single-participant flush skips the collective entirely
        # (zero interconnect), and a multi-shard subset whose size
        # divides the mesh rings only its participants (grouped psum,
        # kernels.sharded — equal index-group sizes are a TPU lowering
        # requirement); any other subset falls back to the full-axis
        # ring with zero payloads from non-participants.  sbq.num_shards
        # IS the participant count (the stack depth of the subset
        # compile).
        p = sbq.num_shards
        ring = p if (p == 1 or self.num_shards % p == 0) else self.num_shards
        self.combine_bytes += combine_bytes_per_batch(
            sbq.num_blocks * sbq.q_block, dim, ring
        )
        self.participant_sizes[sbq.num_shards] = (
            self.participant_sizes.get(sbq.num_shards, 0) + 1
        )
        self.wall_s += wall_s
        self.flush_wall.append(wall_s)

    def record_flush_home(self, home) -> None:
        """Counts one dispatched flush against its home (an int shard,
        the POOL sentinel -1, or an owner-set tuple)."""
        self.shard_flushes[home] = self.shard_flushes.get(home, 0) + 1

    def record_submit(self, seconds: float) -> None:
        """Accounts one submit() call's host latency (µs-scale under
        the thread driver — the never-blocks contract the percentiles
        in :meth:`summary` make auditable)."""
        self.submit_wall.append(seconds)

    def record_accepted(self, t0: float, blocked: float = 0.0) -> None:
        """Adds one accepted submit() that started at ``t0`` to the
        front-door counters, ``blocked`` seconds of it on a full
        hand-off queue.  The caller holds the lock that serializes its
        path's submits (the stamp lock, or the engine lock under
        ``"global"``)."""
        self.submit_s += time.perf_counter() - t0
        self.submits += 1
        self.handoff_full_s += blocked

    def record_compile(self, seconds: float, *, hidden: bool) -> None:
        """Accounts one flush's host compile; ``hidden`` when at least
        one earlier flush was still executing on device while it ran."""
        self.host_compile_s += seconds
        if hidden:
            self.hidden_compile_s += seconds

    @property
    def overlap_fraction(self) -> float:
        """Fraction of host compile time hidden behind device execution."""
        return (self.hidden_compile_s / self.host_compile_s
                if self.host_compile_s > 0 else 0.0)

    def record_patch(self, patch: PlanPatch, tile_bytes: int = 0) -> None:
        """Accounts one applied plan patch (replan vs rebase, moved
        tiles, promotions/demotions, paging traffic)."""
        # paging accounting rides every applied patch: fetches DMA host
        # master bytes onto the device, evictions only free slots
        fetched = len(getattr(patch, "fetch_dma", ()) or ())
        self.fetched_tiles += fetched
        self.evicted_tiles += int(getattr(patch, "evicted_tiles", 0) or 0)
        self.paging_bytes += fetched * int(tile_bytes)
        if patch.is_noop():
            self.rebases += 1
            return
        self.replans += 1
        self.patched_tiles += patch.num_moved_tiles + patch.num_relocated_tiles
        self.promoted_groups += len(patch.promoted)
        self.demoted_groups += len(patch.demoted)

    def summary(self) -> Dict[str, float]:
        """Flat metrics dict for reports/benches (counters, latency
        percentiles, paging and failure accounting)."""
        return {
            "num_shards": self.num_shards,
            "q_block": self.q_block,
            "flush_policy": self.policy,
            "batches": self.batches,
            "queries": self.queries,
            "blocks": self.blocks,
            "grid_cells_per_shard": self.grid_cells_per_shard,
            "max_grid_cells_per_flush": self.max_grid_cells_per_flush,
            "max_shard_width": self.max_shard_width,
            "combine_bytes": self.combine_bytes,
            "wall_s": self.wall_s,
            "shard_flushes": {
                str(k): v for k, v in sorted(
                    self.shard_flushes.items(), key=lambda kv: str(kv[0])
                )
            },
            "participant_sizes": {
                str(k): v for k, v in sorted(self.participant_sizes.items())
            },
            "flush_latency_s": _latency_percentiles(self.flush_wall),
            "submit_latency_s": _latency_percentiles(self.submit_wall),
            "e2e_latency_s": _latency_percentiles(self.stamps.latencies()),
            "barrier_flushes": self.barrier_flushes,
            "deadline_flushes": self.deadline_flushes,
            "host_compile_s": self.host_compile_s,
            "hidden_compile_s": self.hidden_compile_s,
            "overlap_fraction": self.overlap_fraction,
            "in_flight_peak": self.in_flight_peak,
            "schedule_puts": self.schedule_puts,
            "schedule_bytes": self.schedule_bytes,
            "routed": self.routed,
            "route_chunks": self.route_chunks,
            "replans": self.replans,
            "rebases": self.rebases,
            "patched_tiles": self.patched_tiles,
            "promoted_groups": self.promoted_groups,
            "demoted_groups": self.demoted_groups,
            "tiers": self.tier_summary(),
            "faults": self.ledger.summary(),
        }

    def tier_summary(self) -> Dict[str, object]:
        """Hot-tier effectiveness metrics (DESIGN.md §9).

        ``hot_tier_hit_rate`` is the fraction of routed queries served
        entirely from the device images (1.0 when tiering is off or no
        query has been routed yet); ``host_path_fraction`` is its
        complement — the tier bench's steady-state acceptance metric.
        """
        routed = self.hot_queries + self.host_queries
        return {
            "hot_queries": self.hot_queries,
            "host_queries": self.host_queries,
            "hot_tier_hit_rate": (
                self.hot_queries / routed if routed else 1.0
            ),
            "host_path_fraction": (
                self.host_queries / routed if routed else 0.0
            ),
            "host_flushes": self.host_flushes,
            "host_deadline_flushes": self.host_deadline_flushes,
            "sync_cold_batches": self.sync_cold_batches,
            "fetched_tiles": self.fetched_tiles,
            "evicted_tiles": self.evicted_tiles,
            "paged_tiles": self.fetched_tiles + self.evicted_tiles,
            "paging_bytes": self.paging_bytes,
            "load_obs_hits": self.load_obs_hits,
            "load_obs_misses": self.load_obs_misses,
        }


class ShardedEmbeddingServer:
    """Multi-table embedding-reduction server over the ``model`` axis.

    Args:
      tables: ``{name: (rows, dim) float array}`` logical tables.
      histories: ``{name: ragged lookup history}`` driving the offline
        pipeline (grouping + Eq.-1 replication) per table.
      num_shards: model-parallel degree to plan for.
      mesh: optional mesh whose ``axis_name`` axis has ``num_shards``
        devices → the flush runs under shard_map; ``None`` emulates the
        shard loop on the local device (identical numerics).
      axis_name: mesh axis the image shards over (default ``"model"``).
      q_block: queries per kernel block (DMA amortization factor).
      group_size: crossbar height (tile rows).
      batch_size: auto-flush threshold for :meth:`submit`.
      batch_size_for_eq1: Eq. 1's ``batch`` (replication aggressiveness);
        defaults to ``batch_size``.  Online replanning re-evaluates
        Eq. 1 at this batch size unless ``replan.eq1_batch`` overrides.
      combine: cross-shard combine collective — ``"psum_scatter"``
        (reduce-scatter over dim + all-gather) or ``"psum"``.
      combine_chunks: block-axis chunks for combine/DMA overlap.
      dynamic_switch: enable the paper's §III-D READ/MAC switch.
      interpret: force Pallas interpret mode (``None`` = auto off-TPU).
      replan: optional :class:`~repro.serve.drift.ReplanConfig` enabling
        drift-triggered incremental replanning (DESIGN.md §6).
      flush_policy: ``"global"`` (the synchronous PR-2 path, default) or
        an async policy — ``"per-shard"`` / ``"deadline"`` /
        ``"owner-set"`` kind strings or a full
        :class:`~repro.serve.scheduler.FlushPolicy`.  Async policies
        flush homes independently as their block unions fill and
        pipeline host compile against device execution; ``"owner-set"``
        additionally keys multi-owner homes by their frozen owner set
        so a flush's participants are exactly its queries' owners.
        Results are collected with :meth:`drain` (or :meth:`flush`,
        which is a barrier in async mode).  DESIGN.md §7.
      union_budget / flush_deadline / flush_deadline_s / owner_set_max /
        max_in_flight: async policy knobs
        (see :class:`~repro.serve.scheduler.FlushPolicy`); ignored under
        ``"global"``.
      threaded: run the async engine on a dedicated driver thread
        (DESIGN.md §7.2): :meth:`submit` validates + enqueues onto a
        bounded hand-off queue and never blocks on a full in-flight
        pipeline; call :meth:`close` (or use the server as a context
        manager) to stop the driver.  Requires an async flush policy.
      retry: the self-healing policy (DESIGN.md §8) — bounded retries
        with backoff + jitter, offender bisection/quarantine, and the
        flush watchdog.  ``None`` uses the :class:`~repro.serve.faults.
        RetryPolicy` defaults (healing on, watchdog off);
        ``RetryPolicy.legacy()`` restores requeue-and-re-raise.
      faults: optional :class:`~repro.serve.faults.FaultPlan` (or a
        ready injector) wrapping the compile / dispatch / retire /
        patch-apply seams with deterministic, seeded fault injection —
        chaos replays and the driver-fault-branch tests.
      tiers: optional :class:`~repro.serve.tiers.TierConfig` making the
        shard images a capacity-bounded **hot tier** (DESIGN.md §9):
        the plan admits only the hottest groups up to the budget, cold
        queries serve through a deadline-batched host gather+sum path,
        and drift-driven plan patches page groups in/out at flush
        barriers.  Enables replanning implicitly (a default
        :class:`~repro.serve.drift.ReplanConfig`) when ``replan`` is
        not given — paging needs the drift tracker.  ``replan.
        slack_tiles`` / ``shrink_streak`` are ignored under tiering:
        the image depth IS the (fixed) capacity.
    """

    def __init__(
        self,
        tables: Dict[str, np.ndarray],
        histories: Dict[str, Sequence[Sequence[int]]],
        *,
        num_shards: int = 1,
        mesh=None,
        axis_name: str = "model",
        q_block: int = 8,
        group_size: int = 64,
        batch_size: int = 256,
        batch_size_for_eq1: int | None = None,
        combine: str = "psum_scatter",
        combine_chunks: int = 2,
        dynamic_switch: bool = True,
        interpret: bool | None = None,
        replan: ReplanConfig | None = None,
        flush_policy: str | FlushPolicy = "global",
        union_budget: int | None = None,
        flush_deadline: int | None = None,
        flush_deadline_s: float | None = None,
        owner_set_max: int | None = None,
        max_in_flight: int = 2,
        threaded: bool = False,
        retry: RetryPolicy | None = None,
        faults=None,
        tiers: TierConfig | None = None,
    ):
        if set(tables) != set(histories):
            raise ValueError("tables and histories must cover the same names")
        if not tables:
            raise ValueError("need at least one table")
        self.names = sorted(tables)
        self.num_shards = num_shards
        self.mesh = mesh
        self.axis_name = axis_name
        self.q_block = q_block
        self.batch_size = batch_size
        self.combine = combine
        self.combine_chunks = combine_chunks
        self.dynamic_switch = dynamic_switch
        self.interpret = interpret

        eq1_batch = batch_size_for_eq1 or batch_size
        #: seconds of the plan build's stages, summed over tables:
        #: ``cooccurrence``, ``grouping`` (grouping, Eq.-1 replication,
        #: layout) and ``placement`` (shard plan, images, device put)
        self.setup_timings = {"cooccurrence": 0.0, "grouping": 0.0,
                              "placement": 0.0}
        self.layouts, plans, gfreqs = [], [], []
        dims = set()
        for name in self.names:
            table = np.asarray(tables[name])
            hist = histories[name]
            t0 = time.perf_counter()
            graph = build_cooccurrence(hist, table.shape[0])
            t1 = time.perf_counter()
            grouping = correlation_aware_grouping(graph, group_size)
            plan = plan_replication(grouping, graph.freq, eq1_batch)
            self.layouts.append(build_layout(grouping, plan, table.shape[1]))
            self.setup_timings["cooccurrence"] += t1 - t0
            self.setup_timings["grouping"] += time.perf_counter() - t1
            plans.append(plan)
            gfreqs.append(grouping.group_freq(graph.freq))
            dims.add(table.shape[1])
        if len(dims) != 1:
            raise ValueError("fused serving requires a uniform embedding dim")
        self.dim = dims.pop()

        self.tiers = tiers
        if tiers is not None and replan is None:
            # paging rides the drift tracker: tiering without an explicit
            # replan config still needs one to ever page a group in
            replan = ReplanConfig()
        t0 = time.perf_counter()
        self._capacity_tiles: Optional[int] = None
        if tiers is not None:
            # the budget is resolved against what an UNCAPPED plan of
            # the same tables would need — capacity_frac=0.1 means "the
            # device holds a tenth of the working set"
            uncapped = plan_shards(
                self.layouts, plans, num_shards,
                names=self.names, group_freqs=gfreqs,
            )
            self._capacity_tiles = tiers.resolve_capacity(
                uncapped.max_local_tiles
            )
        self.plan: ShardPlan = plan_shards(
            self.layouts, plans, num_shards,
            names=self.names, group_freqs=gfreqs,
            capacity_tiles=self._capacity_tiles,
        )
        # host-resident master image: the serve-time DMA source for
        # incremental plan patches (kept even without replan so a later
        # enable_replan-style extension stays cheap; it is the same bytes
        # a parameter server would hold anyway)
        self._fused = build_fused_image(
            self.layouts, [np.asarray(tables[n]) for n in self.names]
        )
        images = self.plan.build_shard_images(self._fused)
        self.replan_cfg = replan
        self._eq1_batch = (
            replan.eq1_batch if replan and replan.eq1_batch else eq1_batch
        )
        if self._capacity_tiles is not None:
            # the hot tier is FIXED at its budget: pad the image stack
            # to capacity so every free slot is fetchable from day one
            # (slack_tiles growth/shrink is a no-tier concern)
            extra = self._capacity_tiles - images.shape[1]
            if extra > 0:
                pad = np.zeros(
                    (num_shards, extra) + images.shape[2:],
                    dtype=images.dtype,
                )
                images = np.concatenate([images, pad], axis=1)
        elif replan is not None and replan.slack_tiles > 0:
            # zero-tile headroom so early promotions fill slack instead
            # of growing (reallocating) the device image stack
            pad = np.zeros(
                (num_shards, replan.slack_tiles) + images.shape[2:],
                dtype=images.dtype,
            )
            images = np.concatenate([images, pad], axis=1)
        self.shard_images = self._place_images(images)
        self.setup_timings["placement"] = time.perf_counter() - t0
        #: host→device bytes of one fused tile — the paging_bytes unit
        self._tile_bytes = int(self._fused[0].nbytes) if len(self._fused) else 0
        self._tile_group = np.repeat(
            np.arange(self.plan.num_groups, dtype=np.int64),
            self.plan.group_copies,
        )
        # per-table training-time load mass: Eq. 1 is evaluated at this
        # magnitude at replan time (see rescale_load_to_plan) — constant
        # across rebases, since rescaled snapshots carry the same totals
        self._segments = [
            (s.group_offset, s.group_offset + s.num_groups)
            for s in self.plan.tables
        ]
        self._seg_load_totals = [
            float(self.plan.group_load[a:b].sum()) for a, b in self._segments
        ]
        self.tracker: Optional[DriftTracker] = (
            DriftTracker(
                self.plan.group_load,
                half_life=replan.half_life,
                min_queries=replan.min_queries,
            )
            if replan is not None
            else None
        )
        self._staged: Optional[PlanPatch] = None
        self._demote_streak = 0
        # per-flush drift-observation memo (content-keyed): replayed /
        # steady-state streams re-flush byte-identical compiled batches
        self._load_obs: Optional[LoadObservationCache] = (
            LoadObservationCache() if replan is not None else None
        )
        # ---- tiered storage state (DESIGN.md §9); None when untiered --
        self._residency: Optional[ResidencyIndex] = None
        self._host_queue: Optional[HostFetchQueue] = None
        self._tick = 0
        if tiers is not None:
            name_to_layout = dict(zip(self.names, self.layouts))
            self._residency = ResidencyIndex(self.plan, {
                seg.name: np.asarray(
                    name_to_layout[seg.name].group_of, dtype=np.int64
                ) + seg.group_offset
                for seg in self.plan.tables
            })
            hb = tiers.host_batch or batch_size
            self._host_queue = HostFetchQueue(
                hb, tiers.host_deadline or 4 * hb
            )
        knobs_set = (union_budget is not None or flush_deadline is not None
                     or flush_deadline_s is not None
                     or owner_set_max is not None or max_in_flight != 2
                     or threaded)
        if isinstance(flush_policy, str):
            if knobs_set:
                flush_policy = FlushPolicy(
                    kind=flush_policy, union_budget=union_budget,
                    deadline=flush_deadline, deadline_s=flush_deadline_s,
                    owner_set_max=owner_set_max,
                    max_in_flight=max_in_flight, threaded=threaded,
                )
        elif knobs_set:
            raise ValueError(
                "pass the flush knobs inside the FlushPolicy instance OR "
                "as keyword args with a policy-kind string, not both"
            )
        self.policy = FlushPolicy.parse(flush_policy, batch_size=batch_size)
        self.stats = ShardedServeStats(
            num_shards=num_shards, q_block=q_block, policy=self.policy.kind
        )
        self._buffer: Dict[str, List[Sequence[int]]] = {n: [] for n in self.names}
        self._buffered = 0
        # ---- async flush engine state (DESIGN.md §7); inert under
        # the synchronous "global" policy ----
        # ---- per-producer sequence spaces (DESIGN.md §10): every
        # stamped id packs (local_seq, producer_id), so the engine's
        # int64 seq plumbing carries the producer dimension for free --
        self._registry = ProducerRegistry()
        self.scheduler: Optional[FlushScheduler] = (
            FlushScheduler(self.plan, self.layouts, self.names,
                           q_block, self.policy,
                           seq_decode=self._registry.decode)
            if self.policy.is_async else None
        )
        self._in_flight: collections.deque = collections.deque()
        self._completed: Dict[str, List[Tuple[np.ndarray, np.ndarray]]] = {
            n: [] for n in self.names
        }
        # per-table row counts: submit()-time validation rejects
        # out-of-range ids BEFORE anything is enqueued, so a malformed
        # query can never poison a buffered batch (the retry contract's
        # "remove the offender" happens at the door)
        self._num_rows: Dict[str, int] = {
            n: int(np.asarray(tables[n]).shape[0]) for n in self.names
        }
        # ---- self-healing failure policy + fault injection (§8) ----
        self.retry = RetryPolicy.parse(retry)
        self._injector = FaultInjector.parse(faults)
        if self._injector is not None:
            # poison keying speaks (table, producer, LOCAL seq): the
            # injector decodes the packed ids the engine hands it
            self._injector.bind_decoder(self._registry.decode)
        self._retry_rng = np.random.default_rng(self.retry.seed)
        # host copies of the logical tables: the watchdog's degraded
        # flush recomputes its rows here (reference gather+sum) — the
        # same bytes a parameter server holds, like self._fused
        self._host_tables: Dict[str, np.ndarray] = {
            n: np.asarray(tables[n]) for n in self.names
        }
        self._patch_fail_streak = 0
        # ---- thread driver state (DESIGN.md §7.2); started lazily on
        # the first submit under a threaded policy ----
        self._handoff: Optional[queue.Queue] = None
        self._driver: Optional[threading.Thread] = None
        self._driver_stop = threading.Event()
        # driver failures stash into a BOUNDED deque: the first error is
        # surfaced first (with the count of others), overflow beyond the
        # bound is counted in the ledger instead of silently overwriting
        self._driver_errors: collections.deque = collections.deque()
        self._suppressed_errors = 0
        # ---- multi-producer front door state (DESIGN.md §10) ----
        # stamp lock: registration + seq stamp + closed check + driver
        # start are one atomic step, so two producers' first submits
        # cannot race two drivers into existence and a stamp can never
        # interleave with close() or the drain-time seq reset
        # lock order (DESIGN.md §5): 3rd — after engine/results, before
        # the registry's lock
        self._stamp_lock = threading.Lock()
        # engine lock: serializes the INLINE engine (ingest/flush/
        # barrier) under concurrent producers; the thread driver never
        # takes it (the hand-off queue is its serialization)
        # lock order (DESIGN.md §5): outermost — taken before any other
        self._engine_lock = threading.RLock()
        # results lock: _completed appends (driver/host flush) vs the
        # drain-time extract-and-swap
        # lock order (DESIGN.md §5): 2nd — after engine, before stamp
        self._results_lock = threading.Lock()
        self._closed = False
        # submits past the stamp but not yet delivered (hand-off put in
        # flight, or inline ingest running) — the seq-reset guard and
        # close()'s drain loop both key off this being zero
        self._pending_submits = 0
        # ---- engine accounting (DESIGN.md §7.2), written only by the
        # engine (the driver thread, or under the engine lock inline):
        # dispatch sequence number of the next flush (the spans' id),
        # and the flush work that route_s leaves out ----
        self._flush_seq = 0
        self._flush_work_s = 0.0

    # ------------------------------------------------------------ serving --

    def serve(
        self, queries_by_table: Dict[str, Sequence[Sequence[int]]]
    ) -> Dict[str, jax.Array]:
        """Serves one synchronous multi-table batch.

        Pipeline per call: apply any staged plan patch (see
        :meth:`_apply_staged_patch` — this is flush *n+1* of the
        double-buffered ordering), compile each table's ragged queries
        (block-granular replica choice), rebase into the fused tile
        space, block-compile per shard, dispatch the sharded kernel,
        then — while the device executes — observe drift and stage the
        next patch, and finally block on the outputs and record stats.

        Args:
          queries_by_table: ``{table name: ragged row-id queries}``;
            tables absent or mapped to an empty list are skipped.

        Returns:
          ``{table name: (batch, dim) reduction}`` for every table that
          had at least one query (padding rows already sliced off).

        Raises:
          KeyError: a key names an unknown table.
        """
        t0 = time.perf_counter()
        unknown = set(queries_by_table) - set(self.names)
        if unknown:
            raise KeyError(f"unknown tables {sorted(unknown)!r}")
        served = [n for n in self.names if queries_by_table.get(n)]
        if not served:
            return {}
        # a synchronous serve is a barrier: async-pending queries flush
        # under the plan they were routed against and the pipeline
        # drains (the barrier applies any staged patch), so a patch can
        # never land mid-pipeline or orphan stale routing (DESIGN.md
        # §7.3).  In global mode nothing is ever in flight and the
        # staged patch applies here.
        if self.scheduler is not None:
            self._barrier()
        else:
            self._apply_staged_patch()
        # ---- residency split (DESIGN.md §9): a compiled batch may
        # never reference a cold tile, so cold queries peel off to the
        # host gather+sum path here, against the *post-patch* plan ----
        queries_of = {n: list(queries_by_table[n]) for n in served}
        parts: Dict[str, tuple] = {}
        if self._residency is not None and self._residency.any_cold:
            for n in served:
                hot_idx: List[int] = []
                cold_idx: List[int] = []
                for i, q in enumerate(queries_of[n]):
                    arr = np.asarray(list(q), dtype=np.int64)
                    if self._residency.is_resident(n, arr):
                        hot_idx.append(i)
                    else:
                        cold_idx.append(i)
                parts[n] = (hot_idx, cold_idx)
            cold_entries = [
                (n, i, queries_of[n][i])
                for n in served for i in parts[n][1]
            ]
            if cold_entries:
                self.stats.host_queries += len(cold_entries)
                # NOT host_flushes: that counter means "HostFetchQueue
                # batches served" — the sync path's inline cold
                # sub-batch never enters the queue
                self.stats.sync_cold_batches += 1
                if self.tracker is not None:
                    # cold queries never compile, but their loads must
                    # feed the tracker or a cold group can never warm
                    self.tracker.observe(
                        self._residency.host_group_loads(cold_entries),
                        len(cold_entries),
                    )
            self.stats.hot_queries += sum(
                len(parts[n][0]) for n in served
            )
        elif self._residency is not None:
            # fully-resident tiered plan: everything is a hot-tier hit
            self.stats.hot_queries += sum(
                len(queries_of[n]) for n in served
            )
        hot_of = {
            n: ([queries_of[n][i] for i in parts[n][0]]
                if n in parts else queries_of[n])
            for n in served
        }
        served_dev = [n for n in served if hot_of[n]]
        outs: List[np.ndarray] = []
        sbq = None
        if served_dev:
            tc = time.perf_counter()
            fused, sbq = self._compile_batch(
                served_dev, {n: hot_of[n] for n in served_dev}
            )
            # synchronous compile sits squarely on the serving critical
            # path — never hidden (the §7 engine's motivating cost)
            self.stats.record_compile(time.perf_counter() - tc, hidden=False)
            outs = crossbar_reduce_tables(
                self.shard_images, self._put_schedule(sbq, count=True),
                fused.spans,
                mesh=self.mesh, axis_name=self.axis_name,
                combine=self.combine, combine_chunks=self.combine_chunks,
                dynamic_switch=self.dynamic_switch, interpret=self.interpret,
            )
            n_queries = sum(len(hot_of[n]) for n in served_dev)
            # double buffering: the kernel above is dispatched but NOT
            # yet blocked on — drift bookkeeping and patch computation
            # are pure host work overlapping this flush's device time
            self._observe_and_stage(fused, n_queries)
            outs = [jax.block_until_ready(o) for o in outs]
        elif self.tracker is not None:
            # an all-cold batch still observed loads above — give the
            # drift statistic its chance to stage a paging patch
            self._maybe_stage()
        out: Dict[str, jax.Array] = {}
        dev_out = dict(zip(served_dev, outs))
        for n in served:
            if n not in parts or not parts[n][1]:
                out[n] = dev_out[n]
                continue
            hot_idx, cold_idx = parts[n]
            full = np.zeros(
                (len(queries_of[n]), self.dim),
                dtype=self._host_tables[n].dtype,
            )
            if hot_idx:
                full[np.asarray(hot_idx)] = np.asarray(dev_out[n])
            full[np.asarray(cold_idx)] = self._serve_cold_rows(
                n, [queries_of[n][i] for i in cold_idx]
            )
            out[n] = jnp.asarray(full)
        if sbq is not None:
            self.stats.record(
                sbq, self.dim, time.perf_counter() - t0,
                sum(len(hot_of[n]) for n in served_dev),
            )
        return out

    def _place_images(self, images) -> jax.Array:
        """Puts the ``(S, tiles, rows, dim)`` image stack on the device(s)
        once: with a mesh, shard ``i``'s slice lives on the device at
        mesh position ``i`` of ``axis_name``, so no flush reshards the
        table from one chip."""
        if self.mesh is None:
            return jnp.asarray(images)
        return jax.device_put(
            images, NamedSharding(self.mesh, PartitionSpec(self.axis_name))
        )

    def lower_flush(self, queries_by_table, participants=None):
        """Lowers, without running, the program one flush of these
        queries dispatches (``participants=`` as the async homes compile
        a shard subset).  ``.as_text()`` of the result shows whether the
        crossbar kernel is a compiled Mosaic call (``tpu_custom_call``)."""
        served = [n for n in self.names if queries_by_table.get(n)]
        _, sbq = self._compile_batch(served, queries_by_table, participants)
        sbq = self._put_schedule(sbq, count=False)
        return lower_sharded(
            self.shard_images, sbq.tile_ids, sbq.bitmaps,
            mesh=self.mesh, axis_name=self.axis_name, combine=self.combine,
            combine_chunks=self.combine_chunks,
            dynamic_switch=self.dynamic_switch, interpret=self.interpret,
            shard_ids=sbq.shards,
        )

    def _compile_batch(self, served, queries_of, participants=None):
        """Fused host compile shared by the sync and async paths.

        Per-table sparse activations (block-granular replica choice) →
        fused into one tile and row space (blocks never span tables) →
        the per-shard blocked schedule, built straight from them.  All
        NumPy: nothing crosses to the device here.  The fused
        activations also serve the drift observation.

        Returns ``(fused, sbq)``, ``sbq`` with NumPy arrays; the per-table
        spans are ``fused.spans``.
        """
        tables = []
        for name in served:
            i = self.names.index(name)
            acts = compile_activations(
                self.layouts[i], queries_of[name],
                replica_block=self.q_block,
            )
            tables.append((acts, self.plan.tables[i].tile_offset))
        fused = fuse_activations(tables, self.q_block)
        sbq = shard_block_activations(
            fused, self.plan, self.q_block, participants=participants,
            bitmap_dtype=SCHEDULE_BITMAP_DTYPE,
        )
        return fused, sbq

    def _put_schedule(self, sbq, *, count: bool):
        """Returns ``sbq`` with its schedule on the device, put in one
        transfer where the flush program takes it
        (:func:`~repro.kernels.sharded.put_schedule`); ``count`` adds the
        put to ``schedule_puts`` / ``schedule_bytes``."""
        ids, bms = put_schedule(
            self.shard_images, sbq.tile_ids, sbq.bitmaps,
            mesh=self.mesh, axis_name=self.axis_name, shard_ids=sbq.shards,
        )
        if count:
            self.stats.schedule_puts += 1
            self.stats.schedule_bytes += int(ids.nbytes + bms.nbytes)
        return dataclasses.replace(sbq, tile_ids=ids, bitmaps=bms)

    # --------------------------------------------------------- replanning --

    def _apply_staged_patch(self) -> None:
        """Swaps in the patch staged during the previous flush.

        Runs at the top of :meth:`serve`, before anything is compiled
        against the plan — flush *n*'s outputs were produced entirely
        under the old plan, flush *n+1* runs entirely under the new one
        (no torn state).  Image update DMAs only the moved tiles.

        A patch-apply failure (injected or real, before any state
        mutates) keeps the patch staged and retries it at the next
        barrier, up to ``retry.patch_retries`` times — then the patch is
        dropped (recorded) and serving continues under the live plan.
        Under the legacy policy the failure re-raises instead.
        """
        if self._staged is None:
            return
        assert not self._in_flight, (
            "plan patch applied mid-pipeline — barrier rule violated"
        )
        if self._injector is not None:
            try:
                self._injector.on_patch()
            except Exception:
                self.stats.ledger.patch_failures += 1
                self._patch_fail_streak += 1
                if not self.retry.quarantine:
                    raise
                if self._patch_fail_streak > self.retry.patch_retries:
                    self.stats.ledger.patches_dropped += 1
                    dropped, self._staged = self._staged, None
                    self._patch_fail_streak = 0
                    if self.tracker is not None and dropped.promoted:
                        # the drop discards promotions whose Eq.-1
                        # target status may persist: restore their
                        # drift marks so the next evaluation sees them
                        self.tracker.mark_drifted(dropped.promoted)
                return
        patch, self._staged = self._staged, None
        self._patch_fail_streak = 0
        self.shard_images = self._place_images(patch_shard_images(
            self.shard_images, patch, self._fused
        ))
        self.plan = apply_plan_patch(self.plan, patch)
        self.stats.record_patch(patch, tile_bytes=self._tile_bytes)
        if self._residency is not None:
            # paging moved groups across the hot/cold boundary: routing
            # re-snapshots residency HERE and only here (barrier rule),
            # so every routed query matches the images its flush sees
            self._residency.refresh(self.plan)
        # slack age-out bookkeeping (DESIGN.md §6.2): demotion-only
        # patches extend the streak, any promotion resets it
        if patch.promoted:
            self._demote_streak = 0
        elif patch.demoted:
            self._demote_streak += 1
        if self.scheduler is not None:
            # ownership moved: re-derive row→home routing (pending work
            # was flushed under the old plan before we got here)
            self.scheduler.rebuild(self.plan)

    def _observe_and_stage(self, fused, n_queries: int) -> None:
        """Feeds the tracker and stages a patch when drift crosses.

        Host-only work scheduled between kernel dispatch and
        ``block_until_ready``.  A no-op (class-unchanged) patch is
        applied immediately as a load rebase — it touches no device
        state, so there is nothing to double-buffer.
        """
        if self.tracker is None:
            return
        # content-keyed memo: steady-state / replayed streams re-flush
        # identical activations, whose loads are identical too
        loads = self._load_obs.loads(
            fused, self._tile_group, self.plan.num_groups
        )
        self.stats.load_obs_hits = self._load_obs.hits
        self.stats.load_obs_misses = self._load_obs.misses
        self.tracker.observe(loads, n_queries)
        self._maybe_stage()

    def _maybe_stage(self) -> None:
        """Stages a patch when the tracked drift crosses the threshold.

        Shared by the compiled-batch observation above and the host
        (cold) path's flush — under tiering, cold-only traffic must
        still be able to trigger the paging patch that warms it up.
        """
        if self._staged is not None or not self.tracker.ready:
            return
        drift = self.tracker.drift_from(
            self.plan.group_load, segments=self._segments
        )
        if drift < self.replan_cfg.threshold:
            return
        # Eq. 1 is magnitude-sensitive: evaluate the observed
        # distribution at the training-time mass, not the tracker's
        drifted = rescale_load_to_plan(
            self.tracker.load(), self.plan, self._seg_load_totals
        )
        # long demotion streaks: age the accumulated slack back out so
        # the image stack shrinks to the live working set + headroom
        # (untiered only — the hot tier's capacity is fixed)
        shrink = (
            self.replan_cfg.slack_tiles
            if self.tiers is None
            and self.replan_cfg.shrink_streak
            and self._demote_streak >= self.replan_cfg.shrink_streak
            else None
        )
        paging = (
            self.tiers.paging_policy(self._capacity_tiles)
            if self.tiers is not None else None
        )
        # scale-invariant patch math: only the groups with observed
        # traffic since the last evaluation (plus the replicated set,
        # added inside) can change replication class — every other
        # group's estimate merely decayed (DESIGN.md §11)
        candidates = self.tracker.drifted_groups()
        self.tracker.reset_drifted()
        patch = compute_plan_patch(
            self.plan, drifted,
            eq1_batch=self._eq1_batch,
            capacity=int(self.shard_images.shape[1]),
            shrink_slack=shrink,
            paging=paging,
            candidates=candidates,
        )
        if patch.deferred:
            # deferred promotions stay candidates: their Eq.-1 target
            # status outlives the marks this evaluation consumed
            self.tracker.mark_drifted(patch.deferred)
        if patch.fetched:
            # freshly-resident groups may already be Eq.-1 targets; the
            # next evaluation must reconsider them even if untouched
            self.tracker.mark_drifted([g for g, _ in patch.fetched])
        if patch.is_noop():
            # drift without a class change: reanchor group_load so the
            # greedy demotion targets and the drift statistic both track
            # the observed distribution
            self.plan = apply_plan_patch(self.plan, patch)
            self.stats.record_patch(patch, tile_bytes=self._tile_bytes)
            return
        self._staged = patch

    # ----------------------------------------------------------- batching --

    def submit(
        self,
        table: str,
        query: Sequence[int],
        *,
        producer=None,
    ) -> Dict[str, jax.Array]:
        """Buffers one query; flush behavior depends on the policy.

        Under ``"global"``: auto-flushes (synchronously) at
        ``batch_size`` buffered and returns that flush's results.
        Under an async policy: the query routes to its home, any due
        homes flush *asynchronously* (dispatch only — no blocking on
        results), and the return value is always ``{}``; collect
        results with :meth:`drain` / :meth:`flush`.  With the thread
        driver the call only validates, stamps a sequence id and
        enqueues onto the bounded hand-off queue — dispatch and retire
        run on the driver, so submit never blocks on a full in-flight
        pipeline.

        ``submit()`` is safe under N concurrent producer threads
        (DESIGN.md §10): ``producer=`` names the calling stream (any
        hashable; ``None`` is the default producer), lazily registered
        on first stamp.  Each producer owns its own per-table sequence
        space, so one stream's FIFO order never depends on another's
        thread scheduling; a full :meth:`drain` merges streams in
        deterministic ``(local_seq, producer_id)`` order and
        ``drain(producer=...)`` returns one stream's rows alone.

        The query is validated HERE, before anything is enqueued or a
        sequence id is consumed: a malformed query (row ids outside
        the table) raises and leaves every buffer/queue untouched, so
        retrying the pending work never replays the offender.
        Per-call host latency is recorded (``submit_latency_s``
        percentiles in the stats summary).

        Args:
          table: table name the query reduces over.
          query: ragged row ids (an embedding-bag lookup).
          producer: producer-stream label (async policies; ``None`` =
            the default stream).

        Returns:
          The flush result (see :meth:`flush`) when a synchronous flush
          tripped, else ``{}``.

        Raises:
          KeyError: ``table`` is not a served table.
          IndexError: a row id falls outside ``[0, rows)``.
          RuntimeError: the server was :meth:`close`\\ d.
        """
        t0 = time.perf_counter()
        try:
            return self._submit(table, query, producer, t0)
        finally:
            self.stats.record_submit(time.perf_counter() - t0)

    def _submit(
        self, table: str, query: Sequence[int], producer, t0: float
    ) -> Dict[str, jax.Array]:
        """The body of :meth:`submit`; ``t0`` is its entry time, the
        bag's submit stamp.  Each accepted path adds its time to
        ``stats.submit_s`` under the lock it already holds last."""
        if table not in self._buffer:  # unlocked: key set frozen at init
            raise KeyError(f"unknown table {table!r}")
        ids = np.asarray(list(query), dtype=np.int64)
        if ids.size:
            lo, hi = int(ids.min()), int(ids.max())
            if lo < 0 or hi >= self._num_rows[table]:
                raise IndexError(
                    f"query row ids [{lo}, {hi}] out of range "
                    f"[0, {self._num_rows[table]}) for table {table!r}"
                )
        if self.scheduler is not None:
            self._raise_driver_error()
            if self.policy.threaded:
                with self._stamp_lock:
                    # closed-check + stamp + driver-start are one
                    # atomic step: a close() cannot slip between a
                    # granted stamp and its hand-off accounting, and
                    # two producers' first submits cannot race two
                    # drivers into existence
                    if self._closed:
                        raise RuntimeError(
                            "submit() on a closed server: close() "
                            "stopped the driver; drain() still serves "
                            "what was already submitted"
                        )
                    seq = self._registry.stamp(producer, table)
                    if self._driver is None:
                        self._start_driver()
                    handoff = self._handoff
                    self.stats.stamps.submitted(table, seq, t0)
                    self._pending_submits += 1
                item = ("query", table, seq, list(query))
                blocked = 0.0
                try:
                    if handoff.full():
                        # backpressure: only a put that waits is timed
                        # (another producer may fill the queue between
                        # the check and the put: then it goes untimed)
                        tb = time.perf_counter()
                        handoff.put(item)
                        blocked = time.perf_counter() - tb
                    else:
                        handoff.put(item)
                finally:
                    with self._stamp_lock:
                        self._pending_submits -= 1
                        self.stats.record_accepted(t0, blocked)
                return {}
            with self._stamp_lock:
                if self._closed:
                    raise RuntimeError("submit() on a closed server")
                seq = self._registry.stamp(producer, table)
                self.stats.stamps.submitted(table, seq, t0)
                self._pending_submits += 1
            try:
                # the inline engine is not re-entrant: concurrent
                # producers serialize here (they may block behind a
                # flush — the never-blocks contract is the thread
                # driver's, not the inline engine's)
                with self._engine_lock:
                    self._ingest_many([(table, seq, list(query))])
            finally:
                with self._stamp_lock:
                    self._pending_submits -= 1
                    self.stats.record_accepted(t0)
            return {}
        with self._engine_lock:
            self._buffer[table].append(list(query))
            self._buffered += 1
            out = self.flush() if self._buffered >= self.batch_size else {}
            self.stats.record_accepted(t0)
            return out

    def register_producer(self, producer=None) -> int:
        """Pre-registers a producer label, returning its pid.

        Optional — a first ``submit(producer=...)`` registers lazily —
        but registration order is the cross-producer merge tiebreak
        (DESIGN.md §10), so benches/tests that want a reproducible
        interleave register all labels up front, before any thread
        races a first stamp.
        """
        return self._registry.register(producer)

    def next_seq(self, table: str, producer=None) -> int:
        """Next LOCAL sequence id ``producer`` (default stream when
        ``None``) would stamp on ``table``; 0 for a producer that
        never submitted or after a quiesced drain's reset."""
        return self._registry.next_seq(table, producer)

    def producers(self) -> List:
        """Registered producer labels in pid (merge-tiebreak) order."""
        return self._registry.producers()

    def flush(self) -> Dict[str, jax.Array]:
        """Serves and clears all buffered work.

        Under ``"global"`` this serves the buffered per-table batches
        synchronously; the buffer is cleared only after a successful
        serve, so a failed flush (e.g. one malformed query) leaves every
        buffered request intact for retry after the offender is removed.
        Under an async policy this is a **barrier**: every pending home
        flushes, the in-flight pipeline drains, a staged plan patch
        applies, and all results accumulated since the last hand-off are
        returned (see :meth:`drain`).

        Returns:
          ``{table name: (batch, dim) reduction}`` per table with
          results; ``{}`` when nothing is buffered or in flight.  Row
          order within a table is submission order.
        """
        if self.scheduler is not None:
            return self.drain()
        # engine lock: a user-called flush must not interleave with a
        # concurrent global-mode submit() appending to the buffer
        with self._engine_lock:
            if self._buffered == 0:
                return {}
            batch = {n: q for n, q in self._buffer.items() if q}
            out = self.serve(batch)
            self._buffer = {n: [] for n in self.names}
            self._buffered = 0
            return out

    # ------------------------------------------- tiered host path (§9) ----

    def _ingest_many(self, items: List[tuple], on_error=None) -> None:
        """Routes a run of stamped ``(table, seq, query)`` items into the
        engine, flushing whatever falls due on the way.

        The single entry point shared by the inline async submit path
        (a run of one) and the thread driver's loop (a run of what the
        hand-off queue held) — routing must happen where ``_completed``
        is owned (the driver thread, when running), because a due host
        flush appends results directly.  One
        :meth:`FlushScheduler.push_many` routes the run; it stops at
        every due point for :meth:`_maybe_flush`, so each flush holds
        what one-by-one routing would have given it.  A tiered server
        routes item by item: a host flush between two items can hit a
        patch barrier that changes residency.

        ``on_error`` (the driver's stash) takes a flush failure, and the
        run goes on routing, as one-by-one ingest would have; without
        it the failure propagates.
        """
        t0 = time.perf_counter()
        work0 = self._flush_work_s
        flush = self._maybe_flush
        if on_error is not None:
            def flush():
                try:
                    self._maybe_flush()
                except Exception as e:
                    on_error(e)
        # a tiered server's runs are one item long (see above)
        runs = [items] if self._residency is None else [[i] for i in items]
        try:
            for run in runs:
                self.stats.route_chunks += 1
                try:
                    if (self._residency is None
                            or not self._route_host(*run[0])):
                        self.scheduler.push_many(run, flush)
                except Exception as e:
                    if on_error is None:
                        raise
                    on_error(e)
        finally:
            self.stats.route_s += (time.perf_counter() - t0
                                   - (self._flush_work_s - work0))
            self.stats.routed += len(items)

    def _route_host(self, table: str, seq: int, query) -> bool:
        """Detours a cold query into the host fetch queue.

        Every submission (hot or cold) advances the tier tick, so a
        queued cold query's deadline fires even in a hot-dominated
        stream.  Returns True when the query was queued host-side.
        """
        if self._residency is None:
            return False
        self._tick += 1
        arr = np.asarray(list(query), dtype=np.int64)
        if self._residency.is_resident(table, arr):
            self._maybe_flush_host()
            # the host flush above may have hit a patch barrier, which
            # pages groups and refreshes residency — re-check under the
            # post-patch plan: pushing a query whose group was just
            # evicted into the scheduler would raise on the cold group
            # instead of detouring host-side
            if self._residency.is_resident(table, arr):
                self.stats.hot_queries += 1
                return False
        self.stats.host_queries += 1
        self._host_queue.push(table, seq, arr, self._tick)
        self._maybe_flush_host()
        return True

    def _maybe_flush_host(self) -> None:
        reason = self._host_queue.due(self._tick)
        if reason is None:
            return
        if reason == "deadline":
            self.stats.host_deadline_flushes += 1
        t0 = time.perf_counter()
        self._flush_host_queue()
        self._flush_work_s += time.perf_counter() - t0

    def _flush_host_queue(self, *, forced: bool = False) -> None:
        """Serves every queued cold query via the host gather+sum path.

        The cold tier's compute: the same distinct-rows-summed oracle
        semantics the kernels are pinned against (and the watchdog's
        degrade path uses), so a capacity-bounded server stays
        bit-identical to the uncapped one on integer tables.  The
        batch's loads feed the drift tracker FIRST — host traffic is
        how a cold group earns its way in — and when that staged a
        paging patch on an un-forced flush, a barrier is triggered so
        cold-only traffic still reaches a patch-application point.
        ``forced`` marks the barrier's own drain (never re-enters).
        """
        if self._host_queue is None or len(self._host_queue) == 0:
            return
        entries = self._host_queue.take()
        self.stats.host_flushes += 1
        if self.tracker is not None:
            self.tracker.observe(
                self._residency.host_group_loads(entries), len(entries)
            )
            self._maybe_stage()
        rows_of: Dict[str, Tuple[List[int], List[np.ndarray]]] = {}
        for table, seq, query in entries:
            seqs, rows = rows_of.setdefault(table, ([], []))
            seqs.append(seq)
            rows.append(self._cold_row(table, query))
        now = time.perf_counter()
        for table, (seqs, rows) in rows_of.items():
            self._record_completed(
                table, np.asarray(seqs, dtype=np.int64), np.stack(rows), now
            )
        if not forced and self._staged is not None:
            # cold-dominated traffic may never trip a device flush — the
            # staged paging patch would otherwise wait forever
            self._barrier()

    def _cold_row(self, table: str, query) -> np.ndarray:
        """One query's host gather+sum row (distinct rows, zeros when
        empty) — the cold-tier twin of the degrade path's kernel."""
        ids = np.unique(np.asarray(query, dtype=np.int64))
        tab = self._host_tables[table]
        row = (tab[ids].sum(axis=0) if ids.size
               else np.zeros(self.dim, dtype=tab.dtype))
        return row.astype(tab.dtype, copy=False)

    def _serve_cold_rows(self, table: str, queries) -> np.ndarray:
        """Stacked host rows for the sync path's cold sub-batch."""
        return np.stack([self._cold_row(table, q) for q in queries])

    # ------------------------------------------------- async flush engine --

    def _maybe_flush(self) -> None:
        """Dispatches every home the policy says is due.

        If a plan patch is staged, the next trigger forces a **barrier**
        instead (DESIGN.md §7.3): the pipeline drains under the old
        plan, the patch applies atomically, and traffic resumes under
        the new one — a patch never lands between in-flight flushes.
        """
        due = self.scheduler.due_homes()
        if not due:
            return
        t0 = time.perf_counter()
        try:
            if self._staged is not None:
                self._barrier()
                return
            for home in due:
                self._flush_home(home)
        finally:
            self._flush_work_s += time.perf_counter() - t0

    def _flush_home(self, home: int, *, forced: bool = False) -> None:
        """Compiles and dispatches one home's pending batch (no block).

        The dispatch goes through the self-healing loop
        (:meth:`_heal_dispatch`, DESIGN.md §8): transient failures
        retry in place with backoff, persistent failures bisect down to
        (and quarantine) single offenders.  Only an error the policy
        does not absorb (``quarantine=False``, the legacy contract)
        requeues the whole batch in submission order — with its
        deadline clock intact — before re-raising.  ``forced`` marks
        barrier flushes, which are not policy-triggered and must not
        count as deadline firings.
        """
        if not forced and self.scheduler.due_reason(home) == "deadline":
            self.stats.deadline_flushes += 1
        first_tick = self.scheduler.first_tick(home)
        first_wall = self.scheduler.first_wall(home)
        entries, participants = self.scheduler.take(home)
        if not entries:
            return
        try:
            admitted = self._heal_dispatch(home, entries, participants)
        except Exception:
            self.scheduler.requeue(home, entries, first_tick=first_tick,
                                   first_wall=first_wall)
            raise
        # admission is OUTSIDE the requeue guard: a retire failure while
        # trimming the pipeline must not requeue a batch that is already
        # in flight (it would be served twice)
        for entry in admitted:
            self._admit(home, entry)

    def _heal_dispatch(self, home, entries, participants) -> List[_InFlight]:
        """Self-healing dispatch of one batch (DESIGN.md §8).

        State machine: up to ``max_retries`` in-place re-dispatches with
        jittered exponential backoff; a batch that still fails and
        holds > 1 queries **bisects** (both halves heal independently —
        repeated failure converges on single offenders in
        ``O(log batch)`` rounds); a single query that still fails is
        **quarantined** with its error in the ledger and dropped, so
        one poisoned query can never wedge its home.  Under the legacy
        policy (``quarantine=False``) the terminal error re-raises
        instead and the caller requeues.  Returns the successfully
        dispatched entries (metadata attached) for the caller to admit;
        a healed transient records its first-failure→dispatch recovery
        latency.
        """
        policy = self.retry
        ledger = self.stats.ledger
        t_first = None
        last: Optional[Exception] = None
        for attempt in range(policy.max_retries + 1):
            try:
                entry = self._compile_and_dispatch(entries, participants)
            except Exception as e:
                last = e
                if t_first is None:
                    t_first = time.perf_counter()
                if attempt < policy.max_retries:
                    pause = policy.backoff_s(attempt, self._retry_rng)
                    ledger.retries += 1
                    ledger.backoff_s += pause
                    if pause > 0:
                        time.sleep(pause)
                continue
            if t_first is not None:
                ledger.record_recovery(time.perf_counter() - t_first)
            entry.home = home
            entry.entries = entries
            entry.participants = participants
            return [entry]
        if policy.quarantine and policy.bisect and len(entries) > 1:
            ledger.bisections += 1
            mid = len(entries) // 2
            return (self._heal_dispatch(home, entries[:mid], participants)
                    + self._heal_dispatch(home, entries[mid:], participants))
        if policy.quarantine:
            # terminal: drop the offender(s), keep the home serving.
            # With bisection on, entries is a single isolated query;
            # with it off, the whole batch quarantines (recorded).
            for table, seq, _query in entries:
                prod, local = self._registry.decode(seq)
                ledger.quarantine(table, local, last, producer=prod)
                self.stats.stamps.dropped(table, seq)
            self.scheduler.record_quarantine(len(entries))
            return []
        raise last

    def _admit(self, home, entry: _InFlight) -> None:
        """Enqueues one dispatched flush and trims the pipeline."""
        self._in_flight.append(entry)
        # peak is sampled at APPEND time — the queue transiently holds
        # max_in_flight + 1 entries before the retire loop below trims
        # it, and that transient depth is exactly what the stat reports
        self.stats.in_flight_peak = max(
            self.stats.in_flight_peak, len(self._in_flight)
        )
        self.stats.record_flush_home(home)
        # drift bookkeeping is pure host work: it overlaps this flush's
        # device execution exactly like the next flush's compile does
        self._observe_and_stage(entry.fused, entry.n_queries)
        while len(self._in_flight) > self.policy.max_in_flight:
            self._retire_oldest()

    def _device_busy(self) -> bool:
        """Whether any in-flight flush is still executing on device.

        Feeds the ``hidden_compile_s`` accounting, whose contract is a
        conservative LOWER bound on genuinely-overlapped compile time —
        so an array type without ``is_ready`` (e.g. an already-
        materialized NumPy output from a stubbed dispatch) counts as
        idle, never as busy.
        """
        return any(not self._entry_ready(e) for e in self._in_flight)

    def _compile_and_dispatch(
        self,
        entries: List[tuple],
        participants: List[int] | None,
    ) -> _InFlight:
        """Host-compiles a batch and dispatches its kernel, non-blocking.

        The double-buffered ordering (DESIGN.md §7.2): this host compile
        runs while any earlier flush still executes on device — the
        ``record_compile(hidden=...)`` accounting below is exactly that
        overlap, sampled at compile END so a compile only counts as
        hidden if device work was genuinely still running when it
        finished (a conservative lower bound).  ``block_until_ready``
        happens only at result hand-off (:meth:`_retire_oldest`).

        Mutates no engine state besides stats — a raise anywhere leaves
        the pipeline exactly as it was (the caller retries or requeues).
        The fault injector's compile seam fires before the compile and
        its dispatch seam between compile and kernel dispatch
        (DESIGN.md §8); an injected hang tags the entry so readiness
        polling simulates the hung device.
        """
        t0 = time.perf_counter()
        flush = self._flush_seq
        self._flush_seq += 1
        if self._injector is not None:
            self._injector.on_compile(entries)
        by_table: Dict[str, Tuple[List[int], List[list]]] = {}
        for table, seq, query in entries:
            seqs, qs = by_table.setdefault(table, ([], []))
            seqs.append(seq)
            qs.append(query)
        served = [n for n in self.names if n in by_table]
        with TraceAnnotation("recross.compile", flush=flush):
            fused, sbq = self._compile_batch(
                served, {n: by_table[n][1] for n in served},
                participants=participants,
            )
        self.stats.record_compile(
            time.perf_counter() - t0, hidden=self._device_busy()
        )
        hang_s = (
            self._injector.on_dispatch() if self._injector is not None
            else None
        )
        with TraceAnnotation("recross.dispatch", flush=flush):
            # the flush's one host→device transfer: ids and bitmaps
            outs = crossbar_reduce_tables(
                self.shard_images, self._put_schedule(sbq, count=True),
                fused.spans,
                mesh=self.mesh, axis_name=self.axis_name,
                combine=self.combine, combine_chunks=self.combine_chunks,
                dynamic_switch=self.dynamic_switch,
                interpret=self.interpret,
            )
        return _InFlight(
            outs=outs, sbq=sbq, served=served,
            seqs={n: np.asarray(by_table[n][0], dtype=np.int64)
                  for n in served},
            t0=t0, n_queries=sum(len(by_table[n][1]) for n in served),
            fused=fused,
            t_dispatch=time.perf_counter(), hang_s=hang_s, flush=flush,
        )

    def _retire_oldest(self) -> None:
        """Retires the oldest in-flight flush and stashes its rows.

        The §8 failure seams live here: a watchdog timeout (hung device
        work) degrades the flush to the host path instead of blocking
        forever; a retire-time device fault re-enters the healing loop
        (re-compile + re-dispatch of the same batch) under the default
        policy, or requeues + re-raises under the legacy one.
        """
        e = self._in_flight.popleft()
        with TraceAnnotation("recross.retire", flush=e.flush):
            self._retire(e)

    def _retire(self, e: _InFlight) -> None:
        """The body of :meth:`_retire_oldest`, inside its span."""
        try:
            if self._injector is not None:
                self._injector.on_retire()
            outs = self._wait_outputs(e)
        except FlushTimeout:
            self._degrade(e)
            return
        except Exception:
            if self.retry.quarantine and e.entries is not None:
                # late device fault: the outputs are lost but the raw
                # batch is not — heal it like a dispatch-time failure
                self.stats.ledger.retries += 1
                for entry in self._heal_dispatch(
                    e.home, e.entries, e.participants
                ):
                    self._admit(e.home, entry)
                return
            if e.entries is not None:
                # legacy contract: the batch goes back to its home so
                # the next barrier retries it, then the error surfaces
                self.scheduler.requeue(e.home, e.entries)
            raise
        rows = [np.asarray(out) for out in outs]
        now = time.perf_counter()
        self.stats.record(e.sbq, self.dim, now - e.t0, e.n_queries)
        for name, r in zip(e.served, rows):
            self._record_completed(name, e.seqs[name], r, now)

    def _record_completed(
        self, table: str, seqs: np.ndarray, rows: np.ndarray, now: float
    ) -> None:
        """Stashes one flush's rows of ``table`` for :meth:`drain`,
        under the results lock (a drain on another thread may be
        extracting concurrently), and stamps them complete at ``now``."""
        self.stats.stamps.completed(table, seqs, now)
        with self._results_lock:
            self._completed[table].append((seqs, rows))

    def _wait_outputs(self, e: _InFlight) -> List[np.ndarray]:
        """Blocks on one flush's outputs, bounded by the watchdog.

        Without a watchdog (and without an injected hang) this is a
        plain ``block_until_ready``.  With one, readiness is polled and
        :class:`FlushTimeout` raises once ``watchdog_s`` has elapsed
        since the flush's kernel DISPATCH — a flush that hung long
        before the barrier reached it times out immediately.  An
        injected infinite hang with no watchdog configured also times
        out (degrading is always preferred to wedging ``drain()``).
        """
        wd = self.retry.watchdog_s
        if wd is None and e.hang_s is None:
            return [jax.block_until_ready(o) for o in e.outs]
        while not self._entry_ready(e):
            waited = time.perf_counter() - e.t_dispatch
            if wd is not None and waited >= wd:
                raise FlushTimeout(
                    f"flush not ready {waited:.3f}s after dispatch "
                    f"(watchdog {wd}s)"
                )
            if wd is None and e.hang_s == math.inf:
                raise FlushTimeout(
                    "flush hung forever with no watchdog configured"
                )
            time.sleep(self.retry.watchdog_poll_s)
        return [jax.block_until_ready(o) for o in e.outs]

    def _degrade(self, e: _InFlight) -> None:
        """Serves one timed-out flush via the inline host/reference path.

        The graceful half of the watchdog: the hung device outputs are
        abandoned and every query in the flush is recomputed as a host
        gather+sum over the logical table (the oracle semantics the
        kernels are pinned against — distinct rows summed, empty bags
        zero), so ``drain()`` still returns every row.  Recorded as a
        degraded + timed-out flush in the ledger.
        """
        ledger = self.stats.ledger
        ledger.timed_out_flushes += 1
        ledger.degraded_flushes += 1
        if e.entries is None:  # no raw batch — nothing to recompute from
            raise FlushTimeout(
                "timed-out flush carries no raw batch to degrade with"
            )
        rows_of: Dict[str, Tuple[List[int], List[np.ndarray]]] = {}
        for table, seq, query in e.entries:
            ids = np.unique(np.asarray(list(query), dtype=np.int64))
            tab = self._host_tables[table]
            row = (tab[ids].sum(axis=0) if ids.size
                   else np.zeros(self.dim, dtype=tab.dtype))
            seqs, rows = rows_of.setdefault(table, ([], []))
            seqs.append(seq)
            rows.append(row.astype(tab.dtype, copy=False))
        now = time.perf_counter()
        for table, (seqs, rows) in rows_of.items():
            self._record_completed(
                table, np.asarray(seqs, dtype=np.int64), np.stack(rows), now
            )
        self.stats.record(e.sbq, self.dim, now - e.t0, e.n_queries)

    def _barrier(self) -> None:
        """Flush-everything + drain + apply any staged patch atomically.

        Pending queries were routed (and are compiled here) under the
        plan they were submitted against; only after every dispatched
        flush retires does the staged patch swap placement arrays and
        the scheduler re-derive its routing.

        With the thread driver running, a caller on any other thread
        posts a barrier token onto the hand-off queue and joins the
        driver at it: the driver first drains every earlier hand-off
        item (FIFO), then runs this barrier inline — so the ordering
        guarantees are identical to the inline engine's.
        """
        driver = self._driver
        if (driver is not None
                and threading.current_thread() is not driver):
            handoff = self._handoff
            if handoff is not None:
                done = threading.Event()
                handoff.put(("barrier", done))
                # never wait forever on a driver that died or was
                # closed under us — poll its liveness while waiting
                while not done.wait(0.1):
                    if self._driver is not driver or not driver.is_alive():
                        break
                self._raise_driver_error()
                return
        with TraceAnnotation("recross.barrier", flush=self._flush_seq):
            for home in self.scheduler.homes_with_pending():
                self._flush_home(home, forced=True)
            while self._in_flight:
                self._retire_oldest()
            # queued cold work drains with the pipeline (host rows read
            # the master image, so ordering vs the patch below is
            # immaterial — but a drain must hand back every submitted
            # query's row)
            self._flush_host_queue(forced=True)
            self._apply_staged_patch()
        self.stats.barrier_flushes += 1

    # ------------------------------------------------------ thread driver --

    def _start_driver(self) -> None:
        self._handoff = queue.Queue(maxsize=self.policy.handoff_depth)
        self._driver_stop = threading.Event()
        self._driver = threading.Thread(
            target=self._driver_loop, name="recross-flush-driver", daemon=True
        )
        self._driver.start()

    def _driver_loop(self) -> None:
        """Dispatch/retire loop of the thread driver (DESIGN.md §7.2).

        Pops hand-off items FIFO: after a query item it keeps popping
        without waiting, up to ``policy.batch_size`` query items or the
        next barrier token, and routes the run in one
        :meth:`_ingest_many` (the inline engine's submit path, run by
        run); a barrier token runs :meth:`_barrier` inline, after the
        run before it, and wakes its waiter.  While the queue
        is idle, in-flight flushes whose outputs are already
        materialized retire opportunistically, so result hand-off
        latency does not wait for the next submission.  A flush failure
        leaves its batch requeued (the :meth:`_flush_home` contract)
        and is stashed for :meth:`_raise_driver_error` to surface on
        the caller's thread.
        """
        while not self._driver_stop.is_set():
            try:
                item = self._handoff.get_nowait()
            except queue.Empty:
                item = self._wait_for_item()
            if item is None:
                try:
                    self._retire_ready()
                    # a wall deadline (policy.deadline_s) must fire even
                    # when no submission arrives to consult the trigger —
                    # the idle loop is the only clock a quiet stream has
                    if self.policy.deadline_s is not None:
                        self._maybe_flush()
                except Exception as e:  # device fault surfacing at retire
                    self._stash_driver_error(e)
                continue
            token = item if item[0] == "barrier" else None
            if token is None:
                run = [item[1:]]
                while len(run) < self.policy.batch_size:
                    try:
                        item = self._handoff.get_nowait()
                    except queue.Empty:
                        break
                    if item[0] == "barrier":
                        token = item
                        break
                    run.append(item[1:])
                try:
                    # a failed flush leaves its batch requeued; the
                    # failure surfaces at the caller's next submit() or
                    # drain() (retry contract)
                    self._ingest_many(run, on_error=self._stash_driver_error)
                finally:
                    # a popped-but-unprocessed item is invisible to both
                    # empty() and the scheduler — unfinished_tasks is the
                    # counter that still sees it (seq-reset guard)
                    for _ in run:
                        self._handoff.task_done()
            if token is not None:
                try:
                    self._barrier()
                except Exception as e:
                    self._stash_driver_error(e)
                finally:
                    # task_done BEFORE waking the waiter: the seq-reset
                    # guard reads unfinished_tasks right after a drain's
                    # barrier returns, and this token must not count
                    self._handoff.task_done()
                    token[1].set()

    def _wait_for_item(self):
        """Blocks up to 5 ms on the empty hand-off queue for the next
        item (``None`` if none came); the wait is ``engine_wait_s``."""
        t0 = time.perf_counter()
        try:
            return self._handoff.get(timeout=0.005)
        except queue.Empty:
            return None
        finally:
            self.stats.engine_wait_s += time.perf_counter() - t0

    def _retire_ready(self) -> None:
        """Retires in-flight flushes whose outputs are already
        materialized, oldest-first (hand-off order preserved).  With a
        watchdog configured, a hung HEAD entry past its deadline is
        retired proactively here (taking the timeout/degrade path) so
        a stuck flush degrades while the driver idles, not only when a
        barrier finally reaches it."""
        while self._in_flight and self._entry_ready(self._in_flight[0]):
            self._retire_oldest()
        wd = self.retry.watchdog_s
        if (wd is not None and self._in_flight
                and time.perf_counter() - self._in_flight[0].t_dispatch >= wd):
            self._retire_oldest()

    @staticmethod
    def _entry_ready(e: _InFlight) -> bool:
        # an injected hang simulates a device that never reports ready
        # until hang_s has elapsed since dispatch (math.inf = never) —
        # the watchdog path is exercised without wedging real hardware
        if e.hang_s is not None and (
            time.perf_counter() - e.t_dispatch
        ) < e.hang_s:
            return False
        for o in e.outs:
            try:
                if not o.is_ready():
                    return False
            except AttributeError:  # no is_ready ⇒ already materialized
                continue
        return True

    def _stash_driver_error(self, e: BaseException) -> None:
        """Stashes one driver failure for the caller's thread, bounded.

        The first failure is what the caller sees first; later ones
        queue behind it (up to :data:`_MAX_STASHED_ERRORS`) instead of
        silently overwriting, and overflow beyond the bound is counted
        in the ledger — never dropped without trace.
        """
        if len(self._driver_errors) < _MAX_STASHED_ERRORS:
            self._driver_errors.append(e)
        else:
            self._suppressed_errors += 1
            self.stats.ledger.driver_errors_suppressed += 1

    def _raise_driver_error(self) -> None:
        """Re-raises the OLDEST failure stashed by the driver thread.

        The message carries the count of further failures still stashed
        (and of any suppressed past the bound) so a burst of errors is
        never mistaken for a single one; each later
        ``submit()``/``drain()`` surfaces the next.
        """
        if not self._driver_errors:
            return
        err = self._driver_errors.popleft()
        more = len(self._driver_errors) + self._suppressed_errors
        if more and err.args and isinstance(err.args[0], str):
            suppressed = (
                f", {self._suppressed_errors} suppressed past the stash "
                f"bound" if self._suppressed_errors else ""
            )
            err.args = (
                f"{err.args[0]} [+{more} more driver failure(s) "
                f"stashed{suppressed}]",
            ) + err.args[1:]
        raise err

    #: driver join bound at close(); a driver stuck in un-watchdogged
    #: device work is abandoned (daemon thread) rather than wedging the
    #: caller, and the leak is recorded in the ledger's lost-work summary
    _CLOSE_JOIN_S = 30.0

    def close(self) -> None:
        """Stops the thread driver (if running) and closes the front
        door: any later :meth:`submit` — including one already racing
        this call on another thread — gets a clean ``RuntimeError``
        instead of work that would silently never flush.  Hand-off
        items the driver had not yet popped are pushed back into the
        scheduler, so no submitted query (or its stamped sequence id)
        is ever dropped — a later :meth:`drain` serves them inline
        (the driver does not restart).

        Idempotent and bounded: a second ``close()`` is a no-op, the
        driver join can never hang past :data:`_CLOSE_JOIN_S` (a driver
        wedged in un-watchdogged device work is abandoned — it is a
        daemon thread — and recorded), and a producer blocked in a
        full hand-off ``put()`` is unblocked by the push-back loop
        below (its item is drained like the rest), so close can never
        deadlock against concurrent submitters.  Work still unserved
        at close (requeued batches, pushed-back hand-off items,
        unretired in-flight flushes) is summarized into the ledger's
        ``lost_work`` instead of silently discarded.
        """
        with self._stamp_lock:
            already = self._closed
            self._closed = True
        if already:
            return
        leaked = False
        if self._driver is not None:
            self._driver_stop.set()
            self._driver.join(timeout=self._CLOSE_JOIN_S)
            leaked = self._driver.is_alive()
            self._driver = None
        backlog: List[tuple] = []
        if self._handoff is not None:
            # drain until no producer is still inside put(): every get
            # below frees a slot, so a submitter blocked on the full
            # queue completes its put and exits via _pending_submits
            while True:
                try:
                    item = self._handoff.get_nowait()
                except queue.Empty:
                    with self._stamp_lock:
                        if (self._pending_submits == 0
                                and self._handoff.empty()):
                            break
                    time.sleep(0.001)
                    continue
                if item[0] == "barrier":
                    # a concurrent drain()'s token: wake the waiter
                    # (its barrier re-runs inline once the driver is
                    # observed gone)
                    item[1].set()
                else:
                    backlog.append(item[1:])
            self._handoff = None
            self.scheduler.push_many(backlog)
        if self.scheduler is not None:
            requeued = self.scheduler.pending_total()
        else:
            # engine lock: snapshot vs a concurrent global-mode submit
            with self._engine_lock:
                requeued = self._buffered
        unserved = {
            "requeued": requeued,
            "handoff_pushed_back": len(backlog),
            "in_flight": len(self._in_flight),
            "host_pending": (len(self._host_queue)
                             if self._host_queue is not None else 0),
            "stashed_errors": len(self._driver_errors),
            "driver_leaked": int(leaked),
        }
        if any(unserved.values()):
            self.stats.ledger.lost_work = unserved

    def __enter__(self) -> "ShardedEmbeddingServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def drain(self, producer=None) -> Dict[str, jax.Array]:
        """Barrier + result hand-off for async policies.

        Flushes every pending home, retires the whole in-flight queue,
        applies a staged plan patch (the only legal application point
        besides a triggered barrier), and returns everything served
        since the previous hand-off.  Under the thread driver this
        joins the driver at a barrier token; a failure stashed by the
        driver (or one raised by the barrier's own retry of requeued
        work) surfaces here — retry by draining again once the
        transient clears.

        With ``producer=None`` (a FULL drain) every completed row is
        returned, merged per table in the deterministic ``(local_seq,
        producer_id)`` order (DESIGN.md §10) — single-producer streams
        see exactly the pre-§10 submission order.  With ``producer=``
        a label, only that producer's rows return (in ITS submission
        order); every other stream's completed work stays stashed for
        its own drain — no cross-producer head-of-line result mixing.

        Returns:
          ``{table: (n_queries, dim)}`` arrays; ``{}`` for tables with
          no completed work (for this producer).
        """
        if self.scheduler is None:
            if producer is not None:
                raise ValueError(
                    "drain(producer=...) needs an async flush policy"
                )
            return self.flush()
        self._raise_driver_error()
        if self._driver is not None:
            self._barrier()
        else:
            # inline engine: serialize against concurrent submits
            with self._engine_lock:
                self._barrier()
        out: Dict[str, jax.Array] = {}
        with self._results_lock:
            if producer is None:
                for name in self.names:
                    chunks = self._completed[name]
                    if not chunks:
                        continue
                    seqs = np.concatenate([c[0] for c in chunks])
                    rows = np.concatenate([c[1] for c in chunks])
                    # packed ids sort as (local_seq, producer_id): the
                    # cross-producer merge is deterministic, and within
                    # one producer it is that producer's FIFO
                    out[name] = jnp.asarray(rows[np.argsort(seqs)])
                self._completed = {n: [] for n in self.names}
            else:
                pid = self._registry.pid(producer)
                stride = self._registry.stride
                for name in self.names:
                    chunks = self._completed[name]
                    if not chunks or pid is None:
                        continue
                    seqs = np.concatenate([c[0] for c in chunks])
                    rows = np.concatenate([c[1] for c in chunks])
                    mine = (seqs % stride) == pid
                    if mine.any():
                        sel = seqs[mine]
                        out[name] = jnp.asarray(
                            rows[mine][np.argsort(sel)]
                        )
                    rest = ~mine
                    self._completed[name] = (
                        [(seqs[rest], rows[rest])] if rest.any() else []
                    )
        # sequence ids restart ONLY at full quiescence — nothing
        # pending, in flight, queued host-side, stashed for another
        # producer's drain, or still inside a submit()'s stamped-but-
        # undelivered window (the hand-off's unfinished_tasks counts
        # popped-but-unprocessed items too).  Resetting any earlier
        # would hand new submissions colliding packed seqs and
        # scramble a later drain's merge order.  Per-producer drains
        # never reset: other streams' counters are always live.
        if producer is None:
            with self._results_lock:
                with self._stamp_lock:
                    handoff = self._handoff
                    busy = (
                        self._pending_submits > 0
                        or (handoff is not None
                            and handoff.unfinished_tasks > 0)
                    )
                    if (not busy
                            and self.scheduler.pending_total() == 0
                            and not self._in_flight
                            and (self._host_queue is None
                                 or len(self._host_queue) == 0)
                            and not any(self._completed.values())):
                        # opt-in structural validation at quiescence
                        # (RECROSS_VALIDATE=1, DESIGN.md §12) — the
                        # one moment every invariant must hold at once
                        from repro.analysis.invariants import (
                            validation_enabled,
                        )

                        if validation_enabled():
                            from repro.analysis.invariants import (
                                validate_server_state,
                            )

                            validate_server_state(self, quiesced=True)
                        self._registry.reset_seqs()
                        # records of the old sequence spaces close
                        # with them: local seqs restart at 0
                        self.stats.stamps.seal()
        return out

    def take_completion_stamps(self) -> List[StampRecords]:
        """Takes the keyed stamps of every bag completed since the last
        take (async policies): one :class:`~repro.serve.stamps.
        StampRecords` per ``(epoch, producer, table)``, with the local
        seq, the submit stamp and the completion stamp of each bag
        (``perf_counter`` seconds), ``producer`` the label the bags were
        submitted under.  An epoch ends at each quiesced :meth:`drain`,
        which restarts local seqs at 0.  Taking clears the records;
        quarantined bags never complete and have none."""
        labels = self._registry.producers()
        out = self.stats.stamps.take()
        for r in out:
            r.producer = labels[r.producer]
        return out

    # ------------------------------------------------------------- report --

    def _snapshot_closed(self) -> bool:
        """Reads the closed flag under the stamp lock that guards it."""
        with self._stamp_lock:
            return self._closed

    def report(self) -> Dict[str, object]:
        """Serving + placement accounting for dashboards and benches.

        Returns a dict with:
          * ``tables`` — served table names (sorted).
          * ``plan`` — tile residency / replication overhead of the
            *current* (possibly patched) plan
            (:meth:`ShardPlan.memory_summary`).
          * ``serve`` — cumulative flush stats
            (:meth:`ShardedServeStats.summary`), including the replan
            counters.
          * ``mode`` — ``"shard_map"`` or ``"emulated"``.
          * ``retry`` — the live :class:`~repro.serve.faults.
            RetryPolicy` knobs; the matching error ledger rides inside
            ``serve["faults"]`` (retries, backoff, quarantined queries,
            degraded/timed-out flushes, lost work at close).
          * ``faults`` — fault-injection plan + per-seam attempt/
            injection counters (only when a ``faults=`` plan is set).
          * ``replan`` — drift/replanning state (only when enabled):
            current drift vs the live plan, tracker readiness, staged
            patch summary if one is waiting for the next flush.
        """
        rep: Dict[str, object] = {
            "tables": self.names,
            "plan": self.plan.memory_summary(),
            "serve": self.stats.summary(),
            "mode": "shard_map" if self.mesh is not None else "emulated",
            "retry": dataclasses.asdict(self.retry),
            # process-global jit-dispatch cache pressure (bounded LRUs
            # in kernels.sharded) — participants churn shows up here
            "dispatch_cache": dispatch_cache_stats(),
        }
        if self.tiers is not None:
            rep["tiers"] = {
                "capacity_tiles": self._capacity_tiles,
                "hysteresis": self.tiers.hysteresis,
                "cold_groups": int(self.plan.cold_groups.size),
                "cold_tiles": self.plan.cold_tiles,
                "resident_groups": int(self.plan.resident_group.sum()),
                "host_queue": self._host_queue.state(),
            }
        if self._injector is not None:
            rep["faults"] = self._injector.summary()
        if self.scheduler is not None:
            rep["scheduler"] = {
                "policy": self.policy.kind,
                "batch_size": self.policy.batch_size,
                "union_budget": self.policy.union_budget,
                "deadline": self.policy.deadline,
                "deadline_s": self.policy.deadline_s,
                "max_in_flight": self.policy.max_in_flight,
                "in_flight": len(self._in_flight),
                "threaded": self.policy.threaded,
                "handoff_depth": self.policy.handoff_depth,
                "handoff_pending": (
                    self._handoff.qsize() if self._handoff is not None else 0
                ),
                "closed": self._snapshot_closed(),
                **self.scheduler.state(),
                "producers": self._registry.state(),
            }
        if self.tracker is not None:
            rep["replan"] = {
                "threshold": self.replan_cfg.threshold,
                "half_life": self.replan_cfg.half_life,
                "drift": self.tracker.drift_from(
                    self.plan.group_load, segments=self._segments
                ),
                "observed_queries": self.tracker.observed_queries,
                "ready": self.tracker.ready,
                "staged": (
                    self._staged.summary() if self._staged is not None else None
                ),
                "image_capacity": int(self.shard_images.shape[1]),
                # free headroom above the highest allocated slot — what
                # slack age-out (shrink_streak) reclaims
                "slack_slots": int(
                    self.shard_images.shape[1] - self.plan.max_local_tiles
                ),
                "demote_streak": self._demote_streak,
            }
        return rep
