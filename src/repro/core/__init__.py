"""ReCross core: the paper's contribution as composable pieces.

Offline phase: cooccurrence → grouping (Alg. 1) → replication (Eq. 1) →
mapping.  Online phase: dynamic_switch + reduction (JAX) / kernels (Pallas)
+ simulator (ReRAM cost accounting).
"""

from repro.core.cooccurrence import CoOccurrenceGraph, build_cooccurrence, merge_graphs
from repro.core.grouping import (
    Grouping,
    activations_per_query,
    correlation_aware_grouping,
    frequency_grouping,
    naive_grouping,
)
from repro.core.replication import (
    ReplicationPlan,
    log_scaled_copies,
    plan_replication,
    shard_replication_sets,
)
from repro.core.mapping import (
    ActivationSet,
    CrossbarLayout,
    build_layout,
    compile_activations,
    query_tile_bitmaps,
)
from repro.core.dynamic_switch import (
    MAC_MODE,
    READ_MODE,
    energy_breakeven_rows,
    jnp_select_mode,
    mode_statistics,
    popcount,
    select_mode,
)
from repro.core.energy import DEFAULT_RERAM, DEFAULT_TPU, ReRAMCostModel, TPUCostModel
from repro.core.simulator import (
    SimReport,
    simulate_batch,
    simulate_cpu_baseline,
    simulate_nmars_baseline,
)
from repro.core.reduction import (
    BlockedQueries,
    BlockUnionTracker,
    CompiledQueries,
    FusedActivations,
    ShardedBlockedQueries,
    block_compiled_queries,
    compile_queries,
    concat_compiled_queries,
    fuse_activations,
    fused_group_loads,
    offset_compiled_queries,
    reduce_dense_oracle,
    reduce_via_layout,
    shard_block_activations,
    shard_block_queries,
)
from repro.core import baselines

__all__ = [
    "CoOccurrenceGraph", "build_cooccurrence", "merge_graphs",
    "Grouping", "correlation_aware_grouping", "frequency_grouping",
    "naive_grouping", "activations_per_query",
    "ReplicationPlan", "log_scaled_copies", "plan_replication",
    "shard_replication_sets",
    "ActivationSet", "CrossbarLayout", "build_layout",
    "compile_activations", "query_tile_bitmaps",
    "READ_MODE", "MAC_MODE", "popcount", "select_mode", "jnp_select_mode",
    "energy_breakeven_rows", "mode_statistics",
    "ReRAMCostModel", "TPUCostModel", "DEFAULT_RERAM", "DEFAULT_TPU",
    "SimReport", "simulate_batch", "simulate_cpu_baseline",
    "simulate_nmars_baseline",
    "BlockedQueries", "BlockUnionTracker", "CompiledQueries",
    "FusedActivations", "ShardedBlockedQueries",
    "block_compiled_queries", "compile_queries", "concat_compiled_queries",
    "fuse_activations", "fused_group_loads",
    "offset_compiled_queries", "reduce_dense_oracle", "reduce_via_layout",
    "shard_block_activations", "shard_block_queries",
    "baselines",
]
