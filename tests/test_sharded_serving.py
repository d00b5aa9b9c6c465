"""Sharded multi-table serving: the shard-local reduction + cross-shard
combine must be BIT-IDENTICAL to the single-device flat ``crossbar_reduce``
reference for every shard count, including padding tiles, ragged batches
and the dynamic-switch READ path.

Bit-identity is pinned on integer-valued float tables: every partial sum
is exactly representable, so any associativity-only difference between
the sharded combine and the flat accumulator would still compare equal —
what the test rejects is a *wrong or double-counted activation*, the
actual failure mode of a bad ownership split.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import (
    build_cooccurrence,
    build_layout,
    compile_queries,
    concat_compiled_queries,
    correlation_aware_grouping,
    offset_compiled_queries,
    plan_replication,
    shard_block_queries,
)
from repro.core.reduction import reduce_dense_oracle
from repro.data import zipf_queries
from repro.dist import build_fused_image, plan_shards
from repro.kernels import (
    combine_bytes_per_batch,
    crossbar_reduce,
    crossbar_reduce_sharded,
    crossbar_reduce_tables,
)


def _int_table(rows, dim, seed):
    """Integer-valued f32 table: partial sums are exact in float32."""
    return np.random.default_rng(seed).integers(
        -8, 9, size=(rows, dim)
    ).astype(np.float32)


def _pipeline(rows, hist, *, group_size=16, dim=128, batch_size=64):
    g = build_cooccurrence(hist, rows)
    grouping = correlation_aware_grouping(g, group_size)
    plan = plan_replication(grouping, g.freq, batch_size)
    layout = build_layout(grouping, plan, dim)
    return layout, plan, grouping.group_freq(g.freq)


def _sharded_setup(seed, batch, num_shards, *, q_block=4, rows=192, dim=128):
    hist = zipf_queries(rows, 48, 6.0, seed=seed)
    ev = zipf_queries(rows, batch, 6.0, seed=seed + 1)
    layout, plan, gfreq = _pipeline(rows, hist, dim=dim)
    table = _int_table(rows, dim, seed)
    fused = build_fused_image([layout], [table])
    sp = plan_shards([layout], [plan], num_shards, group_freqs=[gfreq])
    cq = compile_queries(layout, ev, replica_block=q_block)
    sbq = shard_block_queries(cq, sp, q_block)
    images = jnp.asarray(sp.build_shard_images(fused))
    flat = crossbar_reduce(
        jnp.asarray(fused), cq.tile_ids, cq.bitmaps
    )
    return images, sbq, flat, table, ev, sp, cq


# ------------------------------------------------------------ planner --


def test_plan_partitions_every_tile_exactly_once():
    hist = zipf_queries(128, 40, 5.0, seed=3)
    layout, plan, gfreq = _pipeline(128, hist)
    for S in (1, 2, 4):
        sp = plan_shards([layout], [plan], S, group_freqs=[gfreq])
        # every tile either owned by one shard or replicated on all
        for t in range(sp.num_tiles):
            holders = (sp.local_tile_of[:, t] >= 0).sum()
            if sp.shard_of_tile[t] < 0:
                assert holders == S
            else:
                assert holders == 1
        # local numbering is dense per shard
        for s in range(S):
            local = sp.local_tile_of[s][sp.local_tile_of[s] >= 0]
            assert sorted(local.tolist()) == list(range(sp.local_num_tiles[s]))
        # replica tiles of a sharded group stay together
        tile_group = np.repeat(
            np.arange(layout.num_groups), layout.copies
        )
        for g in range(layout.num_groups):
            owners = np.unique(sp.shard_of_tile[tile_group == g])
            assert owners.size == 1


def test_plan_is_deterministic_and_balanced():
    hist = zipf_queries(256, 64, 8.0, seed=7)
    layout, plan, gfreq = _pipeline(256, hist)
    a = plan_shards([layout], [plan], 4, group_freqs=[gfreq])
    b = plan_shards([layout], [plan], 4, group_freqs=[gfreq])
    np.testing.assert_array_equal(a.shard_of_group, b.shard_of_group)
    # greedy (descending-load, least-loaded-first) balance bound: no
    # shard exceeds the fair share by more than one group's load
    sharded = ~a.replicated_group
    if sharded.any():
        loads = np.zeros(4)
        np.add.at(loads, a.shard_of_group[sharded], a.group_load[sharded])
        fair = a.group_load[sharded].sum() / 4 + a.group_load[sharded].max()
        assert loads.max() <= fair, (loads, fair)
    # the zero-load cold tail must balance on TILES, not pile onto the
    # least-loaded shard: with all-zero loads the owned tile counts may
    # differ by at most one group's replica set
    cold = plan_shards(
        [layout], [plan], 4,
        group_freqs=[np.zeros(layout.num_groups)],
    )
    owned = np.zeros(4, dtype=np.int64)
    for s in cold.shard_of_tile:
        if s >= 0:
            owned[s] += 1
    if owned.sum():
        assert owned.max() - owned.min() <= int(layout.copies.max()), owned


def test_shard_images_padding_tiles_are_zero():
    hist = zipf_queries(96, 32, 5.0, seed=11)
    layout, plan, gfreq = _pipeline(96, hist)
    sp = plan_shards([layout], [plan], 4, group_freqs=[gfreq])
    fused = build_fused_image([layout], [_int_table(96, 128, 11)])
    imgs = sp.build_shard_images(fused)
    for s in range(4):
        n = int(sp.local_num_tiles[s])
        assert (imgs[s, n:] == 0).all()


# ------------------------------------------- sharded reduction parity --


@given(st.integers(0, 200), st.sampled_from([1, 2, 4]))
@settings(max_examples=6, deadline=None)
def test_sharded_reduce_bit_identical_to_flat_reference(seed, num_shards):
    batch = 10 + seed % 7   # ragged: exercises q_block padding rows
    images, sbq, flat, table, ev, _, _ = _sharded_setup(seed, batch, num_shards)
    out = crossbar_reduce_sharded(
        images, sbq.tile_ids, sbq.bitmaps, combine_chunks=2
    )[: sbq.batch]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(flat))
    # and against the layout-independent dense oracle
    oracle = reduce_dense_oracle(jnp.asarray(table), ev)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))


def test_sharded_reduce_padding_rows_are_zero():
    images, sbq, _, _, _, _, _ = _sharded_setup(5, 10, 2, q_block=4)
    out = np.asarray(crossbar_reduce_sharded(images, sbq.tile_ids, sbq.bitmaps))
    assert out.shape[0] == sbq.num_blocks * sbq.q_block
    assert (out[sbq.batch:] == 0).all()


def test_sharded_reduce_read_path_single_row_queries():
    """Single-row bags drive the dynamic-switch READ path on every shard;
    splitting a block across shards lowers per-shard popcounts, so the
    sharded kernel takes READ where the flat kernel took MAC — values
    must still agree exactly."""
    rows, dim = 128, 128
    hist = zipf_queries(rows, 40, 5.0, seed=21)
    ev = [[int(i)] for i in np.random.default_rng(21).integers(0, rows, 12)]
    ev += [[0, 1, 2, 3], []]
    layout, plan, gfreq = _pipeline(rows, hist, dim=dim)
    table = _int_table(rows, dim, 21)
    fused = build_fused_image([layout], [table])
    cq = compile_queries(layout, ev, replica_block=4)
    flat = crossbar_reduce(jnp.asarray(fused), cq.tile_ids, cq.bitmaps)
    for S in (1, 2, 4):
        sp = plan_shards([layout], [plan], S, group_freqs=[gfreq])
        sbq = shard_block_queries(cq, sp, 4)
        images = jnp.asarray(sp.build_shard_images(fused))
        for dyn in (True, False):
            out = crossbar_reduce_sharded(
                images, sbq.tile_ids, sbq.bitmaps, dynamic_switch=dyn
            )[: sbq.batch]
            np.testing.assert_array_equal(np.asarray(out), np.asarray(flat))


def test_shard_ownership_covers_every_activation_once():
    """Summed over shards, the sharded bitmaps must equal the flat
    compiled bitmaps per (query, fused tile) — no drop, no double count."""
    images, sbq, _, _, ev, sp, cq = _sharded_setup(9, 12, 4, q_block=4)
    q_block = sbq.q_block
    bms = np.asarray(sbq.bitmaps)       # (S, nb, mt, q, rows)
    ids = np.asarray(sbq.tile_ids)      # (S, nb, mt)
    got = {}
    for s in range(sp.num_shards):
        local_to_global = {}
        for t in range(sp.num_tiles):
            if sp.local_tile_of[s, t] >= 0:
                local_to_global[int(sp.local_tile_of[s, t])] = t
        for n in range(sbq.num_blocks):
            for m in range(sbq.max_tiles):
                if ids[s, n, m] < 0:
                    continue
                g = local_to_global[int(ids[s, n, m])]
                for k in range(q_block):
                    q = n * q_block + k
                    if bms[s, n, m, k].any():
                        key = (q, g)
                        assert key not in got, "activation double-owned"
                        got[key] = bms[s, n, m, k]
    # compare against the flat compile the sharded batch was built from
    fids = np.asarray(cq.tile_ids)
    fbms = np.asarray(cq.bitmaps)
    want = {}
    for q in range(fids.shape[0]):
        for sl in range(fids.shape[1]):
            if fids[q, sl] >= 0 and fbms[q, sl].any():
                want[(q, int(fids[q, sl]))] = fbms[q, sl]
    assert set(got) == set(want)
    for key in want:
        np.testing.assert_array_equal(got[key], want[key])


def test_per_shard_grid_never_exceeds_single_device_grid():
    """The acceptance invariant: shard-local unions are subsets of the
    global union, so the per-shard padded grid must not exceed the
    single-device blocked grid."""
    from repro.core import block_compiled_queries

    for seed in (1, 13):
        hist = zipf_queries(256, 64, 8.0, seed=seed)
        ev = zipf_queries(256, 32, 8.0, seed=seed + 1)
        layout, plan, gfreq = _pipeline(256, hist, dim=128)
        cq = compile_queries(layout, ev, replica_block=8)
        bq = block_compiled_queries(cq, 8)
        flat_cells = bq.num_blocks * bq.max_tiles
        for S in (1, 2, 4):
            sp = plan_shards([layout], [plan], S, group_freqs=[gfreq])
            sbq = shard_block_queries(cq, sp, 8)
            assert sbq.grid_cells_per_shard() <= flat_cells
            assert int(np.max(sbq.shard_widths)) <= bq.max_tiles


def test_shard_map_branch_matches_emulation_subprocess():
    """The REAL shard_map branch (psum_scatter + all_gather, psum
    fallback, check_vma=False, out[0] selection) must be bit-identical
    to the emulation path.  Device forcing must precede jax init, so the
    parity check runs in a subprocess with 2 forced host devices."""
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
from repro.dist import build_fused_image, plan_shards
from repro.kernels import crossbar_reduce_sharded

rows, dim, S = 96, 128, 2
hist = zipf_queries(rows, 32, 5.0, seed=1)
ev = zipf_queries(rows, 9, 5.0, seed=2)   # ragged: pads to q_block
g = build_cooccurrence(hist, rows)
grouping = correlation_aware_grouping(g, 16)
plan = plan_replication(grouping, g.freq, 32)
layout = build_layout(grouping, plan, dim)
table = np.random.default_rng(3).integers(-8, 9, size=(rows, dim)).astype(np.float32)
fused = build_fused_image([layout], [table])
cq = compile_queries(layout, ev, replica_block=4)
sp = plan_shards([layout], [plan], S, group_freqs=[grouping.group_freq(g.freq)])
sbq = shard_block_queries(cq, sp, 4)
images = jnp.asarray(sp.build_shard_images(fused))
emu = np.asarray(crossbar_reduce_sharded(images, sbq.tile_ids, sbq.bitmaps,
                                         combine_chunks=2))
mesh = jax.make_mesh((1, S), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
for combine in ("psum_scatter", "psum"):
    sm = np.asarray(crossbar_reduce_sharded(
        images, sbq.tile_ids, sbq.bitmaps, mesh=mesh,
        combine=combine, combine_chunks=2))
    np.testing.assert_array_equal(sm, emu)
print("SHARD_MAP_PARITY_OK")
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
    assert "SHARD_MAP_PARITY_OK" in proc.stdout


def test_bench_mesh_refuses_one_chip_emulation_on_tpu(monkeypatch):
    """A sharded bench on too few TPU chips must fail, not silently
    report single-device emulation as a sharded run; on the CPU it
    still falls back to emulation."""
    from benchmarks.common import mesh_for

    assert mesh_for(2) is None  # one CPU device: emulation

    class Chip:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [Chip()])
    with pytest.raises(RuntimeError, match="need 4 TPU chips"):
        mesh_for(4)
    assert mesh_for(1) is None


# ------------------------------------------------------- multi-table --


def test_multi_table_fused_reduce_matches_oracles():
    rows_a, rows_b, dim = 160, 96, 128
    hist_a = zipf_queries(rows_a, 48, 6.0, seed=31)
    hist_b = zipf_queries(rows_b, 40, 4.0, seed=32)
    la, pa, fa = _pipeline(rows_a, hist_a, dim=dim)
    lb, pb, fb = _pipeline(rows_b, hist_b, dim=dim, group_size=16)
    ta = _int_table(rows_a, dim, 31)
    tb = _int_table(rows_b, dim, 32)
    fused = build_fused_image([la, lb], [ta, tb])
    assert fused.shape[0] == la.num_tiles + lb.num_tiles

    ev_a = zipf_queries(rows_a, 11, 6.0, seed=33)
    ev_b = zipf_queries(rows_b, 7, 4.0, seed=34)
    q_block = 4
    for S in (1, 2, 4):
        sp = plan_shards([la, lb], [pa, pb], S, group_freqs=[fa, fb])
        cq_a = offset_compiled_queries(
            compile_queries(la, ev_a, replica_block=q_block),
            sp.tables[0].tile_offset,
        )
        cq_b = offset_compiled_queries(
            compile_queries(lb, ev_b, replica_block=q_block),
            sp.tables[1].tile_offset,
        )
        fused_cq, spans = concat_compiled_queries([cq_a, cq_b], q_block)
        sbq = shard_block_queries(fused_cq, sp, q_block)
        images = jnp.asarray(sp.build_shard_images(fused))
        out_a, out_b = crossbar_reduce_tables(images, sbq, spans)
        np.testing.assert_array_equal(
            np.asarray(out_a),
            np.asarray(reduce_dense_oracle(jnp.asarray(ta), ev_a)),
        )
        np.testing.assert_array_equal(
            np.asarray(out_b),
            np.asarray(reduce_dense_oracle(jnp.asarray(tb), ev_b)),
        )


# ----------------------------------------------------- serving driver --


def test_sharded_server_serves_and_reports():
    from repro.serve import ShardedEmbeddingServer

    rows, dim = 128, 128
    rng = np.random.default_rng(40)
    tables = {
        "a": _int_table(rows, dim, 41),
        "b": _int_table(rows, dim, 42),
    }
    histories = {
        "a": zipf_queries(rows, 48, 5.0, seed=43),
        "b": zipf_queries(rows, 48, 5.0, seed=44),
    }
    server = ShardedEmbeddingServer(
        tables, histories, num_shards=2, q_block=4,
        group_size=16, batch_size=8,
    )
    stream = zipf_queries(rows, 20, 5.0, seed=45)
    results = []
    for i, q in enumerate(stream):
        out = server.submit("a" if i % 2 == 0 else "b", q)
        if out:
            results.append(out)
    tail = server.flush()
    if tail:
        results.append(tail)
    assert server.stats.batches == len(results) >= 2
    assert server.stats.queries == 20
    # every served value matches the dense oracle on its logical table
    served = {"a": [], "b": []}
    for i, q in enumerate(stream):
        served["a" if i % 2 == 0 else "b"].append(q)
    got = {"a": [], "b": []}
    for r in results:
        for name, arr in r.items():
            got[name].append(np.asarray(arr))
    for name in ("a", "b"):
        want = np.asarray(
            reduce_dense_oracle(jnp.asarray(tables[name]), served[name])
        )
        np.testing.assert_array_equal(np.concatenate(got[name]), want)

    rep = server.report()
    assert rep["mode"] == "emulated"
    assert rep["serve"]["combine_bytes"] > 0
    assert rep["serve"]["max_grid_cells_per_flush"] > 0
    assert rep["plan"]["stored_tiles"] >= rep["plan"]["num_tiles"]


def test_combine_bytes_accounting():
    assert combine_bytes_per_batch(64, 128, 1) == 0
    b4 = combine_bytes_per_batch(64, 128, 4)
    # two ring passes of (S-1)/S * payload per shard, summed over shards
    assert b4 == int(2 * (3 / 4) * 64 * 128 * 4 * 4)
