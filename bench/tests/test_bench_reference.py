"""The plain reference against NumPy in float64, and its control."""

import numpy as np
import pytest

from bench import control, gen, harness, reference
from bench.tests.tiny import make_root

AUTOMOTIVE_LIKE = {"rows": 50000, "mean_bag": 42.26, "zipf_a": 1.2,
                   "in_cluster_p": 0.85, "num_clusters": 0,
                   "rows_per_template": 64, "template_zipf": 1.1}


def _f64(table, bags):
    t = np.asarray(table, dtype=np.float64)
    return np.stack([t[np.unique(np.asarray(b, dtype=np.int64))].sum(axis=0)
                     if len(b) else np.zeros(t.shape[1]) for b in bags])


def test_tables_are_made_from_the_seed():
    (a,) = reference.make_tables(2**40 + 1, [(300, 128)])
    (b,) = reference.make_tables(2**40 + 1, [(300, 128)])
    (c,) = reference.make_tables(2**40 + 2, [(300, 128)])
    assert a.dtype == np.float32 and a.shape == (300, 128)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


@pytest.mark.parametrize("n", [1, 37, 1500])
def test_reference_matches_numpy_float64(n):
    rng = np.random.default_rng(n)
    (table,) = reference.make_tables(n, [(2000, 128)])
    bags = [rng.integers(0, 2000, size=rng.integers(0, 90)) for _ in range(n)]
    bags[0] = np.array([5, 5, 7, 5])             # repeated ids count once
    want = _f64(table, bags)
    got = np.concatenate([np.asarray(r) for r in
                          reference.reference_rows(table, bags)])[:n]
    assert np.max(np.abs(got - want)) < 1e-5
    assert reference.max_gap(table, bags, want.astype(np.float32)) < 1e-5
    wrong = want.astype(np.float32).copy()
    wrong[n // 2, 3] += 1e-3
    assert reference.max_gap(table, bags, wrong) > 5e-4


def test_reference_blocks_have_one_shape():
    bags = [np.arange(k) for k in (1, 33, 64, 65)]
    assert reference.pad_len(bags) == 96
    shapes = {(ids.shape, mask.shape) for _, ids, mask in
              reference.blocks(bags * 700, reference.pad_len(bags))}
    assert shapes == {((reference.BLOCK_BAGS, 96), (reference.BLOCK_BAGS, 96))}


def test_control_fails_the_limit_and_the_reference_passes_it():
    """At a test's size the control, the reference one precision step
    below the configuration's, reads above the automotive limit; the
    float32 reference itself reads well inside it against float64."""
    limit = harness.load_cell("automotive.batch").max_gap_limit
    bags = gen.table_bags(AUTOMOTIVE_LIKE, 0, 3000, 5)
    for seed in (1, 2, 3):
        (table,) = reference.make_tables(seed, [(50000, 128)])
        assert reference.control_gap(table, bags) > limit
        ref = np.concatenate([np.asarray(r) for r in
                              reference.reference_rows(table, bags)])[:len(bags)]
        assert np.max(np.abs(ref - _f64(table, bags))) < limit / 3


def test_split_keeps_two_bfloat16_pieces():
    import jax
    import ml_dtypes

    x = np.random.default_rng(0).standard_normal(10000).astype(np.float32)
    y = np.asarray(jax.jit(reference.split_bf16x2)(x))
    rel = np.abs(y - x) / np.abs(x)
    assert rel.max() <= 2.0**-16 and (y != x).mean() > 0.5
    # the same as NumPy's own float32 -> bfloat16 rounding, piece by piece
    hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    assert np.array_equal(y, hi + lo)


def test_needed_bytes_counts_distinct_rows_per_flush():
    bags = [np.array([1, 2]), np.array([2, 3]), np.array([1]), np.array([9])]
    tabs = np.array([0, 0, 1, 0])
    # flush 1: table 0 rows {1, 2, 3}, flush 2: table 1 row {1}, table 0 {9}
    want = (3 + 2) * 8 + 4 * 4 + (2 + 2) * 8 + 2 * 4
    assert reference.needed_bytes(tabs, bags, 2, 2, 4) == want


def test_control_reading_covers_every_table_of_the_window(tmp_path):
    """``bench/control.py`` reads the bags a window of the cell serves,
    over all its tables, and they put the control above the limit."""
    cell = harness.load_cell("tiny.batch", make_root(tmp_path))
    gap, n = control.reading(cell, 2**35 + 3)
    assert n == cell.traffic["bags"]
    assert gap > cell.max_gap_limit
    assert gap == control.reading(cell, 2**35 + 3)[0]
