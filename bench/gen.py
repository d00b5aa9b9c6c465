"""Seeded traffic: lookup bags and table orders.

:func:`scale_trace` and :func:`zipf_popularity` are copies of
``repro.data.synthetic.scale_trace`` / ``zipf_popularity``, kept here
so that no change to the program can change the traffic the benchmark
measures it with; the copy adds fixed bag lengths (``fixed_len``).
Everything is a pure function of its seed.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator for one purpose (``stream``) of one run;
    any non-negative seed, however large."""
    return np.random.default_rng([int(seed), *[int(s) for s in stream]])


def zipf_popularity(num_rows: int, a: float, rng: np.random.Generator) -> np.ndarray:
    """Normalized Zipf pmf over rows, with a random rank permutation so
    hot ids are scattered across the id space."""
    ranks = np.arange(1, num_rows + 1, dtype=np.float64)
    p = ranks ** (-a)
    p /= p.sum()
    perm = rng.permutation(num_rows)
    out = np.empty(num_rows)
    out[perm] = p
    return out


def scale_trace(
    num_rows: int,
    num_queries: int,
    mean_bag: float,
    *,
    num_templates: int | None = None,
    zipf_a: float = 1.05,
    num_clusters: int | None = None,
    in_cluster_p: float = 0.85,
    template_zipf: float = 1.1,
    seed: int = 0,
    fixed_len: bool = False,
) -> List[np.ndarray]:
    """Lookup trace of ``num_queries`` bags of distinct row ids.

    Zipf-popular template baskets over Zipf-popular interest clusters:
    rows are ranked by global Zipf popularity and bucketed into clusters;
    every template picks a cluster by cluster popularity, draws ``1 +
    Poisson(mean_bag - 1)`` lookups (exactly ``mean_bag`` with
    ``fixed_len``), each in-cluster with probability
    ``in_cluster_p`` else global, then dedups; the query stream samples
    template ids from a Zipf over templates.  Queries share the template
    arrays by reference.  The template picks are drawn last, so a longer
    trace of the same seed extends a shorter one.
    """
    if num_rows < 1 or num_queries < 0:
        raise ValueError("num_rows must be >= 1 and num_queries >= 0")
    if fixed_len and (mean_bag < 1 or mean_bag != int(mean_bag)):
        raise ValueError(f"a fixed bag length must be a whole number >= 1, "
                         f"not {mean_bag}")
    rng = np.random.default_rng(seed)
    nt = num_templates or max(64, num_rows // 64)
    if not num_clusters:
        num_clusters = max(8, num_rows // 256)
    C = int(num_clusters)

    pop = zipf_popularity(num_rows, zipf_a, rng)
    porder = np.argsort(-pop, kind="stable").astype(np.int64)

    cluster_of = rng.integers(0, C, size=num_rows)
    prank = np.empty(num_rows, dtype=np.int64)
    prank[porder] = np.arange(num_rows, dtype=np.int64)
    by_cluster = np.lexsort((prank, cluster_of))
    cl_sorted = cluster_of[by_cluster]
    cl_start = np.searchsorted(cl_sorted, np.arange(C + 1))
    cl_size = np.diff(cl_start)

    def zipf_ranks(m: np.ndarray, u: np.ndarray, a: float) -> np.ndarray:
        """Inverse-CDF Zipf(a) rank in [0, m) per draw."""
        m = np.maximum(m.astype(np.float64), 1.0)
        if abs(a - 1.0) < 1e-9:
            r = np.power(m, u) - 1.0
        else:
            r = np.power((np.power(m, 1.0 - a) - 1.0) * u + 1.0, 1.0 / (1.0 - a)) - 1.0
        return np.minimum(r.astype(np.int64), (m - 1).astype(np.int64))

    cl_mass = np.zeros(C)
    np.add.at(cl_mass, cl_sorted, pop[by_cluster])
    cl_rank = np.argsort(-cl_mass, kind="stable")
    tpl_c = cl_rank[zipf_ranks(np.full(nt, C), rng.random(nt), template_zipf)]
    tpl_c = tpl_c[cl_size[tpl_c] > 0]
    nt = tpl_c.size

    if fixed_len:
        lens = np.full(nt, int(mean_bag), dtype=np.int64)
    else:
        lens = 1 + rng.poisson(max(mean_bag - 1.0, 0.0), size=nt)
    tid = np.repeat(np.arange(nt, dtype=np.int64), lens)
    total = int(lens.sum())
    c_of_draw = tpl_c[tid]
    u = rng.random(total)
    in_c = rng.random(total) < in_cluster_p
    rows_flat = np.empty(total, dtype=np.int64)
    r_in = zipf_ranks(cl_size[c_of_draw[in_c]], u[in_c], zipf_a)
    rows_flat[in_c] = by_cluster[cl_start[c_of_draw[in_c]] + r_in]
    out_c = ~in_c
    rows_flat[out_c] = porder[
        zipf_ranks(np.full(int(out_c.sum()), num_rows), u[out_c], zipf_a)
    ]

    if total and num_rows > ((1 << 63) - 1) // max(total, 1):
        raise ValueError(
            f"scale_trace pack overflow: {nt} templates x {num_rows} rows"
        )
    key = tid * np.int64(num_rows) + rows_flat
    key = np.sort(key)
    keep = np.empty(total, dtype=bool)
    keep[0] = True
    np.not_equal(key[1:], key[:-1], out=keep[1:])
    key = key[keep]
    tid_d = key // num_rows
    rows_d = key - tid_d * num_rows
    tlens = np.bincount(tid_d, minlength=nt)
    ends = np.cumsum(tlens)
    starts = ends - tlens
    templates = [rows_d[s:e] for s, e in zip(starts.tolist(), ends.tolist())]
    templates = [t for t in templates if t.size]

    pick = zipf_ranks(
        np.full(num_queries, len(templates)), rng.random(num_queries), template_zipf
    )
    return [templates[i] for i in pick.tolist()]


def table_bags(table: Dict, history: int, n: int, seed: int) -> List[np.ndarray]:
    """``history + n`` bags of one table's generator (``table`` holds the
    config's ``rows``, ``mean_bag``, ``zipf_a``, ``in_cluster_p``,
    ``num_clusters``, ``rows_per_template`` and ``template_zipf``, and
    may set ``"bag_len": "fixed"``: every template then draws exactly
    ``mean_bag`` ids before its repeats are dropped, as a table of fixed
    multi-hot size does)."""
    rows = int(table["rows"])
    fixed = "bag_len" in table
    if fixed and table["bag_len"] != "fixed":
        raise ValueError(f"bag_len can only be 'fixed', not {table['bag_len']!r}")
    return scale_trace(
        rows, history + n, float(table["mean_bag"]),
        num_templates=max(64, rows // int(table["rows_per_template"])),
        zipf_a=float(table["zipf_a"]),
        num_clusters=int(table["num_clusters"]) or None,
        in_cluster_p=float(table["in_cluster_p"]),
        template_zipf=float(table["template_zipf"]),
        seed=seed,
        fixed_len=fixed,
    )


def sample_table_order(names: List[str], n: int, rng) -> np.ndarray:
    """Table index of each of ``n`` bags: samples of one bag per table,
    each sample's tables in a seeded order (the multi-table lookup of
    one recommendation request)."""
    k = len(names)
    samples = -(-n // k)
    order = np.argsort(rng.random((samples, k)), axis=1).reshape(-1)
    return order[:n]
