"""Smoke run of the embedding server on TPU chips, checked against a reference.

Drives ``ShardedEmbeddingServer`` the way a user does (``submit`` then
``drain``) at the paper's Table-I sizes (``repro.data.WORKLOADS``): row
counts, mean bag lengths and Zipf skew as published, dim 128, f32 tables,
random values made from ``--seed``.  Every drained row is compared with a
plain ``jax.numpy`` gather-and-sum of the same query
(``repro.core.reduction.reduce_dense_oracle``).

    python chip_smoke.py             # one chip: the 932,019-row automotive
                                     # table, global then per-shard policy
    python chip_smoke.py --chips 4   # four chips: all five Table-I tables
                                     # in one plan over a 4-shard mesh

Each phase also checks that the flush program holds the compiled Pallas
kernel (``tpu_custom_call``, not interpret mode) and that the failure
ledger stayed empty: the server runs with ``RetryPolicy.legacy()``, so any
failed flush raises instead of being healed.  Every exception propagates.

Without a TPU the script exits non-zero and prints no result.  Compiled
programs go to the persistent compilation cache
(``repro.launch.compile_cache``).  Timings printed here are smoke output
from one run, not benchmark numbers.  The last line of standard output is
the JSON result ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

DIM = 128
GROUP_SIZE = 64
Q_BLOCK = 8
BATCH_SIZE = 256
#: lookup history per table for the offline plan; the only cut in scale
HISTORY = 50_000
#: requests per table: two full batches per policy on one chip
REQUESTS = {1: 512, 4: 256}
WORKLOADS_BY_CHIPS = {
    1: ("automotive",),
    4: ("software", "office_products", "electronics", "automotive", "sports"),
}
POLICIES = ("global", "per-shard")
#: |served - reference| <= ATOL + RTOL * |reference|, elementwise.  Rows
#: are sums of ~42-96 N(0, 1) f32 values; an f32 table rounded to bf16 on
#: its way through the MXU misses this by an order of magnitude.
ATOL, RTOL = 1e-3, 1e-5


class CompileLog:
    """Counts compiles and persistent-cache hits through jax.monitoring."""

    def __init__(self):
        from jax import monitoring

        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.compile_s, self.hits, self.misses


def make_workload(names, requests, seed):
    """Seeded f32 tables, plan histories and request streams per table."""
    from repro.data import WORKLOADS, scale_trace

    rng = np.random.default_rng(seed)
    tables, histories, streams = {}, {}, {}
    for i, name in enumerate(names):
        wl = WORKLOADS[name]
        trace = scale_trace(
            wl.num_rows, HISTORY + requests, wl.mean_bag,
            zipf_a=wl.zipf_a, num_clusters=wl.num_clusters or None,
            in_cluster_p=wl.in_cluster_p, seed=seed + 1 + i,
        )
        tables[name] = rng.standard_normal((wl.num_rows, DIM), dtype=np.float32)
        histories[name] = trace[:HISTORY]
        streams[name] = trace[HISTORY:]
    return tables, histories, streams


def check(ok, message):
    if not ok:
        raise RuntimeError(message)


def serve_policy(policy, tables, histories, streams, *, mesh, num_shards, log):
    """Builds a server, serves every stream through submit/drain and
    returns ``{table: (requests, dim) rows}``."""
    import jax

    from repro.serve import ShardedEmbeddingServer
    from repro.serve.faults import RetryPolicy

    t0 = time.perf_counter()
    server = ShardedEmbeddingServer(
        tables, histories, num_shards=num_shards, mesh=mesh,
        q_block=Q_BLOCK, group_size=GROUP_SIZE, batch_size=BATCH_SIZE,
        flush_policy=policy, retry=RetryPolicy.legacy(),
    )
    jax.block_until_ready(server.shard_images)
    print(f"[{policy}] plan build + image placement: "
          f"{time.perf_counter() - t0:.3f} s")

    names = list(streams)
    if server.scheduler is None:
        probe, participants = {n: streams[n][:Q_BLOCK] for n in names}, None
    else:
        # a one-row query has one home shard: the single-participant
        # program an async home flush dispatches
        query = streams[names[0]][0][:1]
        home, _ = server.scheduler.route(names[0], query)
        probe, participants = {names[0]: [query]}, (home,)
    text = server.lower_flush(probe, participants=participants).as_text()
    check("tpu_custom_call" in text,
          f"[{policy}] flush program has no compiled Pallas kernel")
    print(f"[{policy}] flush program holds tpu_custom_call: True")

    compile0, hits0, misses0 = log.snapshot()
    rows = {n: [] for n in names}
    t0 = time.perf_counter()
    for i in range(max(len(s) for s in streams.values())):
        for n in names:
            if i < len(streams[n]):
                for t, out in server.submit(n, streams[n][i]).items():
                    rows[t].append(np.asarray(out))
    for t, out in server.drain().items():
        rows[t].append(np.asarray(out))
    wall = time.perf_counter() - t0
    compile1, hits1, misses1 = log.snapshot()
    server.close()

    report = server.report()
    serve = report["serve"]
    faults = serve["faults"]
    served = {n: np.concatenate(rows[n]) for n in names}
    total = sum(len(streams[n]) for n in names)
    print(f"[{policy}] mode {report['mode']}, {serve['batches']} flushes, "
          f"{sum(len(v) for v in served.values())} of {total} rows served")
    print(f"[{policy}] smoke timing, not a benchmark: submit->drain "
          f"{wall:.3f} s, of which backend compile {compile1 - compile0:.3f} s "
          f"(persistent cache hits {hits1 - hits0}, misses {misses1 - misses0})")
    for n in names:
        check(served[n].shape == (len(streams[n]), DIM),
              f"[{policy}] {n}: served {served[n].shape}, "
              f"expected {(len(streams[n]), DIM)}")
    check(faults["retries"] == 0 and faults["bisections"] == 0
          and not faults["quarantined"] and faults["degraded_flushes"] == 0
          and faults["timed_out_flushes"] == 0,
          f"[{policy}] failure ledger not empty: {faults}")
    check(serve["tiers"]["host_flushes"] == 0,
          f"[{policy}] {serve['tiers']['host_flushes']} host flushes")
    print(f"[{policy}] ledger: 0 retries, 0 quarantined, 0 degraded, "
          f"0 host flushes")
    return served


def compare(policy, served, reference):
    """Checks every served row against the reference within tolerance."""
    worst_abs = worst_share = 0.0
    for n, ref in reference.items():
        got = served[n]
        check(np.all(np.isfinite(got)), f"[{policy}] {n}: non-finite rows")
        err = np.abs(got.astype(np.float64) - ref)
        share = err / (ATOL + RTOL * np.abs(ref))
        worst_abs = max(worst_abs, float(err.max()))
        worst_share = max(worst_share, float(share.max()))
        bad = int((share > 1.0).any(axis=1).sum())
        check(bad == 0, f"[{policy}] {n}: {bad} rows outside tolerance, "
                        f"max abs error {float(err.max())}")
    print(f"[{policy}] all rows match the reference: max abs error "
          f"{worst_abs!r}, largest error / tolerance {worst_share!r}")


def run(chips, seed, log):
    import jax
    import jax.numpy as jnp

    from repro.core.reduction import reduce_dense_oracle

    names = WORKLOADS_BY_CHIPS[chips]
    t0 = time.perf_counter()
    tables, histories, streams = make_workload(names, REQUESTS[chips], seed)
    print(f"workload {', '.join(names)}: "
          f"{sum(t.shape[0] for t in tables.values())} rows, dim {DIM}, f32, "
          f"{sum(t.nbytes for t in tables.values()) / 2**30:.3f} GiB of tables, "
          f"{REQUESTS[chips]} requests per table "
          f"(made in {time.perf_counter() - t0:.3f} s)")
    print(f"tolerance: |served - reference| <= {ATOL} + {RTOL} * |reference|")

    mesh = None
    if chips > 1:
        mesh = jax.make_mesh(
            (1, chips), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2,
        )
    results = {
        policy: serve_policy(policy, tables, histories, streams, mesh=mesh,
                             num_shards=chips, log=log)
        for policy in POLICIES
    }
    reference = {
        n: np.asarray(reduce_dense_oracle(jnp.asarray(tables[n]), streams[n]),
                      dtype=np.float64)
        for n in names
    }
    for policy, served in results.items():
        compare(policy, served, reference)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(WORKLOADS_BY_CHIPS),
                    default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, found {len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {devices[0].device_kind} x {len(devices)}")
    print(f"compilation cache: {enable_compile_cache()}")
    log = CompileLog()
    run(args.chips, args.seed, log)
    compile_s, hits, misses = log.snapshot()
    print(f"total backend compile {compile_s:.3f} s, persistent cache hits "
          f"{hits}, misses {misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
