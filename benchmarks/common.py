"""Shared benchmark scaffolding: workload prep + CSV emission."""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from typing import Callable, Dict, List

import numpy as np

from repro.core import build_cooccurrence
from repro.data import make_workload

# scaled-down table sizes keep the suite < ~5 min on one CPU core while
# preserving the power-law/co-occurrence statistics (scale=1.0 reproduces
# the Table I sizes exactly)
DEFAULT_SCALE = 0.02
DEFAULT_QUERIES = 768
HISTORY_FRACTION = 1 / 3  # offline co-occurrence history vs online eval split


def prepared_workload(name: str, *, scale: float = DEFAULT_SCALE,
                      num_queries: int = DEFAULT_QUERIES, seed: int = 0):
    """Returns (num_rows, history_queries, eval_queries, graph)."""
    _, rows, qs = make_workload(name, num_queries=num_queries, scale=scale, seed=seed)
    split = int(len(qs) * HISTORY_FRACTION)
    hist, ev = qs[:split], qs[split:]
    graph = build_cooccurrence(hist, rows)
    return rows, hist, ev, graph


def mesh_for(num_shards: int):
    """A ``(1, num_shards)`` (data, model) mesh when the host presents
    enough devices (CI forces them via XLA_FLAGS), else ``None`` →
    single-device emulation.  Shared by every sharded-serving bench so
    shard_map-vs-emulated selection can never diverge between them.

    On a TPU too few chips is an error: a bench that asked for shards
    must not report emulation on one chip as a sharded run.
    """
    import jax

    if num_shards <= 1:
        return None
    if len(jax.devices()) >= num_shards:
        return jax.make_mesh(
            (1, num_shards), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2,
        )
    if jax.devices()[0].platform == "tpu":
        raise RuntimeError(
            f"{num_shards} shards need {num_shards} TPU chips, "
            f"found {len(jax.devices())}"
        )
    return None


def emit(rows: List[Dict]) -> None:
    """Prints ``name,us_per_call,derived`` CSV rows (benchmark contract)."""
    for r in rows:
        print(f"{r['name']},{r.get('us_per_call', '')},{r.get('derived', '')}")


#: RECROSS_* env vars that do NOT change the measured workload
_NON_WORKLOAD_KNOBS = {"RECROSS_SMOKE_BENCH_DIR"}


def bench_is_full_scale() -> bool:
    """Whether this run measures the committed-record configuration.

    The committed ``BENCH_*.json`` are full-DEFAULT-config records, so
    ANY workload-shaping ``RECROSS_*`` override (sizes, batch, shard
    counts, skew, mean bag, …) makes the run non-canonical — not just
    the row/history counts.  Only knobs that don't change the workload
    (the smoke output dir itself) are exempt.
    """
    return not any(
        k.startswith("RECROSS_") and k not in _NON_WORKLOAD_KNOBS
        for k in os.environ
    )


def bench_json_path(path: str, *, full_scale: bool) -> str:
    """Routes smoke-size runs away from the committed bench records.

    Committed ``BENCH_*.json`` files are FULL-SCALE measurements — the
    perf trajectory future PRs are held against.  CI (and local smoke
    runs) shrink the workload via the ``RECROSS_*`` env knobs; letting
    those runs write the committed path would silently replace real
    records with toy numbers.  Non-full-scale runs therefore write to
    ``RECROSS_SMOKE_BENCH_DIR`` (default: a ``recross-bench-smoke``
    directory under the system temp dir), which CI uploads as its own
    artifact; a CI diff-guard additionally fails the build if any
    committed ``BENCH_*.json`` changed during the smoke runs.
    """
    if full_scale:
        return path
    out_dir = os.environ.get("RECROSS_SMOKE_BENCH_DIR") or os.path.join(
        tempfile.gettempdir(), "recross-bench-smoke"
    )
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, os.path.basename(path))
    print(
        f"# smoke-size bench: writing {os.path.basename(path)} to {out} "
        "(committed record untouched)",
        file=sys.stderr,
    )
    return out


def update_bench_json(
    path: str, updates: Dict, preserve: List[str] | None = None
) -> None:
    """Read-modify-write of a bench JSON shared by several benches.

    BENCH_serving.json is written by both the serving bench (its whole
    record) and the replan bench (the ``"replan"`` section); a rerun of
    one must never drop the other's recorded section.

    With ``preserve=None`` every prior top-level key survives unless
    ``updates`` replaces it (section writers).  A whole-record writer
    passes the explicit list of *foreign* keys to keep — everything
    else it owns, so keys it stopped emitting are dropped instead of
    lingering as stale data from an older code version.  An unreadable
    or missing prior file degrades to a plain write.
    """
    prior: Dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                prior = json.load(f)
        except (OSError, ValueError):
            prior = {}
    if preserve is not None:
        prior = {k: v for k, v in prior.items() if k in preserve}
    prior.update(updates)
    with open(path, "w") as f:
        json.dump(prior, f, indent=1, default=str)


def time_call(fn: Callable, *args, repeats: int = 3, **kw) -> float:
    """Median wall time of fn(*args) in microseconds."""
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(*args, **kw)
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))
