"""A tiny copy of the benchmark for tests on the CPU: the real ``bench``
files under a temporary root, with small configurations and their batch
cells added as new files and entries.  ``tiny4.batch`` asks for four
chips and four shards: it runs where JAX sees four devices (on the CPU,
``--xla_force_host_platform_device_count=4``)."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent

TINY_CONFIG = {
    "name": "tiny",
    "tables": {
        "a": {"rows": 3000, "mean_bag": 10.0, "zipf_a": 1.2, "in_cluster_p": 0.85,
              "num_clusters": 0, "rows_per_template": 64, "template_zipf": 1.1},
        "b": {"rows": 2000, "mean_bag": 20.0, "zipf_a": 1.2, "in_cluster_p": 0.85,
              "num_clusters": 0, "rows_per_template": 64, "template_zipf": 1.1},
    },
    "dim": 128,
    "dtype": "float32",
    "history_bags": 500,
    "max_gap_limit": 4e-5,
    "server": {"num_shards": 1, "q_block": 8, "group_size": 64, "batch_size": 32,
               "flush_policy": "per-shard", "threaded": True, "max_in_flight": 2},
}

TINY_BATCH = {"kind": "batch", "bags": 256}
TINY_DRIFTED = dict(TINY_BATCH, drift=True)


def make_root(tmp: Path) -> Path:
    """``tmp`` holding ``bench/`` and a BENCHMARK.json with the tiny cells
    added beside the real ones."""
    tmp = Path(tmp)
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    (tmp / "bench" / "traffic" / "tiny_batch.json").write_text(json.dumps(TINY_BATCH))
    (tmp / "bench" / "traffic" / "tiny_drifted.json").write_text(json.dumps(TINY_DRIFTED))
    single = dict(TINY_CONFIG, tables={"a": TINY_CONFIG["tables"]["a"]})
    (tmp / "bench" / "configs" / "tiny1.json").write_text(json.dumps(single))
    four = dict(TINY_CONFIG, server=dict(TINY_CONFIG["server"], num_shards=4))
    (tmp / "bench" / "configs" / "tiny4.json").write_text(json.dumps(four))
    bench["configs"] += [
        {"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
         "reduced": [], "why": "test"},
        {"name": "tiny1", "source": "test", "file": "bench/configs/tiny1.json",
         "reduced": [], "why": "test"},
        {"name": "tiny4", "source": "test", "file": "bench/configs/tiny4.json",
         "reduced": [], "why": "test"},
    ]
    bench["workloads"] += [
        {"name": "tiny.batch", "config": "tiny", "traffic": "tiny_batch",
         "chips": 1, "why": "test"},
        {"name": "tiny1.batch", "config": "tiny1", "traffic": "tiny_batch",
         "chips": 1, "why": "test"},
        {"name": "tiny.drifted", "config": "tiny", "traffic": "tiny_drifted",
         "chips": 1, "why": "test"},
        {"name": "tiny4.batch", "config": "tiny4", "traffic": "tiny_batch",
         "chips": 4, "why": "test"},
    ]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and "bags_per_s" in (m["name"], m.get("moves")):
            m["workloads"] += ["tiny.batch", "tiny.drifted", "tiny4.batch"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
