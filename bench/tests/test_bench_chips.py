"""A cell on several chips: one shard per chip on a mesh, readers that
count every chip and shard, and a whole four-device run on the CPU."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench.trace import Summary
from bench.tests.tiny import make_root

HBM = 819e9
REPO = Path(__file__).resolve().parents[2]


def _measured(chips, shards, kernel_s=0.25, cells=1000, needed=10**9):
    window = harness.Window(t_first=0.0, t_end=1.0, attempted=0, groups=[],
                            e2e={})
    return harness.Measured(
        plan_build_s=1.0, before={"batches": 0, "grid_cells": 500},
        after={"batches": 10, "grid_cells": 500 + cells}, host_compile_s=0.0,
        window_compiles=0, window=window, peaks={"hbm_bytes_per_s": HBM},
        dim=128, itemsize=4, tile_rows=64, chips=chips, shards=shards,
        trace=Summary(window_s=1.0, busy_s=0.5, kernel_s=kernel_s,
                      kernel_events=20, collective_s=0.0,
                      device_ops=[], idle_gaps=[]),
        needed_bytes=needed)


def _read(name, m):
    return harness.load_module(harness.ROOT, "metrics", name).read(m)


def test_readers_on_one_chip_keep_their_one_chip_formulas():
    m = _measured(1, 1)
    assert _read("kernel_roofline_pct", m) == 100.0 * (10**9 / HBM) / 0.25
    assert _read("tile_fetch_amplification", m) == 1000 * 64 * 128 * 4 / 10**9


def test_readers_on_four_chips_count_every_chip_and_shard():
    one, four = _measured(1, 1), _measured(4, 4)
    assert _read("kernel_roofline_pct", four) == pytest.approx(
        _read("kernel_roofline_pct", one) / 4, rel=1e-15)
    assert _read("tile_fetch_amplification", four) == pytest.approx(
        4 * _read("tile_fetch_amplification", one), rel=1e-15)


@pytest.mark.parametrize("chips, shards", [(1, 2), (4, 1), (4, 2)])
def test_shards_and_chips_must_agree(tmp_path, chips, shards):
    root = make_root(tmp_path)
    config = json.loads((root / "bench/configs/tiny.json").read_text())
    config["server"]["num_shards"] = shards
    (root / "bench/configs/tiny.json").write_text(json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        if w["name"] == "tiny.batch":
            w["chips"] = chips
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("tiny.batch", root)
    with pytest.raises(ValueError, match=f"{chips} chips.*num_shards {shards}"):
        harness.run(cell, 1, 0.1, False, t_process=0.0, need_chip=False)


def test_one_device_gets_no_mesh():
    import jax

    assert harness.make_mesh(jax.devices()[:1]) is None


FOUR_DEVICES = textwrap.dedent('''
    import json, sys, time
    import numpy as np
    from bench import harness
    from repro.serve import ShardedEmbeddingServer
    import repro.serve.sharded as sharded

    modes = []
    report = ShardedEmbeddingServer.report

    def recorded(self):
        out = report(self)
        modes.append(out["mode"])
        return out

    ShardedEmbeddingServer.report = recorded
    cell = harness.load_cell("tiny4.batch", sys.argv[1])
    results = {}
    results["sound"] = harness.run(cell, 2**33 + 29, 0.3, False,
                                   t_process=time.perf_counter(), need_chip=False)
    results["sound_modes"] = sorted(set(modes))
    real = sharded.crossbar_reduce_tables

    def one_answer_altered(*args, **kw):
        outs = [np.array(o) for o in real(*args, **kw)]
        outs[0][0, 0] += 1.0
        return outs

    sharded.crossbar_reduce_tables = one_answer_altered
    results["altered"] = harness.run(cell, 2**33 + 29, 0.3, False,
                                     t_process=time.perf_counter(), need_chip=False)
    print(json.dumps(results))
''')


@pytest.fixture(scope="module")
def four_device_runs(tmp_path_factory):
    """Two runs of ``tiny4.batch`` in one process with four CPU devices:
    one sound, one with one answer altered where the kernel produces it."""
    root = make_root(tmp_path_factory.mktemp("bench4"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]))
    proc = subprocess.run([sys.executable, "-c", FOUR_DEVICES, str(root)],
                          env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_chip_cell_runs_on_a_mesh_and_is_correct(four_device_runs):
    out = four_device_runs["sound"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["attempted"] % 32 == 0
    assert four_device_runs["sound_modes"] == ["shard_map"]
    assert out["device"]["count"] == 4
    peaks = out["device"]["memory_peak_bytes_per_device"]
    assert len(peaks) == 4 and out["device"]["memory_peak_bytes"] == max(peaks)


def test_four_chip_cell_with_an_answer_altered_is_not_correct(four_device_runs):
    out = four_device_runs["altered"]
    assert out["correct"] is False
    assert out["checks"]["max_gap"]["value"] > out["checks"]["max_gap"]["limit"]
