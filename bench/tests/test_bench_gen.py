"""The traffic generator is a pure function of its seed, and the loader
finds cells, configurations, mixes and metrics added only as files."""

import json

import numpy as np
import pytest

from bench import gen, harness
from bench.tests.tiny import make_root

TABLE = {"rows": 5000, "mean_bag": 12.0, "zipf_a": 1.2, "in_cluster_p": 0.85,
         "num_clusters": 0, "rows_per_template": 64, "template_zipf": 1.1}


def _same(a, b):
    return len(a) == len(b) and all(np.array_equal(x, y) for x, y in zip(a, b))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3])
def test_bags_are_deterministic_per_seed(seed):
    a = gen.table_bags(TABLE, 100, 300, seed)
    b = gen.table_bags(TABLE, 100, 300, seed)
    assert _same(a, b)
    other = gen.table_bags(TABLE, 100, 300, seed + 1)
    assert not _same(a, other)
    # a longer stream of the same seed extends the shorter one
    assert _same(gen.table_bags(TABLE, 100, 600, seed)[:400], a)
    for bag in a:
        assert bag.size and np.all(np.diff(bag) > 0)
        assert bag.min() >= 0 and bag.max() < TABLE["rows"]


def test_scale_trace_matches_the_program_generator_it_copies():
    from repro.data import scale_trace

    kw = dict(zipf_a=1.2, num_clusters=None, in_cluster_p=0.85, seed=3)
    assert _same(gen.scale_trace(5000, 400, 12.0, **kw),
                 scale_trace(5000, 400, 12.0, **kw))


def test_table_order_gives_each_request_one_bag_per_table():
    order = gen.sample_table_order(["a", "b", "c", "d", "e"], 1000,
                                   gen.rng_for(5, 3))
    assert np.array_equal(order, gen.sample_table_order(
        ["a", "b", "c", "d", "e"], 1000, gen.rng_for(5, 3)))
    for s in range(0, 1000, 5):
        assert sorted(order[s:s + 5].tolist()) == [0, 1, 2, 3, 4]


def test_loader_finds_what_is_added_as_new_files(tmp_path):
    root = make_root(tmp_path)
    (root / "bench" / "metrics" / "bags_seen.py").write_text(
        'UNIT = "count"\nLAYER = "device"\nMOVES = "bags_per_s"\n\n\n'
        "def read(m):\n    return m.window.attempted\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append(
        {"name": "bags_seen", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "device", "moves": "bags_per_s",
         "workloads": ["tiny.batch"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("tiny.batch", root)
    assert cell.config["name"] == "tiny"
    assert cell.traffic["kind"] == "batch"
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "bags_per_s"]
    readers = cell.readers()
    assert "bags_seen" in readers and readers["bags_seen"].UNIT == "count"
    assert cell.driver().__name__ == "bench_drivers_batch"
    single = harness.load_cell("tiny1.batch", root)
    assert "bags_seen" not in single.readers()
    with pytest.raises(KeyError):
        harness.load_cell("no.such.cell", root)


def test_benchmark_entries_match_their_files():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert w["config"] in configs
        assert cell.config["name"] == w["config"]
        assert cell.driver() is not None
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e
    for m in bench["per_layer"]:
        mod = harness.load_module(harness.ROOT, "metrics", m["name"])
        assert (mod.UNIT, mod.LAYER, mod.MOVES) == (m["unit"], m["layer"], m["moves"])
    for c in bench["configs"]:
        data = json.loads((harness.ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(data["reduced"])


def test_unknown_chip_has_no_peaks():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


def test_no_tpu_is_refused():
    with pytest.raises(harness.NoChip):
        harness.find_chips(1)


def test_benchmark_json_keeps_to_its_limits():
    import re

    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    for entry in bench["configs"] + bench["workloads"]:
        assert name.match(entry["name"]) and 1 <= len(entry["why"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
