"""Whole runs of tiny cells on the CPU, skipping only the harness's look
for a chip: a sound run is correct, and a run whose timed path is broken
underneath, one answer altered or half of the answers left out where the
kernel produces them, is not; nor is a run in which the control, the
rows one precision step below the configuration's, stands in the
program's place."""

import time

import numpy as np
import pytest

from bench import harness, reference
from bench.tests.tiny import TINY_CONFIG, make_root

LIMIT = TINY_CONFIG["max_gap_limit"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def _run(root, name, seconds=0.3):
    cell = harness.load_cell(name, root)
    return harness.run(cell, 2**33 + 17, seconds, False,
                       t_process=time.perf_counter(), need_chip=False)


def _break(monkeypatch, alter):
    """Wraps the kernel dispatch the server's flushes go through."""
    import repro.serve.sharded as sharded

    real = sharded.crossbar_reduce_tables

    def broken(*args, **kw):
        return [alter(np.asarray(o)) for o in real(*args, **kw)]

    monkeypatch.setattr(sharded, "crossbar_reduce_tables", broken)


def _one_answer_altered(rows):
    rows = rows.copy()
    rows[0, 0] += 4 * LIMIT
    return rows


def _half_left_out(rows):
    rows = rows.copy()
    rows[1::2] = 0.0
    return rows


def test_sound_batch_run_is_correct(root):
    out = _run(root, "tiny.batch")
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["attempted"] % 32 == 0
    assert set(out["metrics"]) == {"setup_s", "bags_per_s"}
    assert list(out)[-1] == "checks"
    assert out["checks"]["max_gap"]["value"] <= LIMIT


def test_sound_drifted_run_is_correct(root):
    """Bags of another seed than the plan's history, served and checked."""
    out = _run(root, "tiny.drifted")
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["attempted"] % 32 == 0


@pytest.mark.parametrize("alter", [_one_answer_altered, _half_left_out])
def test_broken_batch_run_is_not_correct(root, monkeypatch, alter):
    _break(monkeypatch, alter)
    out = _run(root, "tiny.batch")
    assert out["correct"] is False
    assert out["checks"]["max_gap"]["value"] > LIMIT


def test_control_in_the_programs_place_is_not_correct(root, monkeypatch):
    """The kernel reduces rows cut to their two-piece bfloat16 split, the
    ``Precision.HIGH`` step below the configuration's float32 sum."""
    import repro.serve.sharded as sharded

    real = sharded.crossbar_reduce_tables

    def control(images, *args, **kw):
        return real(reference.split_bf16x2(images), *args, **kw)

    monkeypatch.setattr(sharded, "crossbar_reduce_tables", control)
    out = _run(root, "tiny.batch")
    assert out["correct"] is False and out["checks"]["missing_bags"]["value"] == 0
    assert out["checks"]["max_gap"]["value"] > LIMIT
