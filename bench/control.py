"""The control of ``correct``, at a cell's own size, over several seeds.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

For each seed it makes the cell's tables and request bags as a run
does, takes every bag a window submits (in the order the cell's driver
submits them), and prints the compared number (``max_gap``) that the
reference gives when computed one precision step below the
configuration's in the program's place (:func:`bench.reference.control_gap`).  The smallest of
these readings is the upper end from which the configuration's
``max_gap_limit`` was set.  The benchmark's own runs never run it.
"""

import argparse
import json
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)


def reading(cell, seed: int) -> tuple:
    """The control's ``max_gap`` over every bag a window of ``seed``
    submits (the mix's ``bags``), and how many bags that was."""
    import numpy as np

    from bench import harness, reference

    data = harness.make_data(cell.config, cell.traffic, seed, int(cell.traffic["bags"]))
    tabs, bags = cell.driver().sequence(types.SimpleNamespace(data=data, seed=seed))
    tables = reference.make_tables(harness.table_seed(seed), data.shapes)
    gap = max(reference.control_gap(t, [b for k, b in zip(tabs.tolist(), bags) if k == i])
              for i, t in enumerate(tables) if np.any(tabs == i))
    return gap, len(bags)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(args.workload)
    harness.find_chips(cell.chips)
    harness.enable_compile_cache()
    readings = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        gap, n = reading(cell, seed)
        readings.append(gap)
        print(json.dumps({"workload": cell.name, "seed": seed, "bags": n,
                          "control_max_gap": gap,
                          "seconds": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"workload": cell.name, "control_min": min(readings),
                      "control_max": max(readings),
                      "limit": cell.max_gap_limit}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
