"""Benchmark runner: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only fig8]``
Prints ``name,us_per_call,derived`` CSV (the harness contract), one row
per measured quantity, and a paper-claim check summary at the end.
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (
    beyond_multiread,
    chaos_bench,
    fig456_distributions,
    fig8_speedup,
    fig9_activations,
    fig10_duplication,
    fig11_cpu_gpu,
    kernel_bench,
    load_bench,
    pipeline_bench,
    replan_bench,
    scheduler_bench,
    serving_bench,
    tier_bench,
)
from benchmarks.common import emit
from repro.launch.compile_cache import enable_compile_cache

MODULES = {
    "fig8": fig8_speedup,
    "fig9": fig9_activations,
    "fig10": fig10_duplication,
    "fig11": fig11_cpu_gpu,
    "fig456": fig456_distributions,
    "kernels": kernel_bench,
    "multiread": beyond_multiread,
    "pipeline": pipeline_bench,
    "serving": serving_bench,
    # after serving: all four write BENCH_serving.json (each preserves
    # the others' sections, but keep the full-run order deterministic)
    "replan": replan_bench,
    "scheduler": scheduler_bench,
    "chaos": chaos_bench,
    "tiers": tier_bench,
    "load": load_bench,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(MODULES))
    args = ap.parse_args()

    enable_compile_cache()
    names = [args.only] if args.only else list(MODULES)
    print("name,us_per_call,derived")
    all_rows = []
    for name in names:
        t0 = time.time()
        rows = MODULES[name].run()
        emit(rows)
        all_rows += rows
        print(f"# {name}: {len(rows)} rows in {time.time() - t0:.1f}s", file=sys.stderr)

    _claims_summary(all_rows)


def _claims_summary(rows) -> None:
    """Compares measured ratios against the paper's headline claims."""
    import re

    sp_naive = [float(r["derived"][:-1]) for r in rows
                if r["name"].startswith("fig8_speedup_vs_naive")]
    sp_nmars = [float(r["derived"][:-1]) for r in rows
                if r["name"].startswith("fig8_speedup_vs_nmars")]
    ee_naive = [float(r["derived"][:-1]) for r in rows
                if r["name"].startswith("fig8_energy_eff_vs_naive")]
    act = []
    for r in rows:
        if r["name"].startswith("fig9"):
            m = re.search(r"naive=\d+\(([\d.]+)x\)", r["derived"])
            if m:
                act.append(float(m.group(1)))
    if not sp_naive:
        return
    import numpy as np

    print("# --- paper-claim check (paper value in brackets) ---", file=sys.stderr)
    print(f"# speedup vs naive: {min(sp_naive):.2f}-{max(sp_naive):.2f}x "
          f"[paper 2.58-6.85x]", file=sys.stderr)
    print(f"# speedup vs nmars: {min(sp_nmars):.2f}-{max(sp_nmars):.2f}x "
          f"[paper 2.60-5.48x, avg 3.97x] avg={np.mean(sp_nmars):.2f}x", file=sys.stderr)
    print(f"# energy eff vs naive: {min(ee_naive):.2f}-{max(ee_naive):.2f}x "
          f"[paper 3.60-12.55x]", file=sys.stderr)
    if act:
        print(f"# activation reduction vs naive: up to {max(act):.2f}x "
              f"[paper up to 8.79x]", file=sys.stderr)


if __name__ == "__main__":
    main()
