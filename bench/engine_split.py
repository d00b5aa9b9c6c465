"""The serving engine's time in a cell, split by its own spans and counters.

The server opens ``jax.profiler.TraceAnnotation`` spans named
``recross.*`` once per flush on its engine thread (``recross.compile``,
``.dispatch``, ``.retire``, ``.barrier``, each with ``flush=n``), keeps
``perf_counter`` sums on ``ShardedServeStats`` (:data:`COUNTERS`), and
keeps the plan build's stage seconds in ``setup_timings``.  The
benchmark's run (:func:`bench.harness.run`) reads none of them; this
script does, for one cell:

    python3 bench/engine_split.py --workload <cell> --seed <n> --seconds <s>

It runs the cell once, traced, through :func:`bench.harness.run`, with
the cell's driver wrapped so that the server's counters are read around
the measured window alone (the engine idles while the profiler starts
and writes its trace).  On standard error it prints each program span's
count, total and self seconds inside the window (:func:`program_spans`),
the device's idle time split by the innermost program span open, and
the split of :func:`split`; the last line of standard output is one JSON
object holding the harness's result, the traced window's ``bags_per_s``,
the split, the spans and the idle split.

Every time here is a wall clock on a thread that shares the GIL with the
other thread: a stage's time includes its waits for the GIL.
``handoff_full_pct`` high with ``engine_wait_pct`` near 0 means the
engine sets the pace; the reverse means the producer does; both low
means the two threads contend for the GIL.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    # as in bench/run.py: the checkout heads sys.path, so ``bench`` is the
    # package and no module here shadows the standard library's ``trace``
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from bench import trace as tracing  # noqa: E402

#: prefix of the serving engine's own host spans
PROGRAM_SPAN = "recross."
#: the idle split's label of idle time in no program span
OUTSIDE_SPANS = "outside program spans"
#: the engine spans whose self seconds enter the engine accounting (self,
#: since the barrier's compiles, dispatches and retires nest inside it)
ENGINE_SPANS = ("recross.dispatch", "recross.retire", "recross.barrier")
#: the ``ShardedServeStats`` fields read around the window
COUNTERS = ("batches", "host_compile_s", "submit_s", "submits",
            "handoff_full_s", "engine_wait_s", "route_s", "routed")


# ---------------------------------------------------------------- spans --

def span_name(name: str) -> str:
    """A host event's name without the ``#key=value#`` arguments a
    ``TraceAnnotation`` may encode in it."""
    return name.split("#", 1)[0]


def _nest(events: list, w0: float, w1: float, stats: Dict[str, dict],
          segments: list) -> None:
    """Adds one line's ``(start, end, name)`` program spans, clipped to
    the window, to ``stats`` (count, total and self seconds) and the
    ``(start, end, label)`` segments of the window in which each is the
    innermost open span to ``segments``.  The engine opens its spans on
    one thread, so they nest on their line; a child's time is taken off
    its parent's self time."""
    events.sort(key=lambda ev: (ev[0], -ev[1]))
    stack: List[tuple] = []                 # (end, name) of open spans
    cursor = w0
    for s, e, name in events + [(w1, w1, None)]:
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            segments.append((cursor, end, top))
            cursor = end
        if name is None:
            break
        segments.append((cursor, s, stack[-1][1] if stack else OUTSIDE_SPANS))
        cursor = s
        st = stats.setdefault(name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
        st["count"] += 1
        st["total_s"] += (e - s) / 1e9
        st["self_s"] += (e - s) / 1e9
        if stack:
            stats[stack[-1][1]]["self_s"] -= (e - s) / 1e9
        stack.append((e, name))
    segments.append((cursor, w1, OUTSIDE_SPANS))


def _idle_by_label(idle: np.ndarray, segments: list) -> Dict[str, float]:
    """Nanoseconds of the sorted, disjoint ``idle`` intervals that fall
    in each labelled segment; what no program span covers is
    :data:`OUTSIDE_SPANS`."""
    total = float((idle[:, 1] - idle[:, 0]).sum()) if idle.size else 0.0
    out: Dict[str, float] = {}
    if idle.size and segments:
        before = np.concatenate([[0.0], np.cumsum(idle[:, 1] - idle[:, 0])])

        def idle_until(t):             # idle time up to t
            k = np.searchsorted(idle[:, 0], t, side="right") - 1
            kk = np.maximum(k, 0)
            inside = np.clip(t - idle[kk, 0], 0.0, idle[kk, 1] - idle[kk, 0])
            return np.where(k < 0, 0.0, before[kk] + inside)

        seg = np.asarray([(a, b) for a, b, _ in segments], dtype=np.float64)
        covered = idle_until(seg[:, 1]) - idle_until(seg[:, 0])
        for (_, _, name), ns in zip(segments, covered.tolist()):
            if name != OUTSIDE_SPANS and ns > 0:
                out[name] = out.get(name, 0.0) + ns
    out[OUTSIDE_SPANS] = total - sum(out.values())
    return out


def program_spans(path: Path, window: str = "bench.window"
                  ) -> Tuple[Dict[str, dict], Dict[str, float]]:
    """The program's spans in the trace at ``path`` (a file or a log
    directory), inside the host span ``window``: for each name (the part
    before any ``#``) its count, total seconds and self seconds, self
    being the duration less the part nested program spans on the same
    line cover; and the device's idle seconds in the window split by the
    innermost program span open, averaged over the devices traced, the
    rest under :data:`OUTSIDE_SPANS`.  The device side is the one
    :func:`bench.trace.reduce` reads: busy is the union of the
    :data:`bench.trace.BUSY_LINES` events."""
    data = tracing.load(path)
    marks: List[Tuple[float, float]] = []
    by_line: Dict[tuple, list] = {}
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            busy = [(float(e.start_ns), float(e.start_ns + e.duration_ns))
                    for line in plane.lines if line.name in tracing.BUSY_LINES
                    for e in line.events]
            if busy:
                devices.append(np.asarray(busy, dtype=np.float64))
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):      # a line per thread
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    s, t = float(e.start_ns), float(e.start_ns + e.duration_ns)
                    if e.name == window:
                        marks.append((s, t))
                    elif e.name.startswith(PROGRAM_SPAN):
                        by_line.setdefault((plane.name, k), []).append(
                            (s, t, span_name(e.name)))
    if not marks:
        raise ValueError(f"no host span {window!r} in the trace")
    w0, w1 = min(s for s, _ in marks), max(t for _, t in marks)
    stats: Dict[str, dict] = {}
    segments: list = []
    for events in by_line.values():
        inside = [(max(s, w0), min(t, w1), n) for s, t, n in events
                  if t > w0 and s < w1]
        _nest(inside, w0, w1, stats, segments)
    segments = [seg for seg in segments if seg[1] > seg[0]]
    idle: Dict[str, float] = {}
    for busy in devices:
        merged = tracing._clip(tracing._union(busy), w0, w1)
        edges = np.concatenate([[w0], merged.reshape(-1), [w1]]).reshape(-1, 2)
        for name, ns in _idle_by_label(edges[edges[:, 1] > edges[:, 0]],
                                       segments).items():
            idle[name] = idle.get(name, 0.0) + ns
    n = max(len(devices), 1)
    return stats, {k: v / n / 1e9 for k, v in idle.items()}


def describe_spans(spans: Dict[str, dict], idle: Dict[str, float]) -> str:
    """Program spans and the device-idle split, as lines for standard
    error."""
    lines = [f"program span {name}: {v['count']} events, total "
             f"{v['total_s']!r} s, self {v['self_s']!r} s"
             for name, v in sorted(spans.items())]
    total = sum(idle.values())
    for name, sec in sorted(idle.items(), key=lambda kv: -kv[1]):
        share = 100 * sec / total if total else 0.0
        lines.append(f"device idle in {name}: {sec!r} s ({share:.2f}% of idle)")
    return "\n".join(lines)


# ------------------------------------------------------------ the split --

def counters(stats) -> dict:
    """The server's :data:`COUNTERS` now."""
    return {k: getattr(stats, k) for k in COUNTERS}


def _ratio(a: float, b: float, scale: float = 1.0) -> Optional[float]:
    return a / b * scale if b > 0 else None


def split(before: dict, after: dict, window_s: float,
          spans: Dict[str, dict], setup_timings: Optional[dict]) -> dict:
    """The engine's and the producer's time in a window of ``window_s``
    seconds (the driver's ``t_end - t_first``, the time base of
    ``bags_per_s``), from the counters read at its start and end and the
    spans inside it:

    * ``submit_us_per_bag``: (Δ``submit_s`` − Δ``handoff_full_s``) /
      Δ``submits``, a submit's own work without its blocked hand-off;
    * ``handoff_full_pct``: Δ``handoff_full_s`` / window;
    * ``engine_wait_pct``: Δ``engine_wait_s`` / window;
    * ``route_us_per_bag``: Δ``route_s`` / Δ``routed``;
    * ``dispatch_ms_per_flush``, ``retire_ms_per_flush``: self seconds of
      ``recross.dispatch`` and ``recross.retire`` per flush;
    * ``plan_cooccurrence_s``, ``plan_grouping_s``, ``plan_placement_s``:
      the server's ``setup_timings``;
    * ``per_flush_ms``: each piece per flush, in ms;
    * ``engine_accounting_pct``: engine wait + route + host compile +
      the self seconds of :data:`ENGINE_SPANS`, over the window: the
      share of the engine thread's window the counters and spans see.
    """
    d = {k: after[k] - before[k] for k in COUNTERS}
    flushes = d["batches"]

    def self_s(name):
        return spans.get(name, {}).get("self_s", 0.0)

    timings = setup_timings or {}
    engine = (d["engine_wait_s"] + d["route_s"] + d["host_compile_s"]
              + sum(self_s(n) for n in ENGINE_SPANS))
    return {
        "flushes": flushes,
        "window_s": window_s,
        "submit_us_per_bag": _ratio(d["submit_s"] - d["handoff_full_s"],
                                    d["submits"], 1e6),
        "handoff_full_pct": _ratio(d["handoff_full_s"], window_s, 100.0),
        "engine_wait_pct": _ratio(d["engine_wait_s"], window_s, 100.0),
        "route_us_per_bag": _ratio(d["route_s"], d["routed"], 1e6),
        "dispatch_ms_per_flush": _ratio(self_s("recross.dispatch"), flushes, 1e3),
        "retire_ms_per_flush": _ratio(self_s("recross.retire"), flushes, 1e3),
        "plan_cooccurrence_s": timings.get("cooccurrence"),
        "plan_grouping_s": timings.get("grouping"),
        "plan_placement_s": timings.get("placement"),
        "per_flush_ms": {
            "flush": _ratio(window_s, flushes, 1e3),
            "submit": _ratio(d["submit_s"] - d["handoff_full_s"], flushes, 1e3),
            "handoff_full": _ratio(d["handoff_full_s"], flushes, 1e3),
            "route": _ratio(d["route_s"], flushes, 1e3),
            "compile": _ratio(d["host_compile_s"], flushes, 1e3),
            "dispatch": _ratio(self_s("recross.dispatch"), flushes, 1e3),
            "retire": _ratio(self_s("recross.retire"), flushes, 1e3),
            "barrier": _ratio(self_s("recross.barrier"), flushes, 1e3),
            "engine_wait": _ratio(d["engine_wait_s"], flushes, 1e3),
        },
        "engine_accounting_pct": _ratio(engine, window_s, 100.0),
    }


# ------------------------------------------------------------- the run --

def measure(cell, seed: int, seconds: float, trace: bool, *,
            t_process: float, need_chip: bool = True,
            log_dir: Optional[Path] = None) -> dict:
    """One :func:`bench.harness.run` of ``cell`` with its driver wrapped
    to read the server's counters and stage times around the window.
    Returns the harness's ``result``, the driver's ``window`` and the
    ``split`` (span parts empty unless traced into ``log_dir``)."""
    from bench import harness

    real = cell.driver()
    seen: dict = {}

    def window(session, secs):
        stats = session.server.stats
        seen["before"] = counters(stats)
        w = real.window(session, secs)
        seen["after"] = counters(stats)
        seen["setup_timings"] = dict(session.server.setup_timings)
        seen["window"] = w
        return w

    wrapped = types.SimpleNamespace(warm=real.warm, window=window)
    cell.driver = lambda: wrapped        # shadows Cell.driver for this run
    result = harness.run(cell, seed, seconds, trace, t_process=t_process,
                         need_chip=need_chip, log_dir=log_dir)
    spans, idle = {}, {}
    if trace:
        spans, idle = program_spans(Path(log_dir) / f"{cell.name}-{seed}")
    w = seen["window"]
    return {
        "result": result, "window": w, "spans": spans, "span_idle": idle,
        "split": split(seen["before"], seen["after"], w.t_end - w.t_first,
                       spans, seen["setup_timings"]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness

    try:
        cell = harness.load_cell(args.workload)
        harness.find_chips(cell.chips)
    except (harness.NoChip, KeyError, FileNotFoundError) as e:
        print(f"engine_split: {e}", file=sys.stderr)
        return 2
    log_dir = ROOT / ".bench_trace" / "engine_split"
    try:
        got = measure(cell, args.seed, args.seconds, True,
                      t_process=T_PROCESS, log_dir=log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    s = got["split"]
    print(describe_spans(got["spans"], got["span_idle"]), file=sys.stderr)
    for k, v in s.items():
        print(f"split {k}: {v!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps({"result": got["result"],
                      "bags_per_s": got["window"].e2e["bags_per_s"],
                      "split": s, "spans": got["spans"],
                      "span_idle": got["span_idle"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
