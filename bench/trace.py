"""From a profiler trace to device metrics.

:func:`start` / :func:`stop` record a window with JAX's profiler (the
Python tracer off, so the host's own work is not slowed by it);
:func:`reduce` reads the ``.xplane.pb`` it wrote with
``jax.profiler.ProfileData`` and returns a :class:`Summary`:

* ``window_s``: the span of the benchmark's host annotation that brackets
  the measured window (``bench.window``);
* ``busy_s``: the union of the intervals in which a program or an
  operation ran on a device (:data:`BUSY_LINES`), inside that window,
  averaged over the devices traced;
* ``kernel_s``: the summed device time of the Pallas kernels (the ops
  :func:`is_kernel` accepts) inside the window, averaged over the
  devices traced;
* ``collective_s``: the same for the collective operations between
  chips (the ops :func:`is_collective` accepts); 0 on one chip;
* ``device_ops``: the ten device operations that took most time, by
  :func:`short_name`;
* ``idle_gaps``: idle device time inside the window, summed by what the
  host was doing at the time: the host event that covers most of each
  gap (the benchmark's own spans around ``submit`` and ``drain`` among
  them), ten labels at most.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

#: the device line of a TPU trace that holds one event per operation
OPS_LINE = "XLA Ops"
#: device lines whose events count as busy time: whole programs (their
#: asynchronous copies among them) and single operations
BUSY_LINES = ("XLA Modules", OPS_LINE)

_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def short_name(name: str) -> str:
    """An operation's event name without its shapes and operands: the
    HLO instruction ``%fn.3 = f32[..] custom-call(...), ...`` becomes
    ``%fn.3 custom-call``; other names are kept."""
    lhs, eq, rhs = name.partition(" = ")
    m = _OPCODE.search(" " + rhs) if eq else None
    return f"{lhs} {m.group(1)}" if m else name


def start(log_dir: Path) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def latest_xplane(path: Path) -> Path:
    """The newest ``.xplane.pb`` under ``path`` (or ``path`` itself)."""
    path = Path(path)
    if path.is_file():
        return path
    found = sorted(path.glob("**/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def _stats(event) -> Dict[str, object]:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def is_kernel(name: str, stats: Dict[str, object]) -> bool:
    """A Pallas kernel: a TPU custom call (every custom call of the flush
    program is the crossbar kernel)."""
    text = " ".join([name] + [str(v) for v in stats.values()]).lower()
    return "custom-call" in text or "custom_call" in text or "mosaic" in text


#: collective operations between devices, and their asynchronous halves
#: (``all-reduce-start``, ``all-gather-done``, ...)
_COLLECTIVE = re.compile(r"(all-reduce|reduce-scatter|all-gather|collective-permute)")


def is_collective(name: str) -> bool:
    """A collective between devices: an operation whose name or opcode
    (:func:`short_name`) is an all-reduce, reduce-scatter, all-gather or
    collective-permute, or the ``-start`` or ``-done`` half of one."""
    return _COLLECTIVE.search(short_name(name)) is not None


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    kernel_s: float
    kernel_events: int
    collective_s: float
    device_ops: List[list]
    idle_gaps: List[list]


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merged, sorted ``(start, end)`` rows of possibly overlapping ones."""
    if intervals.size == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.float64)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], axis=1)
    return iv[iv[:, 1] > iv[:, 0]]


def load(path: Path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(latest_xplane(path)))


def reduce(path: Path, window: str = "bench.window",
           kernel=is_kernel) -> Summary:
    """Reduces the trace at ``path`` (a file or a log directory)."""
    data = load(path)
    host: List[Tuple[str, float, float]] = []
    devices: List[List[Tuple[str, float, float, Dict]]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops, spans = [], []
            for line in plane.lines:
                if line.name not in BUSY_LINES:
                    continue
                for e in line.events:
                    s, d = float(e.start_ns), float(e.duration_ns)
                    spans.append((s, s + d))
                    if line.name == OPS_LINE:
                        ops.append((e.name, s, s + d, _stats(e)))
            if ops:
                devices.append((ops, spans))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append((e.name, float(e.start_ns),
                                     float(e.start_ns + e.duration_ns)))
    marks = [(s, e) for n, s, e in host if n == window]
    if not marks:
        raise ValueError(f"no host span {window!r} in the trace")
    w0, w1 = min(s for s, _ in marks), max(e for _, e in marks)
    if not devices:
        raise ValueError("no device operations in the trace")

    busy = []
    kernel_ns = 0.0
    kernel_events = 0
    collective_ns = 0.0
    per_op: Dict[str, float] = {}
    gaps_ns: Dict[str, float] = {}
    host_iv = [(n, s, e) for n, s, e in host if n != window and e > w0 and s < w1]
    host_arr = (np.asarray([[s, e] for _, s, e in host_iv], dtype=np.float64)
                if host_iv else np.zeros((0, 2)))
    for ops, spans in devices:
        merged = _clip(_union(np.asarray(spans, dtype=np.float64)), w0, w1)
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()))
        for name, s, e, st in ops:
            d = min(e, w1) - max(s, w0)
            if d <= 0:
                continue
            short = short_name(name)
            per_op[short] = per_op.get(short, 0.0) + d
            if kernel(name, st):
                kernel_ns += d
                kernel_events += 1
            elif is_collective(name):
                collective_ns += d
        edges = np.concatenate([[w0], merged.reshape(-1), [w1]]).reshape(-1, 2)
        for g0, g1 in edges:
            if g1 <= g0:
                continue
            label = "no host event"
            if host_arr.size:
                over = (np.minimum(host_arr[:, 1], g1)
                        - np.maximum(host_arr[:, 0], g0))
                k = int(np.argmax(over))
                if over[k] > 0:
                    label = host_iv[k][0]
            gaps_ns[label] = gaps_ns.get(label, 0.0) + (g1 - g0)
    n = len(devices)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(gaps_ns.items(), key=lambda kv: -kv[1])[:10]
    return Summary(
        window_s=(w1 - w0) / 1e9,
        busy_s=sum(busy) / n / 1e9,
        kernel_s=kernel_ns / n / 1e9,
        kernel_events=kernel_events,
        collective_s=collective_ns / n / 1e9,
        device_ops=[[k, v / n / 1e9] for k, v in top],
        idle_gaps=[[k, float(v) / n / 1e9] for k, v in gaps],
    )


def describe(path: Path, events: int = 5) -> str:
    """A plain listing of the trace's planes, lines and first events,
    to look at a trace by hand."""
    data = load(path)
    out = []
    for plane in data.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for e in evs[:events]:
                out.append(f"    {e.name!r} start {e.start_ns} dur "
                           f"{e.duration_ns} {_stats(e)}")
    return "\n".join(out)
