"""One run of one benchmark cell: set-up, measured window, check, report.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in the file that entry names, its
traffic mix in ``bench/traffic/<traffic>.json``, the mix's driver in
``bench/drivers/<kind>.py`` and each per-layer metric in
``bench/metrics/<name>.py``.  A new cell, configuration, mix or metric is
new files and entries, never an edit here.

The run (:func:`run`) makes the tables on the device from the seed, one at
a time, each copied to the host and freed before the next, and the plan
history and request bags from :mod:`bench.gen`; builds the server (timed
as the plan build) on a mesh of the cell's chips where it has more than
one; lets the driver warm every flush shape its traffic uses, measures
the driver's window, reads every device's memory peak, closes the
server, and only then checks every bag of the window against
:mod:`bench.reference`, table by table.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import gen

ROOT = Path(__file__).resolve().parent.parent

class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------- loading --

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(root: Path, kind: str, name: str):
    """``bench/<kind>/<name>.py`` under ``root`` as a module."""
    path = Path(root) / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, cell: str) -> bool:
    """Whether a ``BENCHMARK.json`` metric is reported in ``cell``."""
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    """One ``workloads`` entry with everything it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path

    @property
    def max_gap_limit(self) -> float:
        """The compared number's limit: the widest |served - reference|
        gap of any element of any bag.  The configuration states it, set
        between the program's worst reading over its seeds and the
        control's least (PERF.md, section 2)."""
        return float(self.config["max_gap_limit"])

    def driver(self):
        return load_module(self.root, "drivers", self.traffic["kind"])

    def readers(self) -> Dict[str, object]:
        return {m["name"]: load_module(self.root, "metrics", m["name"])
                for m in self.per_layer}


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """Finds ``workload`` in ``root/BENCHMARK.json`` and loads its parts."""
    root = Path(root)
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if applies(m, workload)],
        root=root,
    )


# ------------------------------------------------------------ the chip --

def find_chips(chips: int):
    """The TPU devices a cell runs on; :class:`NoChip` otherwise."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform is {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, found {len(devices)}")
    return devices[:chips]


def peaks_for(device_kind: str, root: Path = ROOT) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    table = load_json(Path(root) / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; have {sorted(table)}")
    return table[device_kind]


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed place in the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(root) / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Counts backend compiles and persistent-cache loads of the process
    (``jax.monitoring`` listeners can not be removed, so one instance
    serves every run of a process: :func:`compile_log`)."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def count(self) -> int:
        """Programs made ready so far: compiled, or loaded from the cache."""
        return self.compiles + self.cache_hits


_COMPILE_LOG: Optional[CompileLog] = None


def compile_log() -> CompileLog:
    global _COMPILE_LOG
    if _COMPILE_LOG is None:
        _COMPILE_LOG = CompileLog()
    return _COMPILE_LOG


# ------------------------------------------------------------ the data --

@dataclasses.dataclass
class Data:
    """A run's inputs, all made from its seed."""

    names: List[str]                     # table names, the server's order
    shapes: List[tuple]                  # (rows, dim) per table
    histories: Dict[str, list]           # plan history per table
    streams: Dict[str, list]             # request bags per table, in order


def make_data(config: dict, traffic: dict, seed: int, n_bags: int) -> Data:
    """Plan histories and ``n_bags`` request bags, an equal share of them
    per table.

    The bags continue the generator the history came from, or, where the
    mix has ``drift``, come from the same generator under another seed:
    moved hot rows and moved co-occurrence, against a plan of the old."""
    names = sorted(config["tables"])
    dim = int(config["dim"])
    history = int(config["history_bags"])
    n_stream = -(-n_bags // len(names))
    histories, streams = {}, {}
    for i, name in enumerate(names):
        t = config["tables"][name]
        tseed = int(gen.rng_for(seed, 1, i).integers(2**62))
        trace = gen.table_bags(t, history, n_stream, tseed)
        histories[name] = trace[:history]
        if traffic.get("drift"):
            dseed = int(gen.rng_for(seed, 2, i).integers(2**62))
            streams[name] = gen.table_bags(t, 0, n_stream, dseed)
        else:
            streams[name] = trace[history:]
    shapes = [(int(config["tables"][n]["rows"]), dim) for n in names]
    return Data(names, shapes, histories, streams)


def table_seed(seed: int) -> int:
    """The seed the run's table values are made from."""
    return int(gen.rng_for(seed, 0).integers(2**62))


# ------------------------------------------------------------- session --

@dataclasses.dataclass
class Session:
    """What a driver gets: the server under test and the run's inputs."""

    cell: Cell
    seed: int
    data: Data
    server: object
    trace: bool
    log: CompileLog
    sequence: object = None              # a driver's bag order, if it keeps one

    @property
    def batch_size(self) -> int:
        return int(self.cell.config["server"]["batch_size"])

    def span(self, name: str):
        """A host span in the profiler's trace (a no-op when not tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Window:
    """What a driver's measured window returns.

    ``groups`` are ``(table, bags, served rows)`` triples, one per
    (producer, table) stream, bags in submission order.  ``flushes`` is
    ``(table index per bag, bags)`` in the order the flushes took them,
    where the driver knows it (one producer), else ``None``."""

    t_first: float
    t_end: float
    attempted: int
    groups: List[tuple]
    e2e: Dict[str, float]
    flushes: Optional[tuple] = None


def server_kwargs(cell: Cell) -> dict:
    kw = dict(cell.config["server"])
    kw.update(cell.traffic.get("server", {}))
    return kw


def shards_of(cell: Cell) -> int:
    """The server's ``num_shards``, which has to be the cell's chips: one
    shard per chip of the mesh, or one shard on one chip."""
    shards = int(server_kwargs(cell).get("num_shards", 1))
    if shards != cell.chips:
        raise ValueError(f"cell {cell.name!r} asks for {cell.chips} chips but "
                         f"its server has num_shards {shards}; they must agree")
    return shards


def make_mesh(devices):
    """The serving mesh over ``devices`` on its ``model`` axis (the
    server's default ``axis_name``), or ``None`` for one device, which
    the server then serves without ``shard_map``."""
    if len(devices) == 1:
        return None
    import jax

    return jax.make_mesh((1, len(devices)), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=devices)


def host_tables(seed: int, data: Data) -> Dict[str, np.ndarray]:
    """Every table of the run on the host: each made on the device, copied
    and freed before the next is made."""
    from bench import reference

    host = {}
    for i, name in enumerate(data.names):
        table = reference.make_table(table_seed(seed), data.shapes, i)
        host[name] = np.asarray(table)
        del table
    return host


def memory_peaks(devices) -> List[int]:
    """Each device's peak bytes in use (0 where the backend keeps none)."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def stats_snapshot(stats) -> dict:
    return {
        "batches": stats.batches,
        "grid_cells": stats.grid_cells_per_shard,
        "host_compile_s": stats.host_compile_s,
    }


# ----------------------------------------------------------- the check --

@dataclasses.dataclass
class Check:
    max_gap: float
    missing: int
    failed: int
    correct: bool


def check(window: Window, data: Data, seed: int, report: dict,
          limit: float) -> Check:
    """Every bag of the window against the reference; the tables are
    made again from the seed, one at a time, each freed before the next;
    nothing is taken from the program."""
    from bench import reference

    index = {name: i for i, name in enumerate(data.names)}
    gap = 0.0
    missing = 0
    for name, bags, served in window.groups:
        served = (np.zeros((0, data.shapes[0][1]), np.float32)
                  if served is None else np.asarray(served, dtype=np.float32))
        if served.shape[0] != len(bags):
            missing += abs(len(bags) - served.shape[0])
            continue
        if not np.all(np.isfinite(served)):
            gap = math.inf
            continue
        table = reference.make_table(table_seed(seed), data.shapes, index[name])
        gap = max(gap, reference.max_gap(table, bags, served))
        del table
    faults = report["serve"]["faults"]
    failed = (missing + len(faults["quarantined"]) + faults["degraded_flushes"]
              + report["serve"]["tiers"]["host_flushes"])
    correct = missing == 0 and gap <= limit
    return Check(gap, missing, failed, correct)


# ----------------------------------------------------------------- run --

def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_process: float, need_chip: bool = True,
        log_dir: Optional[Path] = None) -> dict:
    """One run of ``cell``; returns the result object the last line of
    standard output carries.  Earlier lines go to standard error."""
    err = sys.stderr
    import jax

    shards = shards_of(cell)
    if need_chip:
        devices = find_chips(cell.chips)
        peaks = peaks_for(devices[0].device_kind, cell.root)
    else:
        devices = jax.devices()[:cell.chips]
        peaks = None
    device = devices[0]
    print(f"device: {device.device_kind} x {len(devices)} "
          f"({device.platform}), {time.perf_counter() - t_process:.3f} s "
          f"after process start", file=err)
    print(f"compilation cache: {enable_compile_cache(cell.root)}", file=err)
    log = compile_log()

    from bench import reference
    from bench import trace as tracing
    from repro.serve import ShardedEmbeddingServer

    config, traffic = cell.config, cell.traffic
    t0 = time.perf_counter()
    n_bags = int(traffic["bags"])
    data = make_data(config, traffic, seed, n_bags)
    host = host_tables(seed, data)
    print(f"data: {len(data.names)} tables, {sum(s[0] for s in data.shapes)} "
          f"rows x {data.shapes[0][1]}, {sum(h.nbytes for h in host.values()) / 2**30:.3f}"
          f" GiB, {n_bags} bags made in "
          f"{time.perf_counter() - t0:.3f} s", file=err)

    t0 = time.perf_counter()
    mesh = make_mesh(devices)
    server = ShardedEmbeddingServer(host, data.histories, mesh=mesh,
                                    **server_kwargs(cell))
    jax.block_until_ready(server.shard_images)
    plan_build_s = time.perf_counter() - t0
    print(f"plan build + image placement: {plan_build_s!r} s "
          f"({shards} shard(s) of {server.shard_images.shape[1]} tiles, "
          f"{'no mesh' if mesh is None else f'mesh {dict(mesh.shape)}'})",
          file=err)

    session = Session(cell, seed, data, server, trace, log)
    driver = cell.driver()
    t0 = time.perf_counter()
    c0 = log.count()
    warm = driver.warm(session)
    print(f"warm-up: {warm}; {log.count() - c0} programs made ready in "
          f"{time.perf_counter() - t0:.3f} s; set-up "
          f"{time.perf_counter() - t_process:.3f} s", file=err)

    stats = server.stats
    before = stats_snapshot(stats)
    compiles0 = log.count()
    trace_dir = None
    if trace:
        trace_dir = Path(log_dir or (cell.root / ".bench_trace")) / f"{cell.name}-{seed}"
        tracing.start(trace_dir)
    window = driver.window(session, seconds)
    if trace:
        tracing.stop()
    window_compiles = log.count() - compiles0
    after = stats_snapshot(stats)
    setup_s = window.t_first - t_process
    memory_peak = memory_peaks(devices)
    server.close()
    report = server.report()
    host_compile = after["host_compile_s"] - before["host_compile_s"]
    del server, session, host
    gc.collect()
    print(f"window: {window.attempted} bags in {window.t_end - window.t_first:.3f}"
          f" s, {after['batches'] - before['batches']} flushes,"
          f" {window_compiles} programs made ready inside it", file=err)

    t0 = time.perf_counter()
    limit = cell.max_gap_limit
    result = check(window, data, seed, report, limit)
    print(f"check: {len(window.groups)} streams, {window.attempted} bags "
          f"against the reference in {time.perf_counter() - t0:.3f} s", file=err)

    measured = Measured(
        plan_build_s=plan_build_s, before=before, after=after,
        host_compile_s=host_compile,
        window_compiles=window_compiles, window=window, peaks=peaks,
        dim=data.shapes[0][1], itemsize=4,
        tile_rows=int(config["server"]["group_size"]),
        chips=len(devices), shards=shards,
    )
    out_device = {"platform": device.platform, "kind": device.device_kind,
                  "count": len(devices), "memory_peak_bytes": max(memory_peak),
                  "memory_peak_bytes_per_device": memory_peak}
    metrics: Dict[str, dict] = {}
    breakdown = None
    if trace:
        summary = tracing.reduce(trace_dir, window="bench.window")
        if log_dir is None:          # a caller's own directory is its to keep
            shutil.rmtree(trace_dir, ignore_errors=True)
        measured.trace = summary
        if window.flushes is not None:
            tabs, bags = window.flushes
            measured.needed_bytes = reference.needed_bytes(
                tabs, bags, int(config["server"]["batch_size"]),
                measured.dim, measured.itemsize)
        out_device["busy_s"] = summary.busy_s
        out_device["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.device_ops,
                     "idle_gaps": summary.idle_gaps}
        print(f"trace: busy {summary.busy_s!r} s of {summary.window_s!r} s, "
              f"kernel {summary.kernel_s!r} s in {summary.kernel_events} events, "
              f"collectives {summary.collective_s!r} s (per device)", file=err)
        baseline = gather_baseline(window, data, seed, measured)
        if baseline:
            print(baseline, file=err)
        readers = cell.readers()
        for m in cell.per_layer:
            value = readers[m["name"]].read(measured)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(window.e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    checks = {
        "max_gap": {"value": result.max_gap, "limit": limit},
        "missing_bags": {"value": result.missing, "limit": 0},
    }
    print(f"check max_gap {result.max_gap!r} limit {limit!r}", file=err)
    print(f"check missing_bags {result.missing} limit 0", file=err)
    out = {"correct": result.correct, "attempted": window.attempted,
           "failed": result.failed, "metrics": metrics, "device": out_device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


@dataclasses.dataclass
class Measured:
    """What a per-layer metric reader reads (``bench/metrics/*.py``)."""

    plan_build_s: float
    before: dict                 # stats_snapshot at the window's start
    after: dict                  # ... and at its end
    host_compile_s: float        # the window's host block compile, s
    window_compiles: int         # programs compiled or loaded in the window
    window: Window
    peaks: Optional[dict]        # bench/peaks.json entry of the chip
    dim: int
    itemsize: int
    tile_rows: int
    chips: int                   # devices the window ran on
    shards: int                  # the server's num_shards, one grid each
    trace: object = None         # bench.trace.Summary of a traced run
    needed_bytes: Optional[int] = None

    @property
    def flushes(self) -> int:
        return self.after["batches"] - self.before["batches"]


def gather_baseline(window: Window, data: Data, seed: int, m: Measured) -> str:
    """Times the reference's gather-and-sum over the window's bags and
    gives its share of the HBM roofline beside the kernel's (the plain
    XLA gather baseline; printed, not a metric).  The gather runs one
    table at a time on one device, so its wall is the sum of the tables'
    walls and its bound one chip's; the kernel's share is bounded by all
    the cell's chips, as ``kernel_roofline_pct`` is."""
    import jax

    from bench import reference

    if window.flushes is None or m.needed_bytes is None or m.peaks is None:
        return ""
    tabs, bags = window.flushes
    wall = 0.0
    for i in range(len(data.names)):
        mine = [b for t, b in zip(tabs.tolist(), bags) if t == i]
        if not mine:
            continue
        table = reference.make_table(table_seed(seed), data.shapes, i)
        length = reference.pad_len(mine)
        work = [(table, jax.device_put(ids), jax.device_put(mask))
                for _, ids, mask in reference.blocks(mine, length)]
        jax.block_until_ready(reference.block_rows(*work[0]))  # compile
        t0 = time.perf_counter()
        jax.block_until_ready([reference.block_rows(*w) for w in work])
        wall += time.perf_counter() - t0
        del table, work
    bound = m.needed_bytes / m.peaks["hbm_bytes_per_s"]
    kernel = ("not measured" if not (m.trace and m.trace.kernel_s)
              else f"{100 * bound / m.chips / m.trace.kernel_s!r} %")
    return (f"gather baseline: reference gather-and-sum over the window's "
            f"{len(bags)} bags in {wall!r} s (host clock, device work only),"
            f" HBM roofline share {100 * bound / wall!r} %; crossbar kernel "
            f"share {kernel}")
