"""Batch traffic: one producer submits bags back to back.

The producer is paced only by the server's hand-off queue filling up
(its backpressure).  With several tables each request is a sample of one
bag per table, the sample's tables in a seeded order.  The mix's ``bags``
bags, cut into whole batches, are the job's data set.  Warm-up submits
them all once, untimed, and drains: every flush the window will dispatch
has then been dispatched once, so nothing compiles inside the window.
The window submits the same batches again, in the same order and
starting over at the end of the set, until ``seconds`` have passed, then
drains; ``bags_per_s`` is every bag that drain returned over the time
from the first timed submit to drain's return.
"""

from __future__ import annotations

import time

import numpy as np

from bench import gen
from bench.harness import Window


def sequence(session):
    """``(table index per bag, bags)`` of the whole stream, in order."""
    data = session.data
    n = sum(len(s) for s in data.streams.values())
    if len(data.names) == 1:
        tabs = np.zeros(n, dtype=np.int64)
    else:
        tabs = gen.sample_table_order(data.names, n, gen.rng_for(session.seed, 3))
    cursor = [0] * len(data.names)
    bags = []
    for t in tabs.tolist():
        bags.append(data.streams[data.names[t]][cursor[t]])
        cursor[t] += 1
    return tabs, bags


def warm(session) -> str:
    server = session.server
    names = session.data.names
    tabs, bags = sequence(session)
    n = len(bags) // session.batch_size * session.batch_size
    session.sequence = (tabs[:n], bags[:n])
    for k in range(n):
        server.submit(names[tabs[k]], bags[k])
    server.drain()
    stats = server.stats
    return (f"{n} bags in {stats.batches} flushes, widest block union "
            f"{stats.max_shard_width} tiles")


def window(session, seconds: float) -> Window:
    server = session.server
    names = session.data.names
    tabs, bags = session.sequence
    size = session.batch_size
    order = []                           # stream index of each bag submitted
    with session.span("bench.window"):
        t_first = time.perf_counter()
        while time.perf_counter() - t_first < seconds:
            i = len(order) % len(bags)
            with session.span("bench.submit"):
                for k in range(i, i + size):
                    server.submit(names[tabs[k]], bags[k])
            order.extend(range(i, i + size))
        with session.span("bench.drain"):
            out = server.drain()
        t_end = time.perf_counter()
    served = 0
    groups = []
    wtabs = tabs[order]
    wbags = [bags[k] for k in order]
    for t, name in enumerate(names):
        mine = [b for tt, b in zip(wtabs.tolist(), wbags) if tt == t]
        rows = np.asarray(out[name]) if name in out else None
        served += 0 if rows is None else rows.shape[0]
        groups.append((name, mine, rows))
    return Window(
        t_first=t_first, t_end=t_end, attempted=len(order), groups=groups,
        e2e={"bags_per_s": served / (t_end - t_first)},
        flushes=(wtabs, wbags),
    )
