"""Keyed completion stamps of the async engine (DESIGN.md §7.2).

Every bag the async engine accepts gets a submit stamp on the producer
(one ``perf_counter`` read in ``submit()``) and a completion stamp when
its flush retires (one read per retired flush, written against the
flush's sequence-id array at once).  Stamps are keyed by ``(producer
id, table, local seq)``, the decoded form of the packed sequence id
(:mod:`repro.serve.producers`), so a reader can time each request
against its own schedule (an open-loop arrival's due time) rather than
read an anonymous latency sample.

Storage is one pair of float arrays per ``(producer, table)`` sequence
space, indexed by local seq from a moving base: ``NaN`` in the
completion array marks a bag still in the engine, ``-inf`` a bag whose
record is gone (taken, or quarantined, which never completes).
:meth:`CompletionStamps.take` hands back every completed record and
drops the prefix of the space that holds nothing pending, so memory is
bounded by what has not been taken.

A quiesced ``drain()`` restarts every sequence space at local seq 0;
:meth:`CompletionStamps.seal` closes the epoch first, so records of
two epochs, whose local seqs repeat, are never mixed in one record.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Tuple

import numpy as np

from repro.serve.producers import SEQ_STRIDE

#: first capacity of a space's stamp arrays (doubled when full)
_INITIAL_CAPACITY = 1024


@dataclasses.dataclass
class StampRecords:
    """Completed bags of one ``(epoch, producer, table)`` space.

    ``local_seq``, ``submitted`` and ``completed`` are parallel arrays
    (``perf_counter`` seconds), ordered by local seq.  ``producer`` is
    the producer's pid inside the store and its label once a server
    hands the records out.
    """

    epoch: int
    producer: object
    table: str
    local_seq: np.ndarray
    submitted: np.ndarray
    completed: np.ndarray


class _Space:
    """Stamp arrays of one ``(producer, table)`` sequence space."""

    __slots__ = ("base", "n", "submitted", "completed")

    def __init__(self):
        self.base = 0                      # local seq of index 0
        self.n = 0                         # indices in use
        self.submitted = np.empty(_INITIAL_CAPACITY, np.float64)
        self.completed = np.full(_INITIAL_CAPACITY, np.nan, np.float64)

    def grow(self, i: int) -> None:
        """Makes room for index ``i`` (capacity doubles)."""
        cap = max(2 * self.submitted.size, i + 1)
        sub = np.empty(cap, np.float64)
        sub[:self.n] = self.submitted[:self.n]
        done = np.full(cap, np.nan, np.float64)
        done[:self.n] = self.completed[:self.n]
        self.submitted, self.completed = sub, done

    def records(self) -> np.ndarray:
        """Indices of the completed bags not taken yet."""
        return np.flatnonzero(np.isfinite(self.completed[:self.n]))

    def compact(self) -> None:
        """Drops the leading indices that hold nothing pending."""
        pending = np.flatnonzero(np.isnan(self.completed[:self.n]))
        k = int(pending[0]) if pending.size else self.n
        if k == 0:
            return
        keep = self.n - k
        self.submitted[:keep] = self.submitted[k:self.n]
        self.completed[:keep] = self.completed[k:self.n]
        self.completed[keep:self.n] = np.nan
        self.base += k
        self.n = keep


class CompletionStamps:
    """Submit and completion stamps keyed by ``(producer, table, local
    seq)``.  Thread-safe: producers stamp submits while the engine
    stamps completions; each call holds the store's own lock briefly
    (innermost, after every server lock)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._epoch = 0
        self._spaces: Dict[Tuple[int, str], _Space] = {}
        self._sealed: List[StampRecords] = []

    def submitted(self, table: str, gseq: int, t: float) -> None:
        """Stamps one accepted bag's submit time."""
        local, pid = divmod(gseq, SEQ_STRIDE)
        with self._lock:
            space = self._spaces.get((pid, table))
            if space is None:
                space = self._spaces[(pid, table)] = _Space()
            i = local - space.base
            if i >= space.n:
                if i >= space.submitted.size:
                    space.grow(i)
                space.n = i + 1
            space.submitted[i] = t

    def completed(self, table: str, gseqs: np.ndarray, t: float) -> None:
        """Stamps one flush's bags of ``table`` complete at ``t``, all at
        once (one array write per producer in the flush)."""
        gseqs = np.asarray(gseqs, dtype=np.int64)
        if gseqs.size == 0:
            return
        pids = gseqs % SEQ_STRIDE
        locals_ = gseqs // SEQ_STRIDE
        with self._lock:
            if pids.min() == pids.max():
                groups = [(int(pids[0]), locals_)]
            else:
                groups = [(int(p), locals_[pids == p]) for p in np.unique(pids)]
            for pid, loc in groups:
                space = self._spaces.get((pid, table))
                if space is not None:
                    i = loc - space.base
                    # an id that got no submit stamp has no slot
                    space.completed[i[(i >= 0) & (i < space.n)]] = t

    def dropped(self, table: str, gseq: int) -> None:
        """Forgets a bag that will never complete (quarantined)."""
        local, pid = divmod(gseq, SEQ_STRIDE)
        with self._lock:
            space = self._spaces.get((pid, table))
            if space is not None and 0 <= local - space.base < space.n:
                space.completed[local - space.base] = -np.inf

    def _records_locked(self, space: _Space, pid: int, table: str,
                        idx: np.ndarray) -> StampRecords:
        return StampRecords(
            epoch=self._epoch, producer=pid, table=table,
            local_seq=idx.astype(np.int64) + space.base,
            submitted=space.submitted[idx].copy(),
            completed=space.completed[idx].copy(),
        )

    def seal(self) -> None:
        """Closes the epoch at a sequence reset: the completed records of
        every space are kept aside and the spaces start over empty."""
        with self._lock:
            for (pid, table), space in sorted(self._spaces.items()):
                idx = space.records()
                if idx.size:
                    self._sealed.append(
                        self._records_locked(space, pid, table, idx))
            self._spaces = {}
            self._epoch += 1

    def take(self) -> List[StampRecords]:
        """Every completed record not taken before, one per ``(epoch,
        producer, table)``, oldest epoch first; taking clears them."""
        with self._lock:
            out, self._sealed = self._sealed, []
            for (pid, table), space in sorted(self._spaces.items()):
                idx = space.records()
                if idx.size:
                    out.append(self._records_locked(space, pid, table, idx))
                    space.completed[idx] = -np.inf
                space.compact()
            return out

    def latencies(self) -> np.ndarray:
        """Completion minus submit of every record not yet taken (read
        only: nothing is cleared)."""
        with self._lock:
            parts = [r.completed - r.submitted for r in self._sealed]
            for space in self._spaces.values():
                idx = space.records()
                parts.append(space.completed[idx] - space.submitted[idx])
        return np.concatenate(parts) if parts else np.zeros(0)
