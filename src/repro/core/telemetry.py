"""Spans and counters of each solve, kept in memory.

:func:`repro.core.api.solve` opens one record per call (:func:`record`):
a ``solve_id``, the root span ``repro.solve``, the spans its layers open
inside it (:func:`span`) and the work its engines count
(:func:`count`). Records go into a buffer of the last
:data:`CAPACITY` solves; :func:`recent` reads them as plain dicts.

- A span records ``(solve_id, name, start_ns, end_ns, parent)`` on
  ``time.perf_counter_ns`` and enters ``jax.profiler.TraceAnnotation`` of
  the same name, so that a profiler trace holds it on the device's clock.
  Every name starts with ``repro.``.
- Device counters are kept as the device scalars the engines return and
  are fetched only by :func:`recent`: recording adds no wait for the
  device to a solve.
- Outside a record (an engine called directly, or a solve traced under an
  outer ``jit``) spans and counts record nothing, and no tracer is kept.

Counters (summed over a batch's instances):

  greedy_rounds    greedy proposal rounds, the last of which matches nothing
  greedy_matched   pairs matched when greedy ends
  mcm_bfs_layers   BFS layers over all MCM phases
  mcm_sorted_layers  BFS layers whose parent choice ran the sorted-segment
                   scan: all of them on the single-instance route
                   (``single._mcm_bfs``) and on the grid engine (over each
                   device's edge block), None on the batched engine, whose
                   reduction scatters, and so on every warm solve
  awac_augmented   4-cycles augmented over all AWAC rounds
  h2d_bytes        bytes of the host arrays handed to the engines' jitted
                   calls, each of which copies them to the device
  a2a_slots        bucket slots the grid engine's AWAC all-to-alls carried,
                   over both stages, every round and every device (each
                   device's own bucket included)
  a2a_filled       those of them that held a candidate
  a2a_bytes        the bytes those slots carried (``dist.a2a_slot_bytes``:
                   the payloads, and the validity byte on the unpacked path)
  partition_sorted instances whose edges the grid's host partition found
                   lex-sorted and split into blocks by one linear pass
                   (``sparse.partition._block_order``), not the lexsort

The ``a2a_*`` counters and ``partition_sorted`` are the grid engine's
alone: a solve that ran no grid exchange reads None there, not 0.
"""
from __future__ import annotations

import collections
import contextvars
import itertools
import time

import jax
import numpy as np

#: Solves whose records the buffer keeps.
CAPACITY = 64

#: Counters only the grid engine keeps; None where it did not run.
GRID_COUNTERS = ("a2a_slots", "a2a_filled", "a2a_bytes", "partition_sorted")
#: The counters a solve record holds; each other one starts at 0.
COUNTERS = ("greedy_rounds", "greedy_matched", "mcm_bfs_layers",
            "mcm_sorted_layers", "awac_augmented",
            "h2d_bytes") + GRID_COUNTERS

_records: collections.deque = collections.deque(maxlen=CAPACITY)
_solve_ids = itertools.count()
_current: contextvars.ContextVar = contextvars.ContextVar(
    "repro_telemetry_record", default=None)


class _Record:
    """One solve: spans as [name, start_ns, end_ns, parent index], the
    indices of the spans open now, and each counter's recorded values."""

    __slots__ = ("solve_id", "spans", "open", "counts")

    def __init__(self, solve_id: int):
        self.solve_id = solve_id
        self.spans: list[list] = []
        self.open: list[int] = []
        self.counts: dict[str, list] = {name: [] for name in COUNTERS}


class span:
    """Context manager: the span ``name`` in the current solve's record,
    also entered as a ``jax.profiler.TraceAnnotation``. Records nothing
    outside a record."""

    __slots__ = ("name", "_rec", "_entry", "_annotation")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._rec = rec = _current.get()
        if rec is None:
            return self
        self._entry = [self.name, 0, 0,
                       rec.open[-1] if rec.open else None]
        rec.open.append(len(rec.spans))
        rec.spans.append(self._entry)
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._entry[1] = time.perf_counter_ns()
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        rec = self._rec
        if rec is None:
            return False
        self._annotation.__exit__(*exc)
        self._entry[2] = time.perf_counter_ns()
        rec.open.pop()
        return False


class record:
    """Context manager: a new solve record with its root span
    ``repro.solve``, current until the block exits and then put in the
    buffer (a solve that raised included)."""

    __slots__ = ("_rec", "_token", "_root")

    def __enter__(self):
        self._rec = _Record(next(_solve_ids))
        self._token = _current.set(self._rec)
        self._root = span("repro.solve")
        self._root.__enter__()

    def __exit__(self, *exc):
        try:
            self._root.__exit__(*exc)
        finally:
            _current.reset(self._token)
            _records.append(self._rec)
        return False


def count(counters: dict) -> None:
    """Add ``counters`` (name -> device or host count, or None where the
    engine that ran does not count it) to the current solve's counters.
    Tracers are dropped: a traced engine's counts belong to no solve."""
    rec = _current.get()
    if rec is None:
        return
    for name, value in counters.items():
        if not isinstance(value, jax.core.Tracer):
            rec.counts[name].append(value)


def copied(*args) -> None:
    """Add the bytes of the host arrays among ``args`` (pytrees) to the
    current solve's ``h2d_bytes``: a jitted call copies each to the
    device."""
    rec = _current.get()
    if rec is not None:
        rec.counts["h2d_bytes"].append(sum(
            x.nbytes for x in jax.tree_util.tree_leaves(args)
            if isinstance(x, np.ndarray)))


def call(name: str, fn, *args, **kwargs):
    """``fn(*args, **kwargs)`` inside the span ``name``, its host arrays
    added to ``h2d_bytes`` (:func:`copied`)."""
    with span(name):
        copied(args, kwargs)
        return fn(*args, **kwargs)


def _total(name, values):
    if any(v is None for v in values) or (
            not values and name in GRID_COUNTERS):
        return None
    return int(sum(int(np.asarray(v).sum()) for v in values))


def recent(k: int) -> list[dict]:
    """The last ``k`` solves' records, oldest first, as plain dicts:
    ``solve_id``; ``spans``, each {solve_id, name, start_ns, end_ns,
    parent} with ``parent`` the index of the enclosing span in the list
    (None for the root); ``counters``, name -> int or None. Fetching the
    counters waits for the device work that computes them."""
    recs = list(_records)[-k:] if k > 0 else []
    return [{"solve_id": r.solve_id,
             "spans": [{"solve_id": r.solve_id, "name": name,
                        "start_ns": start, "end_ns": end, "parent": parent}
                       for name, start, end, parent in r.spans],
             "counters": {name: _total(name, values)
                          for name, values in r.counts.items()}}
            for r in recs]
