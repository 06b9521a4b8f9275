"""Spans and counters inside the batched sweep engine.

Off by default. While off, :func:`span` returns one shared no-op context
manager and :func:`count` returns at once: a global check, no
allocation. While on, each span opens a ``jax.profiler.TraceAnnotation``
of its name, so that a profiler trace taken meanwhile shows it on the
host plane, on the device trace's clock, and also keeps an in-memory
:class:`Record`; each count adds to a named counter. :func:`take` hands
over what was recorded since the last call, with the sizes of the
engine's host caches read at that moment.

Span and counter names start with ``fabric.``
(``docs/cookbooks/backends.md``, "Tracing a sweep", lists them). Spans
nest as the calls do: a record's ``parent`` is the index, in the same
:func:`take`, of the span open around it. A span opened while no other
is open starts a new ``sweep_id``, which every span inside it shares; in
the engine that span is ``fabric.sweep``, one per ``run_scenarios``
call. At most ``MAX_RECORDS`` records are kept between two calls of
:func:`take`; later spans still reach the profiler's trace, and are
counted in ``fabric.telemetry.dropped``.

One thread at a time: the engine runs a sweep on the calling thread.
"""
from __future__ import annotations

import time
from typing import Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

MAX_RECORDS = 1 << 18


class Record(NamedTuple):
    name: str
    start_ns: int
    end_ns: Optional[int]       # None: still open when take() ran
    parent: Optional[int]       # index of the enclosing record, or None
    sweep_id: int


class Snapshot(NamedTuple):
    records: List[Record]
    counters: Dict[str, int]
    caches: Dict[str, int]      # entries and bytes held, read at take()


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()
_on = False
_records: List[list] = []       # [name, start_ns, end_ns, parent, sweep_id]
_counters: Dict[str, int] = {}
_stack: List[tuple] = []        # (records list, index or None, sweep_id)
_sweeps = 0


class _Span:
    __slots__ = ("_name", "_annotation", "_slot")

    def __init__(self, name: str):
        self._name = name

    def __enter__(self):
        global _sweeps
        self._annotation = TraceAnnotation(self._name)
        self._annotation.__enter__()
        if _stack:
            recs, parent, sweep_id = _stack[-1]
            if recs is not _records:        # opened before the last take()
                parent = None
        else:
            _sweeps += 1
            parent, sweep_id = None, _sweeps
        if len(_records) < MAX_RECORDS:
            index = len(_records)
            _records.append([self._name, 0, None, parent, sweep_id])
        else:
            index = None
            _counters["fabric.telemetry.dropped"] = \
                _counters.get("fabric.telemetry.dropped", 0) + 1
        self._slot = (_records, index, sweep_id)
        _stack.append(self._slot)
        if index is not None:
            _records[index][1] = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        recs, index, _ = self._slot
        if index is not None:
            recs[index][2] = end
        _stack.pop()
        self._annotation.__exit__(*exc)
        return False


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    """Stop recording; what was recorded stays for :func:`take`."""
    global _on
    _on = False


def enabled() -> bool:
    return _on


def span(name: str):
    """A context manager timing the block as span ``name``."""
    if not _on:
        return _NOOP
    return _Span(name)


def count(name: str, n: int = 1) -> None:
    if _on:
        _counters[name] = _counters.get(name, 0) + n


def take() -> Snapshot:
    """The records and counters since the last call, which are cleared,
    and the engine's host caches as they stand."""
    global _records, _counters
    out = Snapshot([Record(*r) for r in _records], _counters,
                   _cache_sizes())
    _records, _counters = [], {}
    return out


def _cache_sizes() -> Dict[str, int]:
    from repro.fabric.backend import jnp_engine as E
    return {
        "fabric.engine_cache.entries": len(E._ENGINE_CACHE),
        "fabric.compute_stream.entries": len(E._COMPUTE_CACHE),
        "fabric.compute_stream.bytes": sum(
            a.nbytes for a in E._COMPUTE_CACHE.values()),
        "fabric.gauss_stream.entries": len(E._GAUSS_CACHE),
        "fabric.gauss_stream.bytes": sum(
            a.nbytes for a in E._GAUSS_CACHE.values()),
        "fabric.runners.entries": len(E._RUNNERS),
    }
