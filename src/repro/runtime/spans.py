"""Spans: named host intervals at the program's layer boundaries.

    from repro.runtime import spans
    with spans.span("repro.level.step", level=3, variant="bu") as s:
        ...
    s.t1 - s.t0                      # the interval, on time.perf_counter
    spans.records(since=t)           # closed spans, oldest first

Each `span` does two things. It opens a `jax.profiler.TraceAnnotation`, so
a profiler trace holds the span as a host-plane event on the same clock as
the device ops (with no profiler running that is a no-op TraceMe whose
metadata is never formatted). And when it closes it appends its record to
one bounded in-process ring, which the benchmark and an operator read
without a profiler.

A record holds `name`, `t0`/`t1` (`time.perf_counter`), `span_id`,
`parent_id` (the innermost span open on the same thread when it opened),
`search_id` (the `span_id` of the enclosing `repro.search`, shared by every
span under one search) and `attrs`. A count is the number of records of a
name; sizes and bytes are attributes. The ring keeps the newest
`CAPACITY` records; `oldest()` says from when on it still holds every
record, so a reader can tell that it dropped part of the range it reads.
The span names, their attributes and what reads them are listed in API.md.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Optional

from jax.profiler import TraceAnnotation

CAPACITY = 4096
SEARCH = "repro.search"


class Span:
    """One span; the record the ring keeps once it has closed."""

    __slots__ = ("name", "t0", "t1", "span_id", "parent_id", "search_id",
                 "attrs", "_tm")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.t0 = self.t1 = None
        self.span_id = self.parent_id = self.search_id = None

    def __enter__(self) -> "Span":
        stack = _ring.stack()
        self.span_id = next(_ring.ids)
        if stack:
            self.parent_id = stack[-1].span_id
            self.search_id = stack[-1].search_id
        if self.name == SEARCH:
            self.search_id = self.span_id
        stack.append(self)
        self._tm = TraceAnnotation(self.name, **self.attrs)
        self._tm.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        self._tm.__exit__(*exc)
        self._tm = None
        _ring.stack().pop()
        _ring.append(self)
        return False

    def __repr__(self):
        return (f"Span({self.name!r}, t0={self.t0}, t1={self.t1}, "
                f"id={self.span_id}, parent={self.parent_id}, "
                f"search={self.search_id}, attrs={self.attrs})")


class _Ring:
    """The bounded record store and each thread's stack of open spans."""

    def __init__(self, capacity: int):
        self.records = collections.deque(maxlen=capacity)
        self.lock = threading.Lock()
        self.local = threading.local()
        self.ids = itertools.count(1)
        self.complete_from = float("-inf")

    def stack(self) -> list:
        stack = getattr(self.local, "stack", None)
        if stack is None:
            stack = self.local.stack = []
        return stack

    def append(self, rec: Span) -> None:
        with self.lock:
            if len(self.records) == self.records.maxlen:
                self.complete_from = max(self.complete_from,
                                         self.records[0].t1)
            self.records.append(rec)


_ring = _Ring(CAPACITY)


def span(name: str, **attrs) -> Span:
    """A context manager over one span named `name` (see the module doc).

    Attributes set on `attrs` of the returned span before it closes are
    kept in its record; the profiler event carries those given here."""
    return Span(name, attrs)


def records(since: Optional[float] = None,
            until: Optional[float] = None) -> list:
    """Closed spans, oldest first: those that start at or after `since`
    and end at or before `until` (either may be left out)."""
    with _ring.lock:
        recs = list(_ring.records)
    return [r for r in recs
            if (since is None or r.t0 >= since)
            and (until is None or r.t1 <= until)]


def oldest() -> float:
    """The time from which the ring holds every span that ended: -inf until
    it first drops a record, then the end of the last record it dropped."""
    with _ring.lock:
        return _ring.complete_from


def reset(capacity: int = CAPACITY) -> None:
    """Drop every record; the ring then holds `capacity` (tests)."""
    with _ring.lock:
        _ring.records = collections.deque(maxlen=capacity)
        _ring.complete_from = float("-inf")
