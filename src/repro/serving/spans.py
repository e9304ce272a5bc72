"""Spans and counters of the serving loop.

``StreamServer.spans`` is one ``Spans``. It holds two things:

* **counters**, plain integer attributes that the loop bumps on every
  call and that are always on: ``chunks_ingested``, ``h2d_bytes``,
  ``score_launches``, ``host_syncs`` (each point where the loop waits on
  the device: the MGNet score pull in the gate, each deferred-prediction
  pull when a session finishes, a timed flush's ``block_until_ready``),
  ``flushes``, ``rows_launched`` and ``rows_real``;
* **spans**, off until ``enable()``. Off, ``span()`` is one attribute
  check that returns a shared no-op context. On, each span opens a
  ``jax.profiler.TraceAnnotation`` of its name (so a profiler session
  records it on the host plane, on the device trace's clock) and appends
  a ``Span`` to a bounded ring that ``dump()`` writes out.

The serving loop's spans (``serving/server.py``): ``serve.call`` (one
``serve()``), ``serve.round`` (one scheduling round), and inside a round
``serve.ingest`` (the chunk's pull and its prefetched host-to-device
put), ``serve.gate`` (the mask cache's walk, MGNet's launch and its score
pull), ``serve.route`` (embed, budget, order and gather launches, the
batcher push) and ``serve.flush`` (place, encode launch, argmax,
bookkeeping); ``serve.finish`` (a session's deferred predictions pulled
to the host) inside ``serve.call``; and ``serve.session``, from a
session's first ingest to its finish, which spans rounds and so is kept
in the ring only. A span's ``id`` is the session's sid, or a flush's
sequence number, whose bucket and owning sids ride along.

Ring times are nanoseconds on the profiler's clock (the host's real-time
clock, as ``time.time_ns``), advanced by the monotonic performance
counter, so durations never run backwards and a ring span lines up with
the trace's annotation of it. A ``.xplane.pb`` stamps its events
relative to the session's ``profile_start_time``.
"""

from __future__ import annotations

import json
import time
from collections import deque
from typing import NamedTuple

import jax

__all__ = ["Span", "Spans"]

COUNTERS = ("chunks_ingested", "h2d_bytes", "score_launches", "host_syncs",
            "flushes", "rows_launched", "rows_real")


class Span(NamedTuple):
    name: str
    parent: str | None     # name of the span it opened inside
    id: int | None         # session sid, or flush sequence number
    start_ns: int
    end_ns: int
    bucket: int | None = None
    owners: tuple | None = None


class _Off:
    """The shared context of a span that records nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Live:
    """A span with a clock: recorded when spans are on; off, a timed
    flush still reads its ``elapsed_s``."""

    __slots__ = ("rec", "name", "id", "bucket", "owners", "parent",
                 "start_ns", "_ann")

    def __init__(self, rec: "Spans", name: str, id, bucket, owners):
        self.rec, self.name, self.id = rec, name, id
        self.bucket, self.owners = bucket, owners
        self._ann = None

    def __enter__(self):
        rec = self.rec
        if rec.on:
            meta = {} if self.id is None else {"id": self.id}
            if self.bucket is not None:
                meta["bucket"] = self.bucket
            self._ann = jax.profiler.TraceAnnotation(self.name, **meta)
            self._ann.__enter__()
            self.parent = rec._stack[-1] if rec._stack else None
            rec._stack.append(self.name)
        self.start_ns = rec.now()
        return self

    def elapsed_s(self) -> float:
        return (self.rec.now() - self.start_ns) * 1e-9

    def __exit__(self, *exc):
        if self._ann is not None:
            end = self.rec.now()
            self.rec._stack.pop()
            self._ann.__exit__(*exc)
            self.rec.ring.append(Span(self.name, self.parent, self.id,
                                      self.start_ns, end, self.bucket,
                                      self.owners))
        return False


class Spans:
    """The serving loop's counters, and its spans when enabled."""

    def __init__(self, capacity: int = 1 << 18):
        self.on = False
        self.ring: deque = deque(maxlen=capacity)
        self._stack: list[str] = []
        self._open: dict = {}            # (name, id) -> start_ns
        self._base_ns = time.time_ns() - time.perf_counter_ns()
        for c in COUNTERS:
            setattr(self, c, 0)

    def now(self) -> int:
        """Nanoseconds on the profiler's clock."""
        return time.perf_counter_ns() + self._base_ns

    def enable(self) -> None:
        self._base_ns = time.time_ns() - time.perf_counter_ns()
        self.on = True

    def disable(self) -> None:
        self.on = False

    def span(self, name: str, id: int | None = None,
             bucket: int | None = None, owners: tuple | None = None,
             clock: bool = False):
        """Context of one span. ``clock=True`` gives one with a start time
        even while spans are off (``elapsed_s()``)."""
        if not self.on and not clock:
            return _OFF
        return _Live(self, name, id, bucket, owners)

    def begin(self, name: str, id: int) -> None:
        """Start a span that outlives the call stack (kept in the ring only,
        by ``end``); a second ``begin`` of the same span keeps the first."""
        if self.on:
            self._open.setdefault((name, id), self.now())

    def end(self, name: str, id: int) -> None:
        if self.on:
            start = self._open.pop((name, id), None)
            if start is not None:
                self.ring.append(Span(name, None, id, start, self.now()))

    def counts(self) -> dict:
        return {c: getattr(self, c) for c in COUNTERS}

    def dump(self, path) -> None:
        """Write the counters and the ring as one JSON object."""
        with open(path, "w") as f:
            json.dump({"clock": "ns, host real-time clock",
                       "counters": self.counts(),
                       "spans": [s._asdict() for s in self.ring]}, f)
