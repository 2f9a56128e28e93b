"""In-memory host spans of the serving path.

A `Recorder` keeps ``(name, start, dur, key, attrs)`` records on
``time.perf_counter``; ``key`` ties the spans of one unit of work together
(a tick number, a dispatch number, a request id) and ``attrs`` holds small
facts about it (``n`` frames). `Scheduler` and `DetectionBackend` each hold
one as their ``tracer`` attribute, set after construction where wanted;
the default is `NULL`, which records nothing and reads no clock. There is
no writer: whoever set the recorder reads ``items``.

Spans of one tick:

- ``sched.tick`` (tick number): the whole `Scheduler.tick`;
- ``sched.admit`` (tick number): expiry, heap pops, the batched admit;
- ``sched.queue`` (request id): one per admitted request, from `submit`
  to its admission;
- ``detect.stage`` (dispatch number, ``n`` real frames, ``bytes`` sent to
  the device): host stack, one upload, pad;
- ``detect.dispatch`` (dispatch number): the bundle's async enqueue;
- ``detect.wait`` (dispatch number of the batch fetched): the host
  blocked in ``jax.device_get``;
- ``detect.unpack`` (dispatch number): building the per-frame payloads;
- ``sched.harvest`` (tick number): emission ingest and finishes, result
  callbacks included.
"""
from __future__ import annotations

import contextlib
import time

_NOTHING = contextlib.nullcontext()


class Recorder:
    """Records spans in memory, in the order they end."""

    enabled = True

    def __init__(self):
        self.clock = time.perf_counter
        self.items = []

    @contextlib.contextmanager
    def span(self, name: str, key=None, **attrs):
        start = self.clock()
        try:
            yield
        finally:
            self.items.append((name, start, self.clock() - start, key,
                               attrs))

    def add(self, name: str, start: float, end: float, key=None) -> None:
        """A span whose start was read earlier (a request's queue wait)."""
        self.items.append((name, start, end - start, key, {}))


class _Null:
    """The shared recorder that records nothing and reads no clock."""

    enabled = False
    items = ()

    def span(self, name: str, key=None, **attrs):
        return _NOTHING


NULL = _Null()
