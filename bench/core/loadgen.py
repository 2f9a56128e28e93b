"""The one load generator: it reads a traffic file and drives a scheduler.

A traffic file (``bench/traffic/<name>.json``) is data. Its ``kind``
picks one of two loops:

- ``closed``: ``outstanding_per_width`` x the server's batch width frames
  stay outstanding; each completion is answered at once by a new frame.
  A frame is due when it is submitted.
- ``streams``: ``streams`` cameras, each sending ``fps`` frames a second
  at even spacing from a random phase in [0, 1/fps) drawn from the seed.
  Frames are due on that schedule whether or not the server keeps up
  (open loop); a late submission is counted, not shifted.

Frames are taken from the set-up's pool in request order, cyclically.
Times are seconds on the host's monotonic clock, relative to the start of
the measured window.
"""
from __future__ import annotations

import contextlib
import math
import time

import numpy as np

DRAIN_S = 60.0          # how long past the window an answer is waited for


def stream_due(streams: int, fps: float, seconds: float,
               rng: np.random.Generator) -> np.ndarray:
    """Sorted due times of every frame of ``streams`` even streams that
    falls inside [0, seconds)."""
    period = 1.0 / fps
    phases = rng.uniform(0.0, period, size=streams)
    n = int(math.ceil(seconds * fps)) + 1
    due = (phases[:, None] + period * np.arange(n)[None, :]).ravel()
    return np.sort(due[due < seconds])


def batch_sizes(traffic: dict, width: int) -> list:
    """The dispatch widths this traffic can stage in one tick, which the
    set-up warms: a full batch under backlog; every width up to a full
    batch under streams, since one slow tick lets any number of frames
    queue (a width first met inside the window would compile there)."""
    if traffic["kind"] == "closed":
        return [width]
    return list(range(1, width + 1))


class Spans:
    """Host spans ``(name, start, seconds)`` on the feeder's clock, kept in
    memory; ``spans(name)`` is a context manager that records one."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.items = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        start = self.clock()
        try:
            yield
        finally:
            self.items.append((name, start, self.clock() - start))


class Log:
    """What happened to each request, indexed by request id."""

    def __init__(self):
        self.due, self.submit, self.done = [], [], []
        self.ok, self.frame, self.output = [], [], []
        self.tick_start, self.tick_s = [], []
        self.end_s = 0.0

    def add(self, due: float, submit: float, frame: int) -> int:
        self.due.append(due)
        self.submit.append(submit)
        self.done.append(math.nan)
        self.ok.append(False)
        self.frame.append(frame)
        self.output.append(None)
        return len(self.due) - 1

    def arrays(self) -> dict:
        return {k: np.asarray(getattr(self, k), np.float64)
                for k in ("due", "submit", "done")}


class Feeder:
    """Drives ``sched`` with one traffic file for one window.

    ``request(rid, frame)`` builds a request; ``pool`` is the number of
    frames in the set-up's pool. The feeder marks its own host spans
    (``bench.submit``, ``bench.tick``, ``bench.wait``) in ``self.span``,
    which the system under test shares for its own; ``sched`` may be set
    after construction, once the scheduler is built on that span log."""

    def __init__(self, sched, request, pool: int, traffic: dict,
                 width: int, rng: np.random.Generator,
                 clock=time.perf_counter, sleep=time.sleep):
        self.sched, self.request, self.pool = sched, request, pool
        self.traffic, self.width, self.rng = traffic, width, rng
        self.clock, self.sleep = clock, sleep
        self.span = Spans(clock)
        self.log = Log()
        self.t0 = self.t_end = 0.0
        self.answered = 0

    # the scheduler's result sink
    def on_result(self, res) -> None:
        rid = res.rid
        self.log.done[rid] = self.clock() - self.t0
        self.log.ok[rid] = res.finish_reason == "ok"
        self.log.output[rid] = res.detections
        self.answered += 1

    def _submit(self, due: float) -> None:
        log = self.log
        rid = len(log.due)
        with self.span("bench.submit"):
            req = self.request(rid, rid % self.pool)
            log.add(due, self.clock() - self.t0, rid % self.pool)
            self.sched.submit(req)

    def _tick(self) -> None:
        start = self.clock()
        with self.span("bench.tick"):
            self.sched.tick()
        self.log.tick_start.append(start - self.t0)
        self.log.tick_s.append(self.clock() - start)

    def busy(self) -> bool:
        return bool(self.sched.queue or self.sched.active)

    def window(self, seconds: float) -> Log:
        """Run the measured window; return the log (answers still open)."""
        kind = self.traffic["kind"]
        if kind == "closed":
            self._closed(seconds)
        elif kind == "streams":
            self._streams(seconds)
        else:
            raise ValueError(f"unknown traffic kind {kind!r}")
        self.t_end = self.clock()
        self.log.end_s = seconds
        return self.log

    def _closed(self, seconds: float) -> None:
        outstanding = int(self.traffic["outstanding_per_width"]) * self.width
        self.t0 = self.clock()
        for _ in range(outstanding):
            self._submit(0.0)
        while self.clock() - self.t0 < seconds:
            self._tick()
            for _ in range(outstanding - (len(self.log.due) - self.answered)):
                self._submit(self.clock() - self.t0)

    def _streams(self, seconds: float) -> None:
        due = stream_due(int(self.traffic["streams"]),
                         float(self.traffic["fps"]), seconds, self.rng)
        i, n = 0, len(due)
        self.t0 = self.clock()
        while True:
            now = self.clock() - self.t0
            while i < n and due[i] <= now:      # late ones too, at the close
                self._submit(float(due[i]))
                i += 1
            if now >= seconds:
                break
            if self.busy():
                self._tick()
            else:
                nxt = due[i] if i < n else seconds
                with self.span("bench.wait"):
                    self.sleep(max(0.0, min(nxt, seconds) - now))

    def drain(self) -> float:
        """Tick until every submitted request has answered, or until
        ``DRAIN_S`` past the window; returns the seconds it took."""
        start = self.clock()
        while self.busy() and self.clock() - start < DRAIN_S:
            self.sched.tick()
        return self.clock() - start
