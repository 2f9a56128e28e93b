"""The load generator on a fake scheduler and a fake clock (no JAX).

Run by path: ``python -m pytest bench/tests``.
"""
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench.core import loadgen  # noqa: E402


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += max(dt, 1e-9)      # a real sleep always lets time pass


class Result:
    def __init__(self, rid):
        self.rid, self.finish_reason, self.detections = rid, "ok", {"rid": rid}


class FakeScheduler:
    """Answers every request one tick after it is submitted; a tick takes
    ``tick_s`` of the fake clock."""

    def __init__(self, clock, tick_s, sink=None):
        self.clock, self.tick_s, self.sink = clock, tick_s, sink
        self.queue, self.active, self.ticks = [], {}, 0

    def submit(self, req):
        self.queue.append(req)

    def tick(self):
        self.clock.t += self.tick_s
        self.ticks += 1
        done, self.active = self.active, {}
        for rid in done:
            self.sink(Result(rid))
        for rid in self.queue:
            self.active[rid] = True
        self.queue = []


def feeder(traffic, tick_s=0.01, width=4, seed=0):
    clock = Clock()
    sched = FakeScheduler(clock, tick_s)
    d = loadgen.Feeder(sched, lambda rid, frame: rid, 8, traffic, width,
                       np.random.default_rng(seed), clock=clock,
                       sleep=clock.sleep)
    sched.sink = d.on_result
    return d, sched


@pytest.mark.parametrize("streams,fps,seconds", [(1, 30, 10), (5, 30, 2.5),
                                                 (3, 7, 1.0)])
def test_stream_due_times_in_seconds(streams, fps, seconds):
    due = loadgen.stream_due(streams, fps, seconds,
                             np.random.default_rng(3))
    assert np.all(np.diff(due) >= 0) and due.min() >= 0
    assert due.max() < seconds
    # every stream sends fps frames a second, evenly spaced
    assert abs(len(due) - streams * fps * seconds) <= streams
    phases = np.sort(due[:streams])
    assert np.all(phases < 1.0 / fps)
    for p in phases:
        own = due[np.isclose((due - p) * fps, np.round((due - p) * fps))
                  & (due >= p)]
        assert np.allclose(np.diff(own), 1.0 / fps)


def test_stream_phases_follow_the_seed():
    a = loadgen.stream_due(4, 30, 1, np.random.default_rng(1))
    b = loadgen.stream_due(4, 30, 1, np.random.default_rng(1))
    c = loadgen.stream_due(4, 30, 1, np.random.default_rng(2))
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_backlog_keeps_outstanding_topped_up():
    traffic = {"kind": "closed", "outstanding_per_width": 4}
    d, sched = feeder(traffic, tick_s=0.01, width=4)
    log = d.window(1.0)
    outstanding = 16
    due, done = np.asarray(log.due), np.asarray(log.done)
    # at every answer a new frame was submitted: never more than 16 open
    for t in np.unique(done[~np.isnan(done)]):
        open_at = np.sum((due <= t) & ~(done <= t))
        assert open_at <= outstanding
    assert len(due) - np.sum(~np.isnan(done)) == outstanding
    # closed loop: a frame is due when it is submitted
    assert np.array_equal(log.due, log.submit)
    assert d.drain() < 1.0 and not d.busy()
    assert all(o == {"rid": i} for i, o in enumerate(log.output))
    assert log.frame == [i % 8 for i in range(len(log.frame))]


def test_open_loop_times_from_due_not_submit():
    traffic = {"kind": "streams", "streams": 2, "fps": 10}
    d, sched = feeder(traffic, tick_s=0.25)      # slower than arrivals
    log = d.window(2.0)
    d.drain()
    due, sub = np.asarray(log.due), np.asarray(log.submit)
    assert len(due) == 40
    assert np.all(sub >= due - 1e-9) and np.max(sub - due) > 0.1
    assert np.all(np.asarray(log.done) > sub)


def test_open_loop_sleeps_when_idle():
    traffic = {"kind": "streams", "streams": 1, "fps": 30}
    d, sched = feeder(traffic, tick_s=0.001)
    log = d.window(1.0)
    assert len(log.due) == 30
    assert np.max(np.asarray(log.submit) - np.asarray(log.due)) < 1e-6
    assert sched.ticks <= 2 * 30 + 2


@pytest.mark.parametrize("traffic,want", [
    ({"kind": "closed", "outstanding_per_width": 4}, [32]),
    ({"kind": "streams", "streams": 1, "fps": 30}, list(range(1, 33))),
    ({"kind": "streams", "streams": 40, "fps": 30}, list(range(1, 33)))])
def test_warm_shapes(traffic, want):
    assert loadgen.batch_sizes(traffic, 32) == want
