"""Host staging and spans of the detector server (serve/tracing.py): a few
width-4 batches at 64x64 through Scheduler over DetectionBackend, kernels
in interpret mode. Staging sends uint8 frames as one transfer and converts
them inside the bundle; the served results stay bit-identical to the
bundle run on host-converted float32 frames."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import detection, yolo
from repro.serve import DetectionBackend, Scheduler, ServeRequest, tracing

SIZE, WIDTH, FRAMES = 64, 4, 10          # dispatches of 4, 4 and 2 frames


@pytest.fixture(scope="module")
def art():
    rng = np.random.default_rng(0)
    calib = jnp.asarray(rng.integers(0, 256, (1, SIZE, SIZE, 3), np.uint8),
                        jnp.float32) / 256.0
    _, art = yolo.build_detector(jax.random.PRNGKey(3), calib)
    return art


def serve(art, recorder=None):
    """Serve FRAMES frames at depth 2; returns (scheduler, results)."""
    backend = DetectionBackend(art, slots=WIDTH, depth=2,
                               profile="interpret", device_nms=True)
    sched = Scheduler(backend)
    if recorder is not None:
        sched.tracer = backend.tracer = recorder
    rng = np.random.default_rng(1)
    reqs = [ServeRequest(rid=100 + i, image=rng.integers(
        0, 256, (SIZE, SIZE, 3), np.uint8)) for i in range(FRAMES)]
    return sched, sched.run(reqs)


@pytest.fixture(scope="module")
def traced(art):
    rec = tracing.Recorder()
    sched, results = serve(art, rec)
    return rec, sched, results


def by_name(rec) -> dict:
    out = {}
    for name, start, dur, key, attrs in rec.items:
        out.setdefault(name, []).append((start, dur, key, attrs))
    return out


def test_null_recorder_records_nothing_and_reads_no_clock(art, monkeypatch):
    calls = []
    fake = types.SimpleNamespace(
        perf_counter=lambda: calls.append(1) or float(len(calls)))
    monkeypatch.setattr(tracing, "time", fake)
    sched, results = serve(art)
    assert len(results) == FRAMES
    assert sched.tracer is tracing.NULL
    assert sched.backend.tracer is tracing.NULL
    assert list(tracing.NULL.items) == [] and not sched._submitted_at
    assert calls == []
    # the same patch is seen by a recorder: the null one made no call
    rec = tracing.Recorder()
    with rec.span("x", 1):
        pass
    assert len(calls) == 2 and rec.items == [("x", 1.0, 1.0, 1, {})]


def test_every_dispatch_has_its_spans_inside_a_tick(traced):
    rec, sched, results = traced
    assert sorted(r.rid for r in results) == [100 + i for i in range(FRAMES)]
    spans = by_name(rec)
    ticks = spans["sched.tick"]
    assert [k for _, _, k, _ in ticks] == list(range(len(ticks)))
    stage = {k: a["n"] for _, _, k, a in spans["detect.stage"]}
    assert stage == {0: 4, 1: 4, 2: 2}
    # only the real frames cross, as uint8
    assert {k: a["bytes"] for _, _, k, a in spans["detect.stage"]} == \
        {k: n * SIZE * SIZE * 3 for k, n in stage.items()}
    assert sched.backend.stage_bytes == FRAMES * SIZE * SIZE * 3
    assert sched.backend.float_stages == 0
    for name in ("detect.stage", "detect.dispatch", "detect.wait",
                 "detect.unpack"):
        keys = sorted(k for _, _, k, _ in spans[name])
        assert keys == [0, 1, 2], name
        for start, dur, _, _ in spans[name]:
            assert dur >= 0
            assert any(s <= start and start + dur <= s + d
                       for s, d, _, _ in ticks), name
    # spans of one dispatch in order: staged, enqueued, then fetched
    first = {name: {k: s for s, _, k, _ in spans[name]}
             for name in ("detect.stage", "detect.dispatch", "detect.wait")}
    for k in range(3):
        assert first["detect.stage"][k] <= first["detect.dispatch"][k] \
            <= first["detect.wait"][k]
    for name in ("sched.admit", "sched.harvest"):
        assert sorted(k for _, _, k, _ in spans[name]) == \
            [k for _, _, k, _ in ticks]


def test_every_request_has_one_queue_span(traced):
    rec, _, results = traced
    queue = by_name(rec)["sched.queue"]
    assert sorted(k for _, _, k, _ in queue) == sorted(r.rid for r in results)
    assert all(d >= 0 for _, d, _, _ in queue)
    # the last two frames wait for a free slot across ticks
    assert max(d for _, d, _, _ in queue) > min(d for _, d, _, _ in queue)


def test_spawn_gives_a_replica_the_null_recorder(art):
    backend = DetectionBackend(art, slots=WIDTH, profile="interpret")
    backend.tracer = tracing.Recorder()
    assert backend.spawn().tracer is tracing.NULL


def test_spawn_resets_the_staging_counters(traced):
    _, sched, _ = traced
    backend = sched.backend
    assert backend.stage_bytes > 0
    backend.float_stages = 3
    twin = backend.spawn()
    assert twin.stage_bytes == 0 and twin.float_stages == 0
    assert backend.stage_bytes == FRAMES * SIZE * SIZE * 3


def float_reference(art, frames, post):
    """The bundle as it ran on float32 input: the head and its NMS, jitted,
    on the frames converted on the host (uint8 / 256) and zero-padded to
    the width."""
    batch = np.zeros((WIDTH, SIZE, SIZE, 3), np.float32)
    for i, f in enumerate(frames):
        batch[i] = (np.asarray(f, np.float32) / np.float32(256)
                    if f.dtype == np.uint8 else f)

    def bundle(imgs):
        raw = yolo.yolo_forward_kernel(art, imgs, profile="interpret")
        return (raw, *detection.postprocess(raw, **post))
    return [np.asarray(x) for x in jax.jit(bundle)(jnp.asarray(batch))]


def serve_raw(art, frames):
    """Serve ``frames`` on the raw-head wire; (backend, {rid: detections})."""
    backend = DetectionBackend(art, slots=WIDTH, profile="interpret")
    results = Scheduler(backend).run(
        [ServeRequest(rid=i, image=f) for i, f in enumerate(frames)])
    return backend, {r.rid: r.detections for r in results}


def assert_bit_identical(got, ref, rows):
    for i, rid in enumerate(rows):
        for j, key in enumerate(("raw", "boxes", "scores", "classes")):
            np.testing.assert_array_equal(got[rid][key], ref[j][i],
                                          err_msg=f"{key} of {rid}")


@pytest.mark.parametrize("n", [WIDTH, 2])
def test_uint8_frames_serve_bit_identical_to_host_conversion(art, n):
    """A full and a partial batch of uint8 frames: converted inside the
    bundle, the head and detections equal the float32 bundle's bit for
    bit."""
    rng = np.random.default_rng(5)
    frames = list(rng.integers(0, 256, (n, SIZE, SIZE, 3), np.uint8))
    backend, got = serve_raw(art, frames)
    assert backend.float_stages == 0
    assert backend.stage_bytes == n * SIZE * SIZE * 3
    assert_bit_identical(got, float_reference(art, frames, backend.post),
                         range(n))


def test_mixed_group_stages_float32_on_the_host(art):
    """A group holding a float frame goes as one float32 transfer, its
    uint8 frames converted on the host, with the same results."""
    rng = np.random.default_rng(6)
    frames = list(rng.integers(0, 256, (2, SIZE, SIZE, 3), np.uint8))
    frames.insert(1, rng.random((SIZE, SIZE, 3), np.float32))
    backend, got = serve_raw(art, frames)
    assert backend.float_stages == 1
    assert backend.stage_bytes == 3 * SIZE * SIZE * 3 * 4
    assert_bit_identical(got, float_reference(art, frames, backend.post),
                         range(3))


def test_uint8_serving_reuses_the_warmed_executable(art):
    """warmup() compiles one executable per bucket; uint8 ticks of width 1
    and the full width reuse it (a second compile would cost set-up)."""
    backend = DetectionBackend(art, slots=WIDTH, profile="interpret",
                               buckets=(32, SIZE), device_nms=True)
    backend.warmup()
    assert backend._fwd._cache_size() == 2
    rng = np.random.default_rng(7)
    sched = Scheduler(backend)
    for n in (1, WIDTH):
        for size in (32, SIZE):
            sched.run([ServeRequest(rid=100 * n + size + i,
                                    image=rng.integers(
                                        0, 256, (size, size, 3), np.uint8))
                       for i in range(n)])
    assert backend._fwd._cache_size() == 2
    assert backend.float_stages == 0
