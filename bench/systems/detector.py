"""The system under test: the program's W1A8 detector server.

Set-up builds what a deployment would, by the recipe of
``models.yolo.build_detector`` (seeded init, range calibration on one
frame, weight packing) run as one jitted call on the device, with the
initial weights scaled by the configuration's ``init`` gains, then a
``serve.DetectionBackend`` with the
configuration's width, dispatch depth, device-NMS wire and kernel
profile, driven by a ``serve.Scheduler``. The timed path is the program's
own: ``Scheduler.tick`` over the backend, whose one jitted bundle holds
conv1 (XLA), conv2-conv10 (W1A8 Pallas kernels), the conv11 head, decode,
NMS and the compact detection wire.

The backend sits behind a thin proxy of the ``Backend`` protocol that
only marks host spans (``backend.admit`` / ``backend.step`` /
``backend.harvest``) in the feeder's span log.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.core import seeds
from bench.core.loadgen import Spans


class SpanProxy:
    """Forwards the ``Backend`` protocol, marking each call as a span."""

    def __init__(self, backend, span):
        self._b, self._span = backend, span
        self.capacity = backend.capacity

    def __getattr__(self, name):          # counters and optional hooks
        return getattr(self._b, name)

    def admit(self, assignments):
        with self._span("backend.admit"):
            return self._b.admit(assignments)

    def step(self):
        with self._span("backend.step"):
            return self._b.step()

    def harvest(self):
        with self._span("backend.harvest"):
            return self._b.harvest()

    def release(self, slot):
        return self._b.release(slot)


def frames(cfg: dict, seed: int) -> np.ndarray:
    """The frame pool: uint8 frames drawn from the seed, on the host."""
    s = int(cfg["input_size"])
    return seeds.rng(seed, "frames").integers(
        0, 256, (int(cfg["frame_pool"]), s, s, 3), np.uint8)


def _check_stack(cfg: dict, program_layers) -> None:
    got = [[s.name, s.kind, s.cin, s.cout, s.ksize, s.pool]
           for s in program_layers]
    if got != [list(r) for r in cfg["layers"]]:
        raise ValueError(f"the program's layer stack {got} is not the "
                         f"configuration's {cfg['layers']}")


class System:
    """One built detector server for a configuration and seed."""

    program_name = "jit__bundle"     # the served bundle's name in a trace

    def __init__(self, cfg: dict, seed: int, pool: np.ndarray):
        from repro.models import yolo
        from repro.serve import DetectionBackend, Scheduler, ServeRequest
        _check_stack(cfg, yolo.YOLO_LAYERS)
        self.cfg, self.pool = cfg, pool
        self._Scheduler, self._Request = Scheduler, ServeRequest
        size = int(cfg["input_size"])
        serving = cfg["serving"]
        self.width = int(serving["width"])

        init = cfg["init"]
        last = yolo.YOLO_LAYERS[-1].name

        def build(key, calib):
            params = yolo.init_yolo_params(key)
            for name, p in params.items():
                p["w"] = p["w"] * init["head_gain" if name == last
                                       else "gain"]
            params = yolo.calibrate_yolo(params, calib)
            return [{k: v for k, v in e.items() if k != "spec"}
                    for e in yolo.deploy_yolo_kernel(params)["layers"]]

        calib = jnp.asarray(pool[:1], jnp.float32) / 256.0
        arrays = jax.block_until_ready(
            jax.jit(build)(seeds.jax_key(seed, "weights"), calib))
        art = {"layers": [{"spec": spec, **a} for spec, a in
                          zip(yolo.YOLO_LAYERS, arrays)],
               "buckets": (size,)}
        nms = cfg["nms"]
        self.backend = DetectionBackend(
            art, slots=self.width, depth=int(serving["depth"]),
            profile=serving["profile"], device_nms=True, buckets=(size,),
            iou_thresh=nms["iou_thresh"], score_thresh=nms["score_thresh"],
            max_out=nms["max_out"])

    def scheduler(self, sink, span):
        """A scheduler over the backend, its calls marked with ``span``."""
        return self._Scheduler(SpanProxy(self.backend, span),
                               result_sink=sink)

    def request(self, rid: int, frame: int):
        return self._Request(rid=rid, image=self.pool[frame])

    def warm(self, sizes) -> None:
        """Compile the bundle, then serve one batch of every width in
        ``sizes`` through the scheduler, so that the host path's shapes
        are compiled too."""
        self.backend.warmup()
        for k in sizes:
            sched = self.scheduler(lambda res: None, Spans())
            for rid in range(k):
                sched.submit(self.request(rid, rid % len(self.pool)))
            while sched.queue or sched.active:
                sched.tick()

    def dispatches(self) -> int:
        return int(self.backend.host_syncs)

    @staticmethod
    def served(output: dict) -> dict:
        """A result's detection payload, as the comparison reads it."""
        return {k: output[k] for k in ("boxes", "scores", "classes",
                                       "valid")}

    def close(self) -> None:
        self.backend = None
