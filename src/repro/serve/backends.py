"""The two `serve.api.Backend` implementations.

`LMBackend` — autoregressive decode over the stage-stacked LM params: one
fused `decode_step` per tick for every pool row, batched multi-row prefill
at admission (requests arriving together prefill as one batch per prompt
length, then scatter into the pool via `cache.merge_rows`), per-row
temperature sampling. Two termination paths:

  * host-checked (default): the sampled token row syncs to the host every
    tick and the scheduler applies stop-token / max_new per emission;
  * ``done_mask=True``: the fused step (`engine.decode_step_donemask`)
    samples, appends to a device-side token buffer and folds the
    stop-token + max_new tests into a per-slot ``done`` bitmask — the only
    per-tick device→host read. Token sequences sync once, in bulk, when a
    slot finishes. Token-for-token equivalent to the host path (same
    sampler expressions, same PRNG-key discipline).

`DetectionBackend` — the paper's deployed workload: batched image requests
through the packed-W1A8 Pallas conv path + head decode + NMS, bundled into
ONE fixed-width jitted dispatch per resolution bucket. With ``depth=K`` the
backend keeps a K-deep in-flight dispatch window, generalizing how the FPGA
pipeline overlaps line-buffered conv with ingest: tick t's batch is
*dispatched* asynchronously and harvested up to K-1 ticks later — strictly
in dispatch order even when K>2 executables are in flight (completion
reordering via `DispatchWindow`) — so admission (host-side image staging,
slot assignment) and the next K-1 dispatches overlap device compute. The
slot pool widens (capacity = (K-1+buckets)·width, admit_width =
buckets·width) so full batches can stage while others are in flight —
steady state stays one batch per bucket per tick.
"""
from __future__ import annotations

import collections
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.config import _UNSET
from repro.models.layers import ModelConfig
from repro.serve import cache as cache_mod
from repro.serve import tracing
from repro.serve.api import Emission, ServeRequest
from repro.serve.engine import decode_step, decode_step_donemask, prefill

# DetectionBackend's legacy kernel kwargs warn exactly once per process
# (the ServeEngine pattern); tests reset this to re-arm the warning.
_detect_kwargs_warned = False


def _warn_detect_kwargs_once() -> None:
    global _detect_kwargs_warned
    if _detect_kwargs_warned:
        return
    _detect_kwargs_warned = True
    import warnings
    warnings.warn(
        "DetectionBackend(interpret=/fuse_pool=) is deprecated; pass "
        "profile='tuned'|'default'|'interpret' instead",
        DeprecationWarning, stacklevel=3)


# The retired overlap flag warns exactly once per process (same pattern);
# tests reset this to re-arm the warning.
_detect_overlap_warned = False


def _warn_detect_overlap_once() -> None:
    global _detect_overlap_warned
    if _detect_overlap_warned:
        return
    _detect_overlap_warned = True
    import warnings
    warnings.warn(
        "DetectionBackend(overlap=) is deprecated; pass depth=K instead "
        "(overlap=True maps to depth=2, overlap=False to depth=1)",
        DeprecationWarning, stacklevel=3)


class DispatchWindow:
    """K-deep in-flight dispatch window with completion reordering.

    Batches push in dispatch order (each push takes a monotonically
    increasing ticket) and pop strictly in that order — an executable that
    finishes early still waits behind older in-flight work, so results
    surface to the scheduler in dispatch order regardless of completion
    order. `pop_due` implements the two-rule harvest schedule shared with
    the pure-python oracle in tests/test_serve_kdeep.py:

      * depth rule — after a tick's dispatches, at most ``depth - 1``
        batches stay resident; the oldest surplus batches block (harvest)
        now. depth=1 is single-shot (dispatch and block same tick);
        depth=2 is the classic double buffer.
      * drain rule — a tick that dispatched nothing harvests exactly one
        resident batch, so a drained queue surfaces trailing results one
        batch per tick (the double buffer's +1 drain tick, generalized).
    """

    def __init__(self, depth: int):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self.depth = int(depth)
        self._q: collections.deque = collections.deque()
        self._tickets = 0
        self._harvested = 0

    def __len__(self) -> int:
        return len(self._q)

    @property
    def tickets(self) -> int:
        """Tickets issued so far: the next push takes this one."""
        return self._tickets

    def push(self, item) -> int:
        ticket = self._tickets
        self._tickets += 1
        self._q.append((ticket, item))
        return ticket

    def pop_due(self, *, pushed: bool) -> list:
        due = []
        if not pushed and self._q:                 # drain rule
            due.append(self._pop())
        while len(self._q) >= self.depth:          # depth rule
            due.append(self._pop())
        return due

    def _pop(self):
        ticket, item = self._q.popleft()
        assert ticket == self._harvested, \
            "harvest must follow dispatch order"
        self._harvested = ticket + 1
        return item


class LMBackend:
    """Slot-pool LM decode backend (capacity = pool batch B)."""

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_len: int = 256, mode: str = "float", seed: int = 17,
                 done_mask: bool = False, max_stop_tokens: int = 4):
        self.cfg, self.params = cfg, params
        self.capacity, self.max_len, self.mode = slots, max_len, mode
        self.done_mask = done_mask
        self.cache = cache_mod.init_cache(cfg, slots, max_len)
        self.last_tok = jnp.zeros((slots,), jnp.int32)
        self.temp = np.zeros((slots,), np.float32)
        self._active = np.zeros((slots,), bool)
        self._emissions: Dict[int, List[Emission]] = collections.defaultdict(
            list)
        self._key = jax.random.PRNGKey(seed)
        self.host_syncs = 0          # per-tick step/harvest-path transfers
        self.host_sync_bytes = 0     # bytes over those transfers
        self.completion_syncs = 0    # bulk token fetches (done-mask path)
        if done_mask:
            self.max_stop_tokens = max_stop_tokens
            # device-side decode state (DESIGN.md §11 wire format)
            self.tok_buf = jnp.zeros((slots, max_len), jnp.int32)
            self.n_gen = jnp.zeros((slots,), jnp.int32)
            self.done = jnp.ones((slots,), bool)       # vacant rows are done
            # host mirrors — derivable from the admission record plus the
            # done-mask reads, so tracking them costs no extra transfers
            self._n_host = np.zeros((slots,), np.int64)
            self._done_host = np.ones((slots,), bool)
            self._stops_host: Dict[int, Tuple[int, ...]] = {}
            self._max_new_host = np.zeros((slots,), np.int64)
            self._stops_pad = np.full((slots, max_stop_tokens), -1, np.int32)
            self._step_done = jax.jit(
                lambda p, c, lt, tb, ng, dn, st, mn, t, k, use_key:
                decode_step_donemask(cfg, p, c, lt, tb, ng, dn, st, mn, t, k,
                                     mode=mode, use_key=use_key),
                static_argnums=(10,))
        else:
            self._step = jax.jit(lambda p, c, t: decode_step(cfg, p, c, t,
                                                             mode=mode))

    # -- admission: batched multi-row prefill --------------------------------
    def admit(self, assignments: Sequence[Tuple[int, ServeRequest]]) -> None:
        by_len: Dict[int, list] = collections.defaultdict(list)
        for slot, req in assignments:
            by_len[len(req.prompt)].append((slot, req))
            self.temp[slot] = req.sampling.temperature
        for group in by_len.values():
            rows = [slot for slot, _ in group]
            prompts = jnp.asarray([list(r.prompt) for _, r in group],
                                  jnp.int32)
            logits, cache1 = prefill(self.cfg, self.params, prompts,
                                     max_len=self.max_len, mode=self.mode)
            self.cache = cache_mod.merge_rows(self.cache, cache1, rows)
            first = self._sample(logits, np.asarray(
                [r.sampling.temperature for _, r in group], np.float32))
            for i, (slot, req) in enumerate(group):
                tok = int(first[i])
                self.last_tok = self.last_tok.at[slot].set(tok)
                self._active[slot] = True
                if self.done_mask:
                    self._admit_done_mask(slot, req, tok)
                else:
                    self._emissions[slot].append(
                        Emission(kind="token", payload=tok))

    def _admit_done_mask(self, slot: int, req: ServeRequest,
                         tok: int) -> None:
        """Seed the device-side decode state for one admitted row. The
        prefill token is sampled host-side (shared path with host-checked
        mode), so its stop test runs here and folds into the initial done
        bit — a stop token in position 1 finishes the request this tick."""
        sp = req.sampling
        stops = tuple(sp.stop_tokens)
        if len(stops) > self.max_stop_tokens:
            raise ValueError(f"request {req.rid}: {len(stops)} stop tokens "
                             f"> backend cap {self.max_stop_tokens}")
        if sp.max_new > self.max_len:
            raise ValueError(f"request {req.rid}: max_new {sp.max_new} "
                             f"exceeds the device token buffer "
                             f"(max_len={self.max_len})")
        done0 = (tok in stops) or (1 >= sp.max_new)
        self.tok_buf = self.tok_buf.at[slot, 0].set(tok)
        self.n_gen = self.n_gen.at[slot].set(1)
        self.done = self.done.at[slot].set(done0)
        self._n_host[slot] = 1
        self._done_host[slot] = done0
        self._stops_host[slot] = stops
        self._max_new_host[slot] = sp.max_new
        self._stops_pad[slot] = -1
        self._stops_pad[slot, :len(stops)] = stops

    # -- one fused decode tick -----------------------------------------------
    def step(self) -> None:
        if not self._active.any():
            return
        if self.done_mask:
            self._step_done_mask()
            return
        logits, self.cache = self._step(self.params, self.cache,
                                        self.last_tok[:, None])
        nxt = self._sample(logits, self.temp)          # token-row host sync
        self.host_syncs += 1
        self.host_sync_bytes += 4 * self.capacity      # (B,) int32 tokens
        self.last_tok = jnp.asarray(nxt, jnp.int32)
        for slot in np.flatnonzero(self._active):
            self._emissions[int(slot)].append(
                Emission(kind="token", payload=int(nxt[slot])))

    def _step_done_mask(self) -> None:
        use_key = bool((self.temp > 0).any())          # same rule as _sample
        if use_key:
            self._key, k = jax.random.split(self._key)
        else:
            k = self._key                              # traced but unused
        (self.cache, self.last_tok, self.tok_buf, self.n_gen,
         self.done) = self._step_done(
            self.params, self.cache, self.last_tok, self.tok_buf, self.n_gen,
            self.done, jnp.asarray(self._stops_pad),
            jnp.asarray(self._max_new_host, jnp.int32),
            jnp.asarray(self.temp), k, use_key)
        # rows live at dispatch grew by one token (mirrors device n_gen)
        self._n_host += (self._active & ~self._done_host)

    def harvest(self) -> Dict[int, List[Emission]]:
        if not self.done_mask:
            out = dict(self._emissions)
            self._emissions = collections.defaultdict(list)
            return out
        out: Dict[int, List[Emission]] = {}
        if not self._active.any():
            return out
        done_np = np.asarray(self.done)          # THE per-tick bitmask read
        self.host_syncs += 1
        self.host_sync_bytes += self.capacity    # (B,) bool bitmask
        newly = done_np & self._active
        self._done_host = done_np.copy()
        if newly.any():
            rows = np.flatnonzero(newly)
            toks = np.asarray(self.tok_buf[jnp.asarray(rows)])  # one gather
            self.completion_syncs += 1
            for i, slot in enumerate(rows):
                slot = int(slot)
                n = int(self._n_host[slot])
                seq = tuple(int(t) for t in toks[i, :n])
                reason = ("stop" if seq and seq[-1]
                          in self._stops_host.get(slot, ()) else "length")
                out[slot] = [Emission(kind="tokens", payload=seq,
                                      finish=reason, final=True)]
        return out

    def release(self, slot: int) -> None:
        self._active[slot] = False
        self.temp[slot] = 0.0        # stale temp would force sampling forever
        self._emissions.pop(slot, None)
        if self.done_mask:
            self.done = self.done.at[slot].set(True)
            self._done_host[slot] = True
            self._stops_host.pop(slot, None)

    # per-row temperature: greedy rows take argmax, sampled rows categorical
    def _sample(self, logits, temp) -> np.ndarray:
        greedy = jnp.argmax(logits, -1)
        t = np.asarray(temp, np.float32)
        if not (t > 0).any():
            return np.asarray(greedy, np.int32)
        self._key, k = jax.random.split(self._key)
        scaled = logits / jnp.maximum(jnp.asarray(t), 1e-6)[:, None]
        sampled = jax.random.categorical(k, scaled, -1)
        return np.asarray(jnp.where(jnp.asarray(t) > 0, sampled, greedy),
                          np.int32)


class DetectionBackend:
    """Packed-W1A8 YOLO detection backend (one image per request).

    ``art`` is a `models.yolo.deploy_yolo_kernel` artifact of any detector
    graph (``art["graph"]``; the paper's model where it has none); images
    are (S, S, 3) float in [0, 1] or uint8 raw pixels (divided by 256, the
    Q0.8 convention), where S is one of the configured resolution
    ``buckets`` (default: the artifact's buckets, else the graph's input
    side). Emissions carry NMS'd detections plus the raw head (a tuple of
    heads for a graph with several) for verification against the float
    reference (core.verify).

    The forward (Pallas convs → head decode → NMS) is ONE jitted dispatch
    at a fixed batch width (= ``slots``) **per bucket** — all buckets share
    the packed weights and the jit cache holds one fixed-width executable
    per image size, the way `spawn()` shares one executable across
    replicas. A dispatch stages its frames as one host stack and one
    host→device transfer, in uint8 when every frame is uint8 (the bundle
    converts to float first thing) and else in float32 converted on the
    host (``float_stages`` counts those); ``stage_bytes`` counts the bytes
    sent. Only the real frames cross; a partial batch zero-pads on the
    device so every tick reuses the same executable. ``depth=K`` keeps up
    to K dispatches in flight, harvested strictly in dispatch order (see
    module docstring / `DispatchWindow`); ``depth=2`` is the retired
    ``overlap=True`` double buffer.

    Kernel launch configuration comes from ``profile``
    (`models.yolo.PROFILES`): ``"tuned"`` — the serving default — resolves
    per-layer winners from the committed autotune table (which is where
    ``fuse_pool=True`` became the default for pool layers, it wins on the
    table); ``"interpret"`` reproduces the historical heuristic/interpret
    behavior; ``"default"`` is heuristics with backend-resolved compile
    mode. The old raw kernel kwargs (``interpret=``, ``fuse_pool=``)
    survive one release behind a DeprecationWarning: they select
    ``"default"`` and override its matching field.

    ``device_nms=True`` changes the emission wire, not the math: the NMS
    always runs inside the one executable, but the default wire still ships
    the raw (G, G, 75) f32 head alongside it for verification. Device-NMS
    mode ships only the final compact detection set per image — fp16 boxes
    (max_out, 4) + fp16 scores + int8 classes + one int32 valid-count
    (`models.detection.compact_detections`) — cutting the per-dispatch
    device→host payload ~56× for the default head geometry.

    Host-sync accounting: the per-dispatch payload is STATIC (fixed-width
    executable per bucket ⇒ `jax.eval_shape` at construction), so syncs and
    bytes are credited at the tick that *dispatches* a batch, not the tick
    whose harvest happens to block on it. K-deep mode therefore shows the
    same per-tick byte attribution as single-shot (its extra drain ticks
    cost 0) instead of charging tick t with an older tick's bytes.
    """

    def __init__(self, art: dict, *, slots: int = 4, profile: str = None,
                 depth: Optional[int] = None, overlap=_UNSET,
                 device_nms: bool = False,
                 buckets: Optional[Sequence[int]] = None,
                 iou_thresh: float = 0.45, score_thresh: float = 0.25,
                 max_out: int = 50, interpret=_UNSET, fuse_pool=_UNSET):
        from repro.models import detection, yolo
        overrides = {}
        if interpret is not _UNSET or fuse_pool is not _UNSET:
            if profile is not None:
                raise TypeError("pass either profile= or the legacy "
                                "interpret=/fuse_pool= kwargs, not both")
            _warn_detect_kwargs_once()
            profile = "default"      # heuristics; interpret only if asked
            if interpret is not _UNSET:
                overrides["interpret"] = interpret
            if fuse_pool is not _UNSET:
                overrides["fuse_pool"] = fuse_pool
        if overlap is not _UNSET:
            if depth is not None:
                raise TypeError("pass either depth= or the legacy overlap= "
                                "flag, not both")
            _warn_detect_overlap_once()
            depth = 2 if overlap else 1
        if depth is None:
            depth = 1
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if profile is None:
            profile = "tuned"
        if profile not in yolo.PROFILES:
            raise ValueError(
                f"profile must be one of {yolo.PROFILES}, got {profile!r}")
        graph = yolo.art_graph(art)
        if buckets is None:
            buckets = art.get("buckets") or (graph.input_size,)
        self.buckets = tuple(dict.fromkeys(int(b) for b in buckets))
        for b in self.buckets:
            if b <= 0 or b % 32:
                raise ValueError(f"bucket sizes must be positive multiples "
                                 f"of 32 (5 pools), got {b}")
            yolo.node_sides(graph, b)
        self.art = art
        self.width = slots                        # device batch per dispatch
        self.depth = depth                        # K-deep dispatch window
        self.capacity = (depth - 1 + len(self.buckets)) * slots
        self.admit_width = len(self.buckets) * slots
        self.bucket_admit_width = slots           # per-bucket page per tick
        self.profile = profile
        self.device_nms = device_nms
        self.post = dict(iou_thresh=iou_thresh, score_thresh=score_thresh,
                         max_out=max_out)
        # per-bucket staging (insertion-ordered: dispatch order is the
        # order buckets first staged this tick)
        self._staged: Dict[int, List[Tuple[int, ServeRequest]]] = {}
        self._window = DispatchWindow(depth)
        self._emissions: Dict[int, List[Emission]] = {}
        self.host_syncs = 0
        self.host_sync_bytes = 0
        self.completion_syncs = 0
        self.stage_bytes = 0                      # host->device image bytes
        self.float_stages = 0                     # dispatches staged as f32
        self.tracer = tracing.NULL                # host spans, when set

        def _bundle(imgs):
            # frames cross the wire as uint8; pixels / 256 is exact (a
            # power-of-two scale), so the head matches host-side conversion
            if imgs.dtype == jnp.uint8:
                imgs = imgs.astype(jnp.float32) / 256.0
            heads = yolo.graph_forward_kernel(art, imgs, profile=profile,
                                              **overrides)
            raw = heads[0] if len(heads) == 1 else heads
            boxes, scores, classes = detection.postprocess(
                heads, anchors=graph.head_anchors(), **self.post)
            if device_nms:                        # compact emission wire only
                with jax.named_scope("wire"):
                    return jax.vmap(detection.compact_detections)(
                        boxes, scores, classes)
            return raw, boxes, scores, classes

        # ONE jit, traced once per bucket shape: the jit cache is the
        # per-bucket executable table, and every executable closes over the
        # same packed weights (no per-bucket model fork)
        self._fwd = jax.jit(_bundle)
        # the dispatch payload is static — one fixed-width executable per
        # bucket — so its byte cost is known without transferring anything
        self._batch_bytes = {
            b: sum(int(np.prod(o.shape)) * o.dtype.itemsize
                   for o in jax.tree_util.tree_leaves(jax.eval_shape(
                       self._fwd, jax.ShapeDtypeStruct(
                           (self.width, b, b, 3), jnp.uint8))))
            for b in self.buckets}

    def spawn(self, *, depth: Optional[int] = None) -> "DetectionBackend":
        """Fresh replica of this backend for the fleet router: independent
        slot/emission/sync state, SHARING the compiled fixed-width
        executable (the program is stateless; the pool is not). One
        warmup() on the template covers every spawned replica, so router
        scale-up costs no recompile. ``depth`` re-sizes the replica's
        dispatch window (and slot pool) without recompiling — how the
        BENCH_serve K-saturation sweep reuses one executable across K."""
        import copy
        twin = copy.copy(self)
        if depth is not None:
            if depth < 1:
                raise ValueError(f"depth must be >= 1, got {depth}")
            twin.depth = int(depth)
            twin.capacity = (twin.depth - 1 + len(self.buckets)) * self.width
        twin._staged = {}
        twin._window = DispatchWindow(twin.depth)
        twin._emissions = {}
        twin.host_syncs = 0
        twin.host_sync_bytes = 0
        twin.completion_syncs = 0
        twin.stage_bytes = 0
        twin.float_stages = 0
        twin.tracer = tracing.NULL
        return twin

    def bucket_of(self, req: ServeRequest) -> int:
        """Resolution bucket (= image side S) for a request — the scheduler
        packs per-bucket batches off this, the router depth-accounts on it.
        Reads only the static `image_shape`, never the pixels."""
        shape = getattr(req, "image_shape", None)
        if shape is None and req.image is not None:
            shape = np.shape(req.image)
        if not shape:
            raise ValueError(f"request {req.rid}: detection needs an image")
        size = int(shape[0])
        if size not in self._batch_bytes:
            raise ValueError(
                f"request {req.rid}: image size {size} matches no "
                f"configured bucket {self.buckets}")
        return size

    def lower(self, bucket: int, *, sharding=None):
        """AOT-lower one bucket's fixed-width bundle (``.compile()`` it to
        inspect the executable a serving tick runs). ``sharding`` places
        the image batch, e.g. on a described device with no chip attached.
        """
        return self._fwd.lower(jax.ShapeDtypeStruct(
            (self.width, bucket, bucket, 3), jnp.uint8, sharding=sharding))

    def warmup(self) -> None:
        """Compile + run every bucket's fixed-width bundle once, on the
        uint8 wire that serving stages, so serving ticks (and the per-K
        comparison in BENCH_serve) exclude trace time."""
        for b in self.buckets:
            z = jnp.zeros((self.width, b, b, 3), jnp.uint8)
            jax.block_until_ready(self._fwd(z))

    def admit(self, assignments: Sequence[Tuple[int, ServeRequest]]) -> None:
        for slot, req in assignments:
            self._staged.setdefault(self.bucket_of(req), []).append(
                (slot, req))

    def step(self) -> None:
        staged, self._staged = self._staged, {}
        pushed = 0
        for bucket, group in staged.items():
            number = self._window.tickets         # this dispatch's ticket
            images = [r.image for _, r in group]
            wire = (np.uint8 if all(getattr(im, "dtype", None) == np.uint8
                                    for im in images) else np.float32)
            nbytes = len(group) * bucket * bucket * 3 * np.dtype(wire).itemsize
            with self.tracer.span("detect.stage", number, n=len(group),
                                  bytes=nbytes):
                # one host stack, one upload of the real frames only
                imgs = jax.device_put(self._host_batch(images, wire))
                if imgs.shape[0] < self.width:   # fixed-width executable
                    imgs = jnp.pad(imgs, ((0, self.width - imgs.shape[0]),
                                          (0, 0), (0, 0), (0, 0)))
            self.stage_bytes += nbytes
            self.float_stages += wire is np.float32
            with self.tracer.span("detect.dispatch", number):
                results = self._fwd(imgs)         # async dispatch
            self._window.push((number, [slot for slot, _ in group], results))
            pushed += 1
            # credit the transfer to the tick that dispatched the batch —
            # the payload width is static, the harvest tick is a schedule
            # detail (a K-deep window blocks up to K-1 ticks later; the
            # bytes are the same)
            self.host_syncs += 1
            self.host_sync_bytes += self._batch_bytes[bucket]
        # harvest in dispatch order: everything beyond depth-1 resident
        # batches, or one batch on a drain (no-dispatch) tick
        for inflight in self._window.pop_due(pushed=bool(pushed)):
            self._emit(inflight)

    def _emit(self, inflight: tuple) -> None:
        number, slots_, results = inflight
        with self.tracer.span("detect.wait", number):
            fetched = jax.device_get(results)     # one transfer
        with self.tracer.span("detect.unpack", number):
            self._unpack(slots_, fetched)

    def _unpack(self, slots_: list, fetched: tuple) -> None:
        if self.device_nms:
            boxes, scores, classes, valid = fetched
            for i, slot in enumerate(slots_):
                # upcast host-side (lossless); the fp16/int8 forms above are
                # what crossed the wire and what _batch_bytes counted
                payload = {"boxes": np.asarray(boxes[i], np.float32),
                           "scores": np.asarray(scores[i], np.float32),
                           "classes": np.asarray(classes[i], np.int32),
                           "valid": int(valid[i])}
                self._emissions.setdefault(slot, []).append(
                    Emission(kind="detections", payload=payload, final=True))
            return
        raw, boxes, scores, classes = fetched
        for i, slot in enumerate(slots_):
            payload = {"boxes": np.asarray(boxes[i]),
                       "scores": np.asarray(scores[i]),
                       "classes": np.asarray(classes[i]),
                       "raw": (tuple(np.asarray(r[i]) for r in raw)
                               if isinstance(raw, tuple)
                               else np.asarray(raw[i]))}
            self._emissions.setdefault(slot, []).append(
                Emission(kind="raw_head", payload=payload, final=True))

    def harvest(self) -> Dict[int, List[Emission]]:
        out, self._emissions = self._emissions, {}
        return out

    def release(self, slot: int) -> None:
        self._emissions.pop(slot, None)

    @staticmethod
    def _host_batch(images, wire) -> np.ndarray:
        """The frames stacked on the host in the wire dtype: uint8 as they
        are (the bundle converts them), else float32 with uint8 frames
        converted as the bundle would."""
        if wire is np.uint8:
            return np.stack(images)
        return np.stack([
            np.asarray(im, np.float32) / np.float32(256)
            if getattr(im, "dtype", None) == np.uint8
            else np.asarray(im, np.float32) for im in images])
