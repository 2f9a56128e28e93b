"""The reduction from a trace to the per-layer metrics (no JAX, no chip).

Run by path: ``python -m pytest bench/tests``. One part uses a small
recorded trace of the 320x320 bundle on a TPU v5 lite
(``data/trace_slice.json``); the rest uses traces built by hand, whose
answers can be worked out on paper.
"""
import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench.core import costs, spec, trace  # noqa: E402
from bench.core.peaks import PEAKS, chip_peaks  # noqa: E402
from bench.run import Run  # noqa: E402

V5E = PEAKS["TPU v5 lite"]
DEV = "/device:TPU:0"


def op(name, opcode, start, dur, dev=DEV):
    return (dev, f"%{name} = f32[8,128]{{1,0:T(8,128)}} {opcode}(...)",
            start, dur)


@pytest.mark.parametrize("text,want", [
    ("%fusion.29 = f32[32,320,320,16]{0,3,2,1:T(8,128)} fusion(f32[32] %a)",
     ("fusion.29", "fusion")),
    ("%_w1a8_conv3x3_pool.4 = u8[32,80,80,32]{3,2,1,0:T(8,128)(4,1)S(1)} "
     "custom-call(u8[32,162,162,16]{3,2,1,0} %pad.22)",
     ("_w1a8_conv3x3_pool.4", "custom-call")),
    ("%while.18 = (s32[]{:T(128)}, f32[32,300]{1,0:T(8,128)S(1)}) "
     "while((s32[], f32[32,300]) %tuple.71), condition=%c",
     ("while.18", "while")),
    ("%copy-start.1 = (f32[3,3,3,16]{3,2,1,0:T(4,128)S(1)}, u32[]{:S(2)}) "
     "copy-start(f32[3,3,3,16] %constant.364)", ("copy-start.1", "copy-start")),
    ("plain name", ("plain name", ""))])
def test_split_hlo_finds_the_opcode_past_tile_layouts(text, want):
    assert trace.split_hlo(text) == want


def test_label_drops_layouts():
    text = ("%copy.73 = f32[32,320,320,16]{3,2,1,0:T(8,128)} "
            "copy(f32[32,320,320,16]{0,3,2,1:T(8,128)} %fusion.29)")
    assert trace.label(text) == "copy.73 copy f32[32,320,320,16]"


def test_busy_is_the_union_of_op_intervals():
    tr = {"ops": [op("a", "fusion", 1.0, 0.5), op("b", "fusion", 1.2, 0.5),
                  op("c", "copy", 2.0, 0.25), op("d", "copy", 9.0, 1.0)],
          "modules": []}
    # [1.0, 1.7] and [2.0, 2.25] inside [0, 3]
    assert trace.busy(tr, 0.0, 3.0) == pytest.approx(0.95)
    # clipped at the window's edges
    assert trace.busy(tr, 1.5, 2.1) == pytest.approx(0.3)
    assert trace.gaps(tr, 0.0, 3.0) == [(0.0, 1.0), (1.7, 2.0), (2.25, 3.0)]


def test_busy_averages_over_devices():
    tr = {"ops": [op("a", "fusion", 0.0, 1.0, "/device:TPU:0"),
                  op("b", "fusion", 0.0, 0.5, "/device:TPU:1")],
          "modules": []}
    assert trace.busy(tr, 0.0, 2.0) == pytest.approx(0.75)


def test_marker_aligns_host_clock():
    tr = {"ops": [], "modules": [(DEV, "jit__bundle(1)", 50.0, 1.0),
                                 (DEV, trace.MARKER + "(7)", 40.00003, 1e-6),
                                 (DEV, trace.MARKER + "(7)", 60.0, 1e-6)]}
    assert trace.offset(tr, 2.0) == pytest.approx(38.00003)
    with pytest.raises(ValueError):
        trace.offset({"ops": [], "modules": []}, 0.0)


def test_idle_gaps_go_to_the_innermost_open_span():
    tr = {"ops": [op("a", "fusion", 0.0, 1.0), op("b", "fusion", 2.0, 1.0),
                  op("c", "fusion", 3.5, 0.5)], "modules": []}
    spans = [("bench.tick", 0.9, 2.2), ("backend.step", 1.0, 0.8),
             ("bench.submit", 3.05, 0.1)]
    # gaps: [1, 2] mid 1.5 in backend.step (inside bench.tick);
    # [3, 3.5] mid 3.25: bench.submit ended at 3.15, tick at 3.1;
    # [4, 5] mid 4.5: nothing open
    got = dict(trace.idle_by_host_state(tr, spans, 0.0, 5.0))
    assert got == pytest.approx({"backend.step": 1.0,
                                 trace.UNTRACED: 1.5})


def test_ops_in_keeps_each_execution_apart():
    tr = {"ops": [op("k1", "custom-call", 0.1, 0.2),
                  op("f1", "fusion", 0.4, 0.1),
                  op("k1", "custom-call", 1.1, 0.3),
                  op("x", "fusion", 0.95, 0.02)],
          "modules": [(DEV, "jit__bundle(3)", 0.0, 0.6),
                      (DEV, "jit__bundle(3)", 1.0, 0.5),
                      (DEV, "jit_other(9)", 0.9, 0.1)]}
    execs = trace.modules_named(tr, "jit__bundle", 0.0, 2.0)
    assert len(execs) == 2
    per = trace.ops_in(tr, execs)
    assert [[(n, o) for n, o, _, _ in ops] for ops in per] == [
        [("k1", "custom-call"), ("f1", "fusion")], [("k1", "custom-call")]]
    # an execution that ends after the window is left out
    assert len(trace.modules_named(tr, "jit__bundle", 0.0, 1.2)) == 1


def test_least_time_takes_the_larger_bound():
    # 393e9 ops: 1 ms of compute; 819e6 bytes: 1 ms of memory
    assert costs.least_seconds(393e9, 1.0, V5E) == pytest.approx(1e-3)
    assert costs.least_seconds(1.0, 819e6, V5E) == pytest.approx(1e-3)
    assert costs.least_seconds(2 * 393e9, 819e6, V5E) == pytest.approx(2e-3)
    with pytest.raises(ValueError):
        chip_peaks("TPU v99")


def config():
    return spec.config(spec.benchmark(), "yolo-w1a8-320")


def test_frame_ops_match_the_paper_stack():
    # every MAC twice, binary ones included: 1.19 GOP at 320, 4.30 at 608
    assert costs.frame_ops(config()) == pytest.approx(1.19e9, rel=0.01)
    assert costs.frame_ops(dict(config(), input_size=608)) == \
        pytest.approx(4.30e9, rel=0.01)


def test_w1a8_calls_are_the_nine_binary_layers():
    cfg = config()
    calls = costs.w1a8_calls(cfg, 32)
    assert [c[0] for c in calls] == [f"conv{i}" for i in range(2, 11)]
    # conv2: 3x3, 16 -> 32 at 160x160, pooled to 80x80
    name, ops, nbytes = calls[0]
    hw = 160 * 160
    assert ops == 32 * (2 * 9 * 16 * 32 * hw + 5 * 32 * hw
                        + 3 * 32 * 80 * 80)
    assert nbytes == 32 * (hw * 16 + 80 * 80 * 32) + 9 * 16 * 32 / 8 \
        + 4 * (16 + 2 * 32)
    # per frame the nine calls hold all but conv1 and the head's MACs
    w1a8 = sum(c[1] for c in costs.w1a8_calls(cfg, 1))
    assert w1a8 == pytest.approx(1.10e9, rel=0.01)


def recorded():
    data = json.loads((HERE / "data" / "trace_slice.json").read_text())
    tr = {"ops": [tuple(o) for o in data["ops"]],
          "modules": [tuple(m) for m in data["modules"]]}
    return tr, tuple(data["window"]), [tuple(s) for s in data["spans"]]


def run_of(tr, window, frames=64):
    cfg = config()
    return Run(trace=tr, trace_window=window, bundle="jit__bundle",
               kernel_calls=costs.w1a8_calls(cfg, 32), peaks=V5E,
               frame_ops=costs.frame_ops(cfg), seconds=1.0, chips=1,
               served_in_window=lambda: np.ones(frames, bool))


def test_recorded_bundle_has_nine_kernels_per_execution():
    tr, (lo, hi), _ = recorded()
    execs = trace.modules_named(tr, "jit__bundle", lo, hi)
    assert len(execs) == 2
    for ops in trace.ops_in(tr, execs):
        kernels = [n for n, o, _, _ in ops if trace.is_kernel(o)]
        assert len(kernels) == 9
        assert kernels[0].startswith("_w1a8_conv3x3_pool")


def test_recorded_readers():
    tr, window, spans = recorded()
    run = run_of(tr, window)
    roof = spec.reader("w1a8_roofline.backlog")(run)
    xla = spec.reader("xla_ms_per_batch.backlog")(run)
    idle = spec.reader("device_idle_frac.backlog")(run)
    step = spec.reader("step_mfu.live")(run)
    # the nine kernels take about 4.3 ms of the 20.3 ms execution; their
    # least time at the 8-bit peak and HBM bandwidth is some tens of us
    assert 0.1 < roof < 5.0
    assert 14.0 < xla < 20.5
    assert 0.0 < idle < 1.0
    # 64 frames over two 20.3 ms executions at 393 TOP/s
    assert step == pytest.approx(100 * 64 * costs.frame_ops(config())
                                 / (0.0406896 * 393e12), rel=0.01)
    top = trace.top_ops(tr, *window, n=3)
    assert top[0][0] == "broadcast_maximum_fusion fusion f32[32,320,320,16]"
    assert top[0][1] > top[1][1] > top[2][1]
    states = dict(trace.idle_by_host_state(tr, spans, *window))
    assert sum(states.values()) == pytest.approx(
        (window[1] - window[0]) - trace.busy(tr, *window))


def test_readers_return_nothing_without_a_trace():
    run = run_of(None, None)
    for name in ("w1a8_roofline.backlog", "xla_ms_per_batch.backlog",
                 "device_idle_frac.live", "step_mfu.live"):
        assert spec.reader(name)(run) is None


def test_roofline_counts_every_kernel_of_an_execution():
    """Least time of the nine calls over all Pallas time of each execution
    that ran any; an execution without a kernel is left out."""
    run = run_of(None, (0.0, 3.0))
    least = sum(costs.least_seconds(o, b, V5E) for _, o, b in
                run.kernel_calls)
    run.trace = {"ops": [op("k1", "custom-call", 0.1, 0.2),
                         op("k2", "custom-call", 0.4, 0.3),
                         op("f", "fusion", 0.8, 0.1),
                         op("g", "fusion", 2.1, 0.1)],
                 "modules": [(DEV, "jit__bundle(1)", 0.0, 1.0),
                             (DEV, "jit__bundle(1)", 2.0, 0.5)]}
    got = spec.reader("w1a8_roofline.backlog")(run)
    assert got == pytest.approx(100 * least / 0.5)
    run.trace["ops"] = run.trace["ops"][2:]
    assert spec.reader("w1a8_roofline.backlog")(run) is None
