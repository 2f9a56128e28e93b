"""The readers of a graph configuration's per-layer metrics (no JAX, no
chip): ``yolov3_mfu``, ``w1a8_graph_roofline``, ``w1a8_res_roofline``,
``w1a8_s2_roofline`` and the counts of ``core/costs_graph.py`` they read.

Run by path: ``python -m pytest bench/tests``. The synthetic cases can be
worked out on paper; one case reads a recorded execution of the served
W1A8 YOLOv3-416 bundle on a TPU v5 lite (``data/yolov3_slice.json``).
"""
import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]

from bench.core import costs, costs_graph, program, spec, trace  # noqa: E402
from bench.core.peaks import PEAKS  # noqa: E402
from bench.run import Run  # noqa: E402

DEV = "/device:TPU:0"
CFG = spec.config(spec.benchmark(), "yolov3-w1a8-416")
PEAK = PEAKS["TPU v5 lite"]
ROOFLINES = ("w1a8_graph_roofline.backlog", "w1a8_res_roofline.backlog",
             "w1a8_s2_roofline.backlog")


def op(name, opcode, start, dur):
    return (DEV, f"%{name} = u8[8,128]{{1,0:T(8,128)}} {opcode}(...)",
            start, dur)


def make_run(ops, modules, window=(0.0, 4.0)):
    return Run(trace={"ops": ops, "modules": modules}, trace_window=window,
               bundle="jit__bundle", cfg=CFG, width=32, peaks=PEAK,
               seconds=4.0, chips=1, due=np.zeros(3),
               done=np.array([1.0, 2.0, 5.0]), ok=np.ones(3, bool))


def least(name):
    calls = {c[0]: c for c in costs_graph.w1a8_calls(CFG, 32)}
    _, ops, nbytes, _ = calls[name]
    return costs.least_seconds(ops, nbytes, PEAK)


def test_counts_follow_the_graph():
    calls = costs_graph.w1a8_calls(CFG, 32)
    assert len(calls) == 71
    assert sum(c[3]["res"] for c in calls) == 23
    assert [c[0] for c in calls if c[3]["s2"]] == \
        ["conv2", "conv5", "conv10", "conv27", "conv44"]
    # every MAC twice: 2 x 32.7 G MACs a frame, and the epilogues
    assert 65.4e9 < costs_graph.frame_ops(CFG) < 65.7e9
    # conv4, a residual 3x3 conv at 208x208: 32 -> 64 channels
    name, ops, nbytes, kind = calls[2]
    hw = 208 * 208
    assert name == "conv4" and kind == {"res": True, "s2": False}
    assert ops == 32 * (2 * 9 * 32 * 64 * hw + 32 * hw + 3 * 64 * hw
                        + 2 * 64 * hw)
    assert nbytes == 32 * (hw * 32 + 2 * hw * 64) + 9 * 32 * 64 / 8 \
        + 4 * (32 + 3 * 64)
    # conv2, stride 2 from 416: its input plane, a quarter of the outputs
    name, ops, nbytes, kind = calls[0]
    assert kind == {"res": False, "s2": True}
    assert ops == 32 * (2 * 9 * 32 * 64 * hw + 32 * 416 * 416 + 3 * 64 * hw)
    # a layout that paper's chain counters see as empty
    assert costs.frame_ops(CFG) == 0 and costs.w1a8_calls(CFG, 32) == []


def test_calls_are_matched_by_their_exact_name():
    """``w1a8_conv4.1`` is conv4's call (a residual block's last conv);
    ``w1a8_conv44.3`` is conv44's (stride 2), not conv4's; ``w1a8_conv4x.1``
    and an XLA op are nobody's."""
    ops = [op("w1a8_conv4.1", "custom-call", 0.0, 0.5),
           op("w1a8_conv44.3", "custom-call", 0.5, 0.25),
           op("w1a8_conv4x.1", "custom-call", 0.75, 0.1),
           op("fusion.7", "fusion", 0.85, 0.15)]
    run = make_run(ops, [(DEV, "jit__bundle(1)", 0.0, 1.0)])
    res = spec.reader("w1a8_res_roofline.backlog")(run)
    assert res == pytest.approx(100 * least("conv4") / 0.5)
    s2 = spec.reader("w1a8_s2_roofline.backlog")(run)
    assert s2 == pytest.approx(100 * least("conv44") / 0.25)
    graph = spec.reader("w1a8_graph_roofline.backlog")(run)
    assert graph == pytest.approx(
        100 * (least("conv4") + least("conv44")) / 0.75)
    # executions outside the window, or of another program, read nothing
    for modules in ([(DEV, "jit__bundle(1)", 3.5, 1.0)],
                    [(DEV, "jit_other(1)", 0.0, 1.0)]):
        for name in ROOFLINES:
            assert spec.reader(name)(make_run(ops, modules)) is None


@pytest.mark.parametrize("name", ROOFLINES)
def test_rooflines_read_nothing_without_a_trace(name):
    run = make_run([], [])
    assert spec.reader(name)(run) is None
    run.trace = run.trace_window = None
    assert spec.reader(name)(run) is None


def test_mfu_counts_frames_answered_in_the_window():
    run = make_run([], [])
    # two frames answered by the window's close (4 s), one after it
    want = 100 * 2 * costs_graph.frame_ops(CFG) / (4.0 * PEAK["peak_ops_int8"])
    assert spec.reader("yolov3_mfu.backlog")(run) == pytest.approx(want)


def recorded():
    data = json.loads((HERE / "data" / "yolov3_slice.json").read_text())
    run = make_run([tuple(o) for o in data["ops"]],
                   [tuple(m) for m in data["modules"]],
                   tuple(data["window"]))
    return run, data["op_scopes"]


def test_recorded_execution_holds_every_named_call_once():
    run, scopes = recorded()
    execs = trace.modules_named(run.trace, run.bundle, *run.trace_window)
    assert len(execs) == 1
    [ops] = trace.ops_in(run.trace, execs)
    named = [n.split(".", 1)[0] for n, o, _, _ in ops
             if trace.is_kernel(o) and n.startswith("w1a8_")]
    assert sorted(named) == sorted(f"w1a8_{c[0]}" for c in
                                   costs_graph.w1a8_calls(CFG, 32))
    # each roofline is its calls' least time over their device time
    spent = {n.split(".", 1)[0]: d for n, o, _, d in ops
             if n.startswith("w1a8_")}
    calls = costs_graph.w1a8_calls(CFG, 32)
    for reader, keep in zip(ROOFLINES, (lambda k: True, lambda k: k["res"],
                                        lambda k: k["s2"])):
        pick = [c for c in calls if keep(c[3])]
        want = 100 * sum(least(c[0]) for c in pick) / sum(
            spent[f"w1a8_{c[0]}"] for c in pick)
        got = spec.reader(reader)(run)
        assert got == pytest.approx(want) and 0 < got < 100
    # the rest of the execution is XLA's: the first conv, the im2col
    # views, the heads, decode and NMS
    xla = sum(d for _, o, _, d in ops if not trace.is_kernel(o))
    assert spec.reader("xla_ms_per_batch.yolov3")(run) == \
        pytest.approx(1e3 * xla)


def test_recorded_execution_maps_kernels_to_stage_scopes():
    from bench.tools.graph_trace import stage_scopes
    run, scopes = recorded()
    stages = stage_scopes(CFG)
    assert stages[:8] == ("backbone.s1", "backbone.s2", "backbone.s3",
                          "backbone.s4", "backbone.s5", "neck.13", "neck.26",
                          "neck.52") and stages[8:] == program.POST_SCOPES
    assert scopes["w1a8_conv2.1"] == "backbone.s1"
    assert scopes["w1a8_conv5.1"] == "backbone.s2"
    assert scopes["w1a8_conv53.1"] == "neck.13"
    per = program.scope_seconds(run.trace, trace.modules_named(
        run.trace, run.bundle, *run.trace_window), scopes)
    assert set(per) <= set(stages) | {program.OTHER}
    assert per.most_common(1)[0][0] == "backbone.s1"
    assert per["nms"] > 0 and per["decode"] > 0
