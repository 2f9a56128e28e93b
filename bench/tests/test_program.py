"""The readers of the program's own spans and layer scopes (no JAX, no
chip).

Run by path: ``python -m pytest bench/tests``. Most cases use spans and
traces built by hand, whose answers can be worked out on paper; one uses
a small recorded slice of the 320x320 bundle on a TPU v5 lite
(``data/program_slice.json``) with the program's spans over it.
"""
import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench.core import program, spec, trace  # noqa: E402
from bench.run import Run  # noqa: E402

DEV = "/device:TPU:0"
SCOPES = ("conv1", "conv2", "conv11") + program.POST_SCOPES
READERS = ("stage_ms_per_batch.backlog", "wait_ms_per_batch.backlog",
           "idle_in_stage_frac.backlog", "conv1_ms_per_batch.backlog",
           "queue_ms_p50.cams", "queue_ms_p50.live")


def op(name, opcode, start, dur):
    return (DEV, f"%{name} = f32[8,128]{{1,0:T(8,128)}} {opcode}(...)",
            start, dur)


HLO = """\
HloModule jit__bundle, entry_computation_layout={(f32[32,320,320,3])->u8[]}

%fused_computation.83 (param_0: f32[32,320,320,3]) -> f32[32,320,320,16] {
  %convolution.1 = f32[32,320,320,16]{0,3,2,1} convolution(%param_0), metadata={op_name="jit(_bundle)/conv1/conv_general_dilated" stack_frame_id=65}
}

ENTRY %main (imgs.1: f32[32,320,320,3]) -> u8[] {
  %copy.72 = f32[32,320,320,3]{0,3,2,1:T(4,128)} copy(%imgs.1), metadata={op_name="imgs"}
  %fusion.29 = f32[32,320,320,16]{0,3,2,1:T(8,128)} fusion(%copy.72), kind=kOutput, calls=%fused_computation.83, metadata={op_name="jit(_bundle)/conv1/conv_general_dilated" stack_frame_id=65}
  %w1a8_conv2.1 = u8[32,80,80,32]{3,2,1,0} custom-call(%pad.23), custom_call_target="tpu_custom_call", output_to_operand_aliasing={}, metadata={}}, metadata={op_name="jit(_bundle)/conv2/jit(_w1a8_conv3x3_pool)/w1a8_conv2/pallas_call" stack_frame_id=80}, backend_config={"a":{"b":1}}
  %while.18 = (s32[], f32[32,300]) while(%tuple.71), condition=%c, body=%b, metadata={op_name="jit(_bundle)/jit(postprocess)/nms/vmap()/while" stack_frame_id=156}
  %copy-start.1 = (f32[3,3,3,16], u32[]) copy-start(%constant.365), cross_program_prefetch_index=0
  ROOT %convert.9 = u8[] convert(%x), metadata={op_name="jit(_bundle)/wire/vmap()/convert_element_type"}
}
"""


def test_scope_is_the_first_layer_component_of_op_name():
    assert program.scope_of("jit(_bundle)/conv1/jit(relu)/max",
                            SCOPES) == "conv1"
    assert program.scope_of("jit(_bundle)/jit(postprocess)/decode/exp",
                            SCOPES) == "decode"
    assert program.scope_of("imgs", SCOPES) == program.OTHER
    assert program.scope_of("jit(_bundle)/conv1x/add",
                            SCOPES) == program.OTHER


def test_op_scopes_read_the_compiled_metadata():
    got = program.op_scopes(HLO, SCOPES)
    assert got == {"convolution.1": "conv1", "copy.72": program.OTHER,
                   "fusion.29": "conv1", "w1a8_conv2.1": "conv2",
                   "while.18": "nms", "convert.9": "wire"}


def test_layer_scopes_follow_the_configuration():
    cfg = spec.config(spec.benchmark(), "yolo-w1a8-320")
    assert program.layer_scopes(cfg) == tuple(
        f"conv{i}" for i in range(1, 12)) + ("decode", "nms", "wire")


def synthetic(program_spans=None, op_scopes=None):
    """Two bundle executions, [0, 1] and [2, 2.5], in a window [0, 4]:
    conv1 ops of 0.3 + 0.1 and 0.2 s, a kernel, an unscoped copy; frames
    0..3 due at 0, 1, 2 and 5 s of a 4 s window."""
    tr = {"ops": [op("copy.72", "copy", 0.0, 0.1),
                  op("fusion.29", "fusion", 0.1, 0.3),
                  op("copy.73", "copy", 0.4, 0.1),
                  op("w1a8_conv2.1", "custom-call", 0.5, 0.4),
                  op("fusion.29", "fusion", 2.0, 0.2),
                  op("w1a8_conv2.1", "custom-call", 2.2, 0.3)],
          "modules": [(DEV, "jit__bundle(1)", 0.0, 1.0),
                      (DEV, "jit__bundle(1)", 2.0, 0.5)]}
    run = Run(trace=tr, trace_window=(0.0, 4.0), bundle="jit__bundle",
              seconds=4.0, due=np.array([0.0, 1.0, 2.0, 5.0]))
    if program_spans is not None:
        run.program_spans = program_spans
    if op_scopes is not None:
        run.op_scopes = op_scopes
    return run


SPANS = [("sched.queue", -0.5, 0.5, 0, {}),
         ("detect.stage", 0.9, 0.4, 7, {"n": 32}),     # all idle
         ("detect.dispatch", 1.3, 0.01, 7, {}),
         ("detect.wait", 1.31, 0.02, 6, {}),
         ("sched.queue", 0.1, 1.0, 1, {}),
         ("detect.stage", 1.5, 0.2, 8, {"n": 32}),     # all idle
         ("detect.wait", 2.4, 0.1, 7, {}),
         ("sched.queue", 1.0, 2.0, 2, {}),
         ("detect.stage", 3.9, 0.5, 9, {"n": 4}),      # idle to the close
         ("sched.queue", 4.5, 0.3, 3, {}),              # due after the window
         ("detect.stage", 4.5, 0.3, 10, {"n": 1})]     # after the window


def test_span_readers_on_synthetic_spans():
    run = synthetic(SPANS)
    stage = spec.reader("stage_ms_per_batch.backlog")(run)
    assert stage == pytest.approx(400.0)           # median of 0.4, 0.2, 0.5
    assert spec.reader("wait_ms_per_batch.backlog")(run) == \
        pytest.approx(60.0)                         # median of 0.02, 0.1
    # device idle [0.9, 2] and [2.5, 4]; stage spans [0.9, 1.3], [1.5,
    # 1.7], [3.9, 4.4]: 0.4 + 0.2 + 0.1 idle seconds of 4
    assert spec.reader("idle_in_stage_frac.backlog")(run) == \
        pytest.approx(0.7 / 4.0)
    # requests 0, 1, 2 are due in the window; 3 is not
    for name in ("queue_ms_p50.cams", "queue_ms_p50.live"):
        assert spec.reader(name)(run) == pytest.approx(1000.0)


def test_conv1_per_execution_reads_the_scoped_ops():
    scopes = {"fusion.29": "conv1", "copy.73": "conv1",
              "w1a8_conv2.1": "conv2"}
    run = synthetic(op_scopes=scopes)
    # (0.3 + 0.1 + 0.2) s of conv1 over two executions
    assert spec.reader("conv1_ms_per_batch.backlog")(run) == \
        pytest.approx(300.0)
    per = program.scope_seconds(run.trace, trace.modules_named(
        run.trace, "jit__bundle", 0.0, 4.0), scopes)
    assert per == pytest.approx({"conv1": 0.6, "conv2": 0.7,
                                 program.OTHER: 0.1})
    # a program whose ops carry no conv1 scope reads nothing
    assert spec.reader("conv1_ms_per_batch.backlog")(
        synthetic(op_scopes={"fusion.29": program.OTHER})) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_without_program_records(name):
    """A run of a program that records no spans (or of a harness that does
    not pass them on) carries neither attribute, or empty ones."""
    assert spec.reader(name)(synthetic()) is None
    assert spec.reader(name)(synthetic([], {})) is None
    untraced = synthetic()
    untraced.trace = untraced.trace_window = None
    assert spec.reader(name)(untraced) is None


def test_queue_waits_are_not_host_states():
    tr = {"ops": [op("a", "fusion", 0.0, 1.0), op("b", "fusion", 3.0, 1.0)],
          "modules": []}
    spans = program.host_spans([("sched.tick", 1.0, 1.5, 0, {}),
                                ("detect.stage", 1.1, 0.8, 0, {"n": 1}),
                                ("sched.queue", 1.2, 1.6, 5, {})])
    assert [n for n, _, _ in spans] == ["sched.tick", "detect.stage"]
    # the gap [1, 3]: mid 2.0, after the stage ended and inside the tick
    got = dict(trace.idle_by_host_state(tr, spans, 0.0, 4.0))
    assert got == pytest.approx({"sched.tick": 2.0})


def recorded():
    data = json.loads((HERE / "data" / "program_slice.json").read_text())
    tr = {"ops": [tuple(o) for o in data["ops"]],
          "modules": [tuple(m) for m in data["modules"]]}
    run = Run(trace=tr, trace_window=tuple(data["window"]),
              bundle="jit__bundle", op_scopes=data["op_scopes"],
              program_spans=[tuple(s) for s in data["program_spans"]])
    return run, [tuple(s) for s in data["spans"]]


def test_recorded_slice_maps_every_kernel_to_its_layer():
    run, _ = recorded()
    execs = trace.modules_named(run.trace, run.bundle, *run.trace_window)
    assert len(execs) == 2
    for ops in trace.ops_in(run.trace, execs):
        kernels = [n for n, o, _, _ in ops if trace.is_kernel(o)]
        assert [run.op_scopes[n] for n in kernels] == \
            [f"conv{i}" for i in range(2, 11)]
    per = program.scope_seconds(run.trace, execs, run.op_scopes)
    total = sum(per.values())
    assert per.most_common(1)[0][0] == "conv1"
    assert per[program.OTHER] / total < 0.05
    conv1 = spec.reader("conv1_ms_per_batch.backlog")(run)
    assert conv1 == pytest.approx(1e3 * per["conv1"] / 2)
    assert 10.0 < conv1 < 20.0


def test_recorded_slice_program_spans_split_backend_step():
    run, harness = recorded()
    lo, hi = run.trace_window
    names = {sp[0] for sp in run.program_spans}
    assert {"sched.tick", "detect.stage", "detect.wait"} <= names
    for _, s, d, k, a in program.spans(run, "detect.stage"):
        assert a["n"] == 32 and d > 0
    states = dict(trace.idle_by_host_state(
        run.trace, harness + program.host_spans(run.program_spans), lo, hi))
    assert "backend.step" not in states or \
        states["backend.step"] < max(states.values())
