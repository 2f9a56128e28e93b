"""The check that decides ``correct`` for W1A8 YOLOv3, driven on the CPU.

Run by path: ``python -m pytest bench/tests``. As ``test_check.py`` does
for the paper's detector: the harness's run of the backlog traffic
through the program's detector server over the YOLOv3 graph, in interpret
mode and under ``yolov3-w1a8-416``'s own limits, at a size that fits a
test: the configuration's graph at input 64 with every width divided by
4 and one residual block per stage (all three heads, both routes, five
stride-2 convs, five fused shortcuts), a batch width of 4, a pool of 8
frames and a sample of up to 256 answers, which in a 1.5 s window on the
CPU is every answer: a fault that spoils half of each batch then reads
about half of the detections missed (0.50 to 0.52 here), where a sample
of 64 swings around that (0.46 to 0.52). The sound run is correct; each
fault of ``tools/faults.py`` planted in the timed path is not. The
control (the plain reference in 4-bit activations in the program's
place) is read at the configuration's own size, which the reference
alone computes on the CPU in seconds a frame: at input 64 a frame has
252 candidates and the int4 reference's NMS sets stay near the int8
one's (set_miss 0.17 to 0.3 on two seeds), while at 416, with 10647
candidates and the full depth, they read 0.68 to 0.98 (the chip readings
of the limits).
"""
import json
import pathlib
import sys
import time

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402
from bench.core import spec  # noqa: E402
from bench.core.peaks import PEAKS  # noqa: E402
from bench.tools.faults import FAULTS  # noqa: E402
from bench.tools.limits import control_readings  # noqa: E402

SEED = 2 ** 33 + 7          # more than 32 bits, as the check's seeds are
NAME = "yolov3-w1a8-416"


def small(cfg: dict) -> dict:
    from repro.configs import yolov3_w1a8
    from repro.models import yolo
    shape = dict(base=8, blocks=(1, 1, 1, 1, 1), input_size=64)
    graph = yolov3_w1a8.graph(**shape)
    cfg = dict(cfg, input_size=64, base_width=8, blocks=[1, 1, 1, 1, 1],
               graph=yolo.graph_rows(graph), frame_pool=8)
    cfg["serving"] = dict(cfg["serving"], width=4)
    cfg["check"] = dict(cfg["check"], sample=256)
    return cfg


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    real = spec.benchmark()
    path = tmp_path_factory.mktemp("cfg") / "tiny.json"
    path.write_text(json.dumps(small(spec.config(real, NAME))))
    unbound = {g: [{k: v for k, v in m.items() if k != "workloads"}
                   for m in real[g]] for g in ("end_to_end", "per_layer")}
    return {"configs": [{"name": "tiny", "file": str(path)}],
            "workloads": [{"name": "tiny-backlog", "config": "tiny",
                           "traffic": "backlog", "chips": 1}], **unbound}


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


def cpu_run(bench, cache, hook=None):
    res, _ = run.run_cell(bench, "tiny-backlog", SEED, 1.5, False,
                          require_tpu=False, peaks=PEAKS["TPU v5 lite"],
                          cache_dir=cache, t_start=time.perf_counter(),
                          system_hook=hook)
    assert res["attempted"] > 0 and res["metrics"]["img_per_s"]["value"] > 0
    return res


def test_sound_run_is_correct(bench, cache):
    res = cpu_run(bench, cache)
    assert res["correct"], res["check"]
    assert res["failed"] == 0
    assert list(res["check"]) == ["box_err", "set_miss", "failed"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(bench, cache, fault):
    res = cpu_run(bench, cache, FAULTS[fault])
    assert not res["correct"]
    over = [k for k, v in res["check"].items() if v["value"] > v["limit"]]
    assert over, res["check"]


def test_control_fails_the_configuration_limits():
    cfg = spec.config(spec.benchmark(), NAME)
    limits = cfg["check"]["limits"]
    control = control_readings(cfg, SEED, 8)
    assert any(control[k] > limits[k] for k in limits), control
    # the reference against itself reads only the float16 wire's rounding
    same = control_readings(cfg, SEED, 8, act_bits=8)
    assert all(same[k] <= limits[k] for k in limits), same


def test_program_graph_must_be_the_configuration_graph():
    from bench.systems import yolov3
    cfg = small(spec.config(spec.benchmark(), NAME))
    yolov3.program_graph(cfg)
    bad = dict(cfg, graph=cfg["graph"][:-1])
    with pytest.raises(ValueError, match="graph"):
        yolov3.program_graph(bad)
    bad = dict(cfg, fixed_point=dict(cfg["fixed_point"], head_w=[2, 14]))
    with pytest.raises(ValueError, match="head_w"):
        yolov3.program_graph(bad)
