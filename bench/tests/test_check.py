"""The check that decides ``correct``, driven on the CPU.

Run by path: ``python -m pytest bench/tests``. Each run skips the
harness's look for a chip and drives the rest of a run of the backlog
traffic (every dispatch full) through the program's detector server, in
interpret mode, at the configuration's own input size and under its own
limits, with a batch width of 4 and a pool of 8 frames so that it fits
a test: once as it is and once with each fault of ``tools/faults.py``
planted in the timed path, and reads ``correct``. The control, the plain
reference in 4-bit activations put in the program's place, has to fail
the same limits on the same size of frame.
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

SEED = 2 ** 33 + 5          # more than 32 bits, as the check's seeds are


def small(cfg: dict) -> dict:
    cfg = dict(cfg, frame_pool=8)
    cfg["serving"] = dict(cfg["serving"], width=4)
    cfg["check"] = dict(cfg["check"], sample=8)
    return cfg


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    real = spec.benchmark()
    cfg = small(spec.config(real, "yolo-w1a8-320"))
    path = tmp_path_factory.mktemp("cfg") / "tiny.json"
    path.write_text(json.dumps(cfg))
    unbound = {g: [{k: v for k, v in m.items() if k != "workloads"}
                   for m in real[g]] for g in ("end_to_end", "per_layer")}
    return {"configs": [{"name": "tiny", "file": str(path)}],
            "workloads": [{"name": "tiny-backlog", "config": "tiny",
                           "traffic": "backlog", "chips": 1}], **unbound}


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("jax_cache")


def cpu_run(bench, cache, hook=None):
    res, lines = run.run_cell(bench, "tiny-backlog", SEED, 1.5, False,
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
    cfg = spec.config(spec.benchmark(), "yolo-w1a8-320")
    limits = cfg["check"]["limits"]
    control = control_readings(cfg, SEED, 16)
    assert any(control[k] > limits[k] for k in limits), control
    # the reference against itself reads only the float16 wire's rounding
    same = control_readings(cfg, SEED, 16, act_bits=8)
    assert all(same[k] <= limits[k] for k in limits), same
